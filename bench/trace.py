"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle share,
per-op device time and the longest idle gaps.

What the reduction reads, as the TPU runtime writes it:

* a plane ``/device:TPU:<i>`` per chip, whose line ``XLA Ops`` holds one
  event per executed HLO op (its name is the op's HLO text, e.g.
  ``%fusion.1 = f32[2056]{0} fusion(...)``) and whose line ``XLA Modules``
  holds one event per executed program;
* the plane ``/host:CPU``, whose events include the benchmark's own spans:
  ``jax.profiler.TraceAnnotation`` names that start with ``bench.``.  The
  span ``bench.window`` bounds the measured window; device events outside it
  are clipped away.

Host and device events share one clock (nanoseconds from the start of the
profile).  Busy time is the union of a chip's op intervals inside the
window, averaged over the chips that ran any op; the idle share is 1 minus
busy over the window.  Each idle gap of the first busy chip is put down to
the benchmark span name whose spans (merged per name, whichever thread ran
them) overlap it most, or to ``none``.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10  # entries of each breakdown list


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class TraceSummary:
    window: Event
    ops: dict[str, list[Event]]  # device plane name -> ops clipped to the window
    modules: dict[str, list[Event]]  # device plane name -> programs clipped to it
    spans: list[Event]  # the benchmark's host spans, the window excluded

    @property
    def window_s(self) -> float:
        return self.window.seconds

    def busy_intervals(self, plane: str) -> list[tuple[float, float]]:
        return _union((e.start_ns, e.end_ns) for e in self.ops[plane])

    @property
    def busy_planes(self) -> list[str]:
        return sorted(p for p, evs in self.ops.items() if evs)

    @property
    def busy_s(self) -> float:
        planes = self.busy_planes
        if not planes:
            return 0.0
        total = sum(sum(b - a for a, b in self.busy_intervals(p)) for p in planes)
        return total * 1e-9 / len(planes)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match) -> float:
        """Device seconds of the ops whose HLO text satisfies ``match``,
        summed over chips."""
        return sum(e.seconds for evs in self.ops.values() for e in evs if match(e.name))

    def module_seconds(self, match) -> float:
        """Device seconds of the programs whose name satisfies ``match``."""
        return sum(e.seconds for evs in self.modules.values() for e in evs if match(e.name))

    def top_ops(self, n: int = TOP) -> list[list]:
        """``[[op, seconds], ...]``: the ops that took most device time,
        by :func:`op_label`, summed over chips."""
        total: dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for e in evs:
                total[op_label(e.name)] += e.seconds
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self) -> list[tuple[str, float]]:
        """Every idle gap of the first busy chip inside the window, as
        ``(span it is put down to, seconds)``, longest first."""
        planes = self.busy_planes
        if not planes:
            return [(self.attribute(self.window.start_ns, self.window.end_ns), self.window_s)]
        out = []
        t = self.window.start_ns
        for a, b in self.busy_intervals(planes[0]) + [(self.window.end_ns, self.window.end_ns)]:
            if a > t:
                out.append((self.attribute(t, a), (a - t) * 1e-9))
            t = max(t, b)
        return sorted(out, key=lambda g: -g[1])

    @functools.cached_property
    def _span_unions(self) -> dict[str, tuple[np.ndarray, np.ndarray]]:
        """Per span name, the union of its spans as sorted disjoint
        ``(starts, ends)``: what the host was doing, whichever thread."""
        by_name: dict[str, list] = defaultdict(list)
        for sp in self.spans:
            by_name[sp.name].append((sp.start_ns, sp.end_ns))
        out = {}
        for name, intervals in sorted(by_name.items()):
            merged = np.asarray(_union(intervals), np.float64)
            out[name] = (merged[:, 0], merged[:, 1])
        return out

    def attribute(self, start_ns: float, end_ns: float) -> str:
        """The benchmark span name whose spans overlap ``[start, end)`` most."""
        best, most = "none", 0.0
        for name, (starts, ends) in self._span_unions.items():
            i = np.searchsorted(ends, start_ns, side="right")
            j = np.searchsorted(starts, end_ns, side="left")
            if j <= i:
                continue
            o = float((np.minimum(ends[i:j], end_ns) - np.maximum(starts[i:j], start_ns)).sum())
            if o > most:
                best, most = name, o
        return best

    def idle_by_span(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for name, sec in self.gaps():
            out[name] += sec
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(),
                "idle_gaps": [[n, s] for n, s in self.gaps()[:TOP]]}


_LABEL = re.compile(r"^\S+ = \(?[a-z0-9]+\[[^\]]*\]")


def op_label(hlo_text: str) -> str:
    """A short label for an op event: its HLO name and (first) result type,
    e.g. ``%fusion.1 = f32[2056]``; the layout and operands are dropped."""
    m = _LABEL.match(hlo_text)
    return m.group(0) if m else hlo_text[:60]


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(ev, lo: float, hi: float):
    a, b = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
    return Event(ev.name, a, b) if b > a else None


def reduce(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    spans, window = [], None
    device_lines: dict[str, dict[str, list]] = {}
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    e = Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = e if window is None else window
                    else:
                        spans.append(e)
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            device_lines[plane.name] = lines
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not device_lines:
        raise ValueError(f"the trace holds no {DEVICE_PREFIX}* plane")
    lo, hi = window.start_ns, window.end_ns
    ops, modules = {}, {}
    for plane, lines in device_lines.items():
        ops[plane] = [e for e in (_clip(ev, lo, hi) for ev in lines.get(OPS_LINE, [])) if e]
        modules[plane] = [e for e in (_clip(ev, lo, hi) for ev in lines.get(MODULES_LINE, []))
                          if e]
    spans = [s for s in spans if s.end_ns > lo and s.start_ns < hi]
    return TraceSummary(window=window, ops=ops, modules=modules, spans=spans)


def load(log_dir: str) -> TraceSummary:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return reduce(ProfileData.from_file(paths[0]))

"""The program's own spans and counters (``repro.core.tracing``) in a run's
window, for the per-layer metrics that read them.

The program keeps them in a process-wide ring on the ``time.perf_counter``
clock; the run's window is ``[run.t0, run.t1)`` on the same clock.  A
program without that module (an older commit) has nothing to read, and
every helper here returns ``None`` for it, as for a run with no iterations.

:func:`clock_offset` maps that clock onto the device trace's: it anchors
``run.t0`` on the start of the ``bench.window`` trace span, pairs each of
the window's ``serve`` benchmark spans (``run.spans``, perf_counter) with
the ``bench.serve`` trace span that starts nearest its anchored start, and
takes the median of the paired offsets.  The spread of those offsets is the
alignment's residual; over :data:`MAX_RESIDUAL_NS` the mapping is refused.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench.trace import _union

SERVE = "serve"  # the driver's span around GraphServer.serve
MAX_RESIDUAL_NS = 1e6


def records(run, name: str) -> Optional[list[tuple[str, float, float]]]:
    """The program's entries ``name`` that start in the run's window, or
    ``None`` (no iterations, no tracing module, or a window the ring has
    partly dropped)."""
    if not run.iterations:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.records(name, run.t0, run.t1)


def span_ms_per_spmv(run, name: str) -> Optional[float]:
    """Summed duration of span ``name`` in the window per SpMV, in ms."""
    recs = records(run, name)
    if not recs:
        return None
    return sum(b - a for _, a, b in recs) * 1e3 / run.iterations


def count_per_spmv(run, name: str) -> Optional[float]:
    """Summed amount of counter ``name`` in the window per SpMV."""
    recs = records(run, name)
    if not recs:
        return None
    return sum(n for _, _, n in recs) / run.iterations


def clock_offset(run) -> Optional[tuple[float, float]]:
    """``(offset_ns, residual_ns)`` with ``trace_ns = t * 1e9 + offset_ns``
    for a perf_counter time ``t``; ``None`` without a trace, without paired
    serve spans, or when the pairs disagree by more than
    :data:`MAX_RESIDUAL_NS`."""
    if run.trace is None:
        return None
    host = np.array(sorted(a for a, _ in run.spans.records.get(SERVE, ())
                           if run.t0 <= a < run.t1), np.float64) * 1e9
    dev = np.array(sorted(s.start_ns for s in run.trace.spans
                          if s.name == "bench." + SERVE), np.float64)
    if not host.size or not dev.size:
        return None
    anchored = host + (run.trace.window.start_ns - run.t0 * 1e9)
    j = np.searchsorted(dev, anchored)
    before, after = np.clip(j - 1, 0, dev.size - 1), np.clip(j, 0, dev.size - 1)
    nearest = np.where(np.abs(dev[before] - anchored) <= np.abs(dev[after] - anchored),
                       before, after)
    offsets = dev[nearest] - host
    residual = float(offsets.max() - offsets.min())
    if residual > MAX_RESIDUAL_NS:
        return None
    return float(np.median(offsets)), residual


def idle_inside(run, name: str) -> Optional[float]:
    """Device-idle nanoseconds of the first busy chip that fall inside
    span ``name``, mapped onto the trace clock and clipped to the window."""
    recs = records(run, name)
    aligned = clock_offset(run)
    if not recs or aligned is None:
        return None
    offset, _ = aligned
    w = run.trace.window
    spans = np.clip(np.array([(a, b) for _, a, b in recs]) * 1e9 + offset, w.start_ns, w.end_ns)
    spans = np.asarray(_union(map(tuple, spans)), np.float64)
    inside = float((spans[:, 1] - spans[:, 0]).sum())
    planes = run.trace.busy_planes
    if not planes:
        return inside
    busy = np.asarray(run.trace.busy_intervals(planes[0]), np.float64).reshape(-1, 2)
    starts, ends = busy[:, 0], busy[:, 1]
    covered = 0.0
    for a, b in spans:
        i = np.searchsorted(ends, a, side="right")
        k = np.searchsorted(starts, b, side="left")
        if k > i:
            covered += float((np.minimum(ends[i:k], b) - np.maximum(starts[i:k], a)).sum())
    return inside - covered

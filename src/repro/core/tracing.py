"""Spans and counters on the serve path, kept in one bounded ring.

Every span is two things at once:

* a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profile
  (Perfetto, TensorBoard) shows it on the host plane beside the device's
  ops, on the device trace's clock;
* an entry ``(name, t_start, t_end)`` on the ``time.perf_counter`` clock in
  a process-wide ring of :data:`RING_SIZE` entries, from which
  :func:`records` returns a window and :func:`snapshot` lifetime totals.

Counters (:func:`count`) add ``(name, t, n)`` entries to the same ring.
Everything is always on: the profiler's own gate decides whether an
annotation is recorded, and a span costs a few microseconds either way.  A
process that has not imported JAX runs no profiler, so its spans skip the
annotation and this module never imports JAX itself.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Optional

import numpy as np

__all__ = ["RING_SIZE", "Ring", "count", "interval", "records", "snapshot", "span"]

# A 51 s window at 1,000 requests/s with ~10 entries each fits.
RING_SIZE = 1 << 19


class Ring:
    """A bounded ring of ``(name, a, b)`` entries — a span's start and end,
    or a counter's time and amount — plus per-name lifetime totals."""

    def __init__(self, size: int = RING_SIZE) -> None:
        self.size = int(size)
        self._name_ids: dict[str, int] = {}
        self._ids = np.empty(self.size, np.int32)
        self._a = np.empty(self.size, np.float64)
        self._b = np.empty(self.size, np.float64)
        self._n = 0  # entries ever added
        self._lost = -np.inf  # latest ``a`` among overwritten entries
        self._totals: dict[str, list] = {}
        self._lock = threading.Lock()

    def add(self, name: str, a: float, b: float, amount: float) -> None:
        """Append ``(name, a, b)``; ``amount`` adds to the name's total."""
        with self._lock:
            nid = self._name_ids.get(name)
            if nid is None:
                nid = self._name_ids[name] = len(self._name_ids)
                self._totals[name] = [0, 0.0]
            i = self._n % self.size
            if self._n >= self.size:
                self._lost = max(self._lost, float(self._a[i]))
            self._ids[i] = nid
            self._a[i] = a
            self._b[i] = b
            self._n += 1
            tot = self._totals[name]
            tot[0] += 1
            tot[1] += amount

    def records(self, name: str, lo: float, hi: float) -> Optional[list[tuple[str, float, float]]]:
        """Entries ``name`` whose ``a`` lies in ``[lo, hi)``, ordered by
        ``a``; ``None`` when an entry at or after ``lo`` was overwritten,
        so the window is no longer whole."""
        with self._lock:
            if self._lost >= lo:
                return None
            nid = self._name_ids.get(name)
            if nid is None:
                return []
            n = min(self._n, self.size)
            a = self._a[:n]
            hit = (self._ids[:n] == nid) & (a >= lo) & (a < hi)
            a, b = a[hit], self._b[:n][hit]
        order = np.argsort(a, kind="stable")
        return [(name, x, y) for x, y in zip(a[order].tolist(), b[order].tolist())]

    def snapshot(self) -> dict[str, dict]:
        """``{name: {"count", "total"}}`` since the ring was made: seconds
        for a span, the summed amount for a counter."""
        with self._lock:
            return {k: {"count": c, "total": t} for k, (c, t) in sorted(self._totals.items())}


_RING = Ring()


class span:
    """``with span("plan.resolve"): ...`` — time the block into the ring
    and, while a profiler runs, annotate it as ``repro.plan.resolve``."""

    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        self._annotation = None
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation("repro." + self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _RING.add(self.name, self._t0, t1, t1 - self._t0)


def interval(name: str, t_start: float, t_end: float) -> None:
    """Record a span that was timed elsewhere (a queue wait, measured when
    it ends); it carries no profiler annotation."""
    _RING.add(name, t_start, t_end, t_end - t_start)


def count(name: str, n: float) -> None:
    """Add ``n`` to counter ``name`` (an entry ``(name, now, n)``)."""
    _RING.add(name, time.perf_counter(), n, n)


def records(name: str, lo: float, hi: float) -> Optional[list[tuple[str, float, float]]]:
    """The process ring's entries ``name`` that start in ``[lo, hi)``; see
    :meth:`Ring.records`."""
    return _RING.records(name, lo, hi)


def snapshot() -> dict[str, dict]:
    """Lifetime ``{name: {"count", "total"}}`` of the process ring."""
    return _RING.snapshot()

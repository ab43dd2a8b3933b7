"""Resolve a cell of ``BENCHMARK.json`` to its files, run it once, and build
the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name:

* ``configs[i].file`` — the deployment's sizes and service settings, and
  under ``tiny`` the overrides that CPU tests apply to them (:func:`tiny`);
  chip runs never read ``tiny``;
* ``bench/traffic/<traffic>.json`` — the traffic mix's parameters; its
  ``driver`` key names the general generator in ``bench/drivers/`` that
  reads them;
* ``bench/metrics/<metric>.py`` — one reader per metric, end-to-end and
  per-layer alike: ``read(run) -> float | None``.

A driver module defines ``Driver(cell, spans)`` with ``setup()``,
``prepare(seed, seconds)``, ``measure(seconds, run)``, ``collect(run)``
(host copies of the :class:`Answer` s to compare), ``close()`` (frees the
program's state) and ``after_trace(run, log)``; see ``bench/drivers/cg.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import sys
import tempfile
import threading
import time
from collections import defaultdict
from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

from bench import reference

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TINY_ITERATIONS_PER_SET = 10


# ---------------------------------------------------------------------------
# Resolution: names in BENCHMARK.json -> files under bench/
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any  # the driver module
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path = ROOT


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def reports(metric: dict, cell: str) -> bool:
    """True when ``cell`` reports ``metric`` (no ``workloads`` key: every cell)."""
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def metric_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "metrics" / f"{name}.py"


def resolve(bench: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, driver and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"], root)) as f:
        traffic = json.load(f)
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        driver=driver,
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)],
        root=root,
    )


def tiny(bench: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` at the CPU tests' size: its configuration's ``tiny``
    overrides, and sets of ``TINY_ITERATIONS_PER_SET`` iterations.  Tests
    only; :func:`resolve` alone makes a chip run's cell."""
    cell = resolve(bench, name, root)
    cell.config = {**cell.config, **cell.config["tiny"]}
    cell.traffic = {**cell.traffic, "iterations_per_set": TINY_ITERATIONS_PER_SET}
    return cell


def reader(metric: str, root: Path = ROOT) -> Callable[["Run"], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = metric_path(metric, root)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# Spans: host intervals around calls into the program
# ---------------------------------------------------------------------------


class Spans:
    """Host spans, kept in memory: ``name -> [(start, end)]`` on the
    ``time.perf_counter`` clock.  With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so the trace
    can put device idle gaps down to it."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.records: dict[str, list[tuple[float, float]]] = defaultdict(list)

    def _annotation(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def span(self, name: str):
        with self._annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records[name].append((t0, time.perf_counter()))

    def wrap(self, obj: Any, method: str, name: str) -> None:
        """Time every call of ``obj.<method>`` as span ``name``."""
        inner = getattr(obj, method)

        def timed(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)

        setattr(obj, method, timed)

    def durations(self, name: str, lo: float = -math.inf, hi: float = math.inf) -> list[float]:
        """Seconds of the spans ``name`` that start in ``[lo, hi)``."""
        return [b - a for a, b in self.records.get(name, ()) if lo <= a < hi]


class CompileCounter:
    """Backend compiles JAX reports, with their times; register it with
    ``jax.monitoring.register_event_duration_secs_listener``."""

    def __init__(self) -> None:
        self.times: list[float] = []
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        with self._lock:
            return sum(1 for t in self.times if lo <= t < hi)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Answer:
    """One served SpMV: the request's own matrix and x, and the y it got."""

    n_rows: int
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    x: np.ndarray
    y: Any


@dataclasses.dataclass
class Run:
    """What a run recorded; the metric readers read it."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    t0: float = 0.0  # window start, perf_counter clock
    t1: float = 0.0  # window end: the last timed answer is in
    attempted: int = 0
    failed: int = 0
    iterations: int = 0  # closed loop: SpMVs served in the window
    counters: dict = dataclasses.field(default_factory=dict)
    spans: Spans = dataclasses.field(default_factory=Spans)
    trace: Any = None  # bench.trace.TraceSummary, traced runs only

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def worst_error(answers: list[Answer], spmv=None) -> float:
    """Worst componentwise error over ``answers``; with ``spmv`` (e.g. the
    control) its output stands in for each served y."""
    worst = 0.0
    for a in answers:
        y = a.y if spmv is None else spmv(a.n_rows, a.rows, a.cols, a.vals, a.x)
        worst = max(worst, reference.rel_err(y, a.n_rows, a.rows, a.cols, a.vals, a.x))
    return worst


def checks(cell: Cell, run: Run, answers: list[Answer]) -> list[Check]:
    """The numbers that decide ``correct``, each beside its limit."""
    limits = cell.config["limits"]
    return [
        Check("max_rel_err", worst_error(answers), float(limits["max_rel_err"])),
        Check("unchecked", float(run.counters["answers_due"] - len(answers)), 0.0),
    ]


@contextlib.contextmanager
def traced(enabled: bool, run: Run):
    """Profile the window when ``enabled``; reduce the trace on the way out."""
    if not enabled:
        yield
        return
    import jax

    from bench import trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the benchmark's spans are annotations
    opts.host_tracer_level = 1  # the annotations, not the runtime's internals
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        run.trace = trace.load(d)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             log: Callable[[str], None] = print) -> dict:
    """Set up, measure ``seconds``, check, and return the result line."""
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    run = Run(cell=cell, seed=seed, seconds=seconds, spans=Spans(annotate=trace))
    drv = cell.driver.Driver(cell, run.spans)
    drv.setup()
    drv.prepare(seed, seconds)
    with traced(trace, run):
        run.t0 = time.perf_counter()
        run.setup_s = run.t0 - t_start
        drv.measure(seconds, run)
        run.t1 = time.perf_counter()
    run.counters["window_compiles"] = counter.between(run.t0, run.t1)
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    run.counters["device_kind"] = dev.device_kind
    answers = drv.collect(run)
    drv.close()
    if trace:
        drv.after_trace(run, log)
    found = checks(cell, run, answers)
    for k, v in sorted(run.counters.items()):
        if not isinstance(v, list):
            log(f"info {k}={v}")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"], cell.root)(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    out = {
        "correct": all(c.ok for c in found),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        t = run.trace
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        for name, sec in t.idle_by_span().items():
            log(f"info idle_by_span {name}={sec}")
        out["breakdown"] = t.breakdown()
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in found}
    for c in found:
        print(f"check {c.name} value={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return out

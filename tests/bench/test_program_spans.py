"""The readers of the program's spans and counters: windows, per-SpMV sums,
the mapping of the program's clock onto the trace's, and the device idle
time inside plan resolution, on hand-built rings and traces."""
from __future__ import annotations

import sys

import pytest

from bench import harness, program_spans, trace
from repro.core import tracing

T0 = 100.0  # perf_counter seconds at the window's start
W0 = 7_000_000_000.0  # trace ns at the window's start
OFFSET = W0 - T0 * 1e9 - 2_000.0  # the window span opens 2 us before t0
PLANE = "/device:TPU:0"
ITERS = 10
NEW_KERNEL = "%ep_spmv.1 = f32[1024,512]{1,0} custom-call(f32[1024,3072]{1,0} %p)"
OLD_KERNEL = "%_unknown_.1 = f32[1024,512]{1,0} custom-call(f32[1024,3072]{1,0} %p)"


@pytest.fixture()
def ring(monkeypatch):
    ring = tracing.Ring()
    monkeypatch.setattr(tracing, "_RING", ring)
    return ring


def _ns(t: float) -> float:
    return t * 1e9 + OFFSET


def _run(ring, jitter_ns=(0.0,) * ITERS, ops_in_plan=()):
    """Ten 100 ms iterations: a serve span from +1 ms to +51 ms holding a
    30 ms plan resolution from +2 ms (its affinity part 10 ms) and a 1 ms
    launch, then 8 ms of device work from +40 ms; plus ``ops_in_plan`` as
    ``(start, end)`` offsets from each plan span's start, on iteration 0."""
    run = harness.Run(cell=None, seed=1, seconds=1.0, iterations=ITERS, t0=T0, t1=T0 + 1.0)
    run.spans.records["serve"].append((T0 - 5.0, T0 - 4.9))  # warm-up, before the window
    serve_spans, ops = [], []
    for i in range(ITERS):
        s = T0 + 0.1 * i
        run.spans.records["serve"].append((s + 0.001, s + 0.051))
        serve_spans.append(trace.Event("bench.serve", _ns(s + 0.001) + jitter_ns[i],
                                       _ns(s + 0.051) + jitter_ns[i]))
        ring.add("plan.resolve", s + 0.002, s + 0.032, 0.030)
        ring.add("plan.affinity", s + 0.002, s + 0.012, 0.010)
        ring.add("serve.launch", s + 0.032, s + 0.033, 0.001)
        ring.add("serve.h2d_bytes", s + 0.033, 442_368, 442_368)
        ops.append(trace.Event(NEW_KERNEL, _ns(s + 0.040), _ns(s + 0.048)))
    for a, b in ops_in_plan:
        ops.append(trace.Event(NEW_KERNEL, _ns(T0 + 0.002 + a), _ns(T0 + 0.002 + b)))
    # Outside the window: counted by nothing.
    ring.add("plan.resolve", T0 - 1.0, T0 - 0.5, 0.5)
    ring.add("serve.h2d_bytes", T0 + 1.0, 10**9, 10**9)
    run.trace = trace.TraceSummary(
        window=trace.Event("bench.window", W0, W0 + 1e9),
        ops={PLANE: sorted(ops, key=lambda e: e.start_ns)},
        modules={PLANE: [trace.Event("jit_ep_spmv_plan(1)", e.start_ns, e.end_ns)
                         for e in ops]},
        spans=serve_spans)
    return run


def test_span_and_counter_readers_sum_the_window_per_spmv(ring):
    run = _run(ring)
    assert harness.reader("launch_host_ms.cg")(run) == pytest.approx(1.0)
    assert harness.reader("h2d_mb_per_spmv.cg")(run) == pytest.approx(0.442368)
    # The fixture's program recorded no fingerprint: nothing to read.
    assert harness.reader("plan_fingerprint_ms.cg")(run) is None


def test_the_clock_offset_is_the_median_of_the_paired_serve_spans(ring):
    jitter = (0.0, 3_000.0, -1_000.0, 2_000.0, 500.0, 0.0, 1_000.0, -2_000.0, 0.0, 4_000.0)
    offset, residual = program_spans.clock_offset(_run(ring, jitter))
    assert offset == pytest.approx(OFFSET + 250.0, abs=1.0)  # median jitter 250 ns
    assert residual == pytest.approx(6_000.0, abs=1.0)


def test_idle_in_plan_counts_only_idle_device_time_inside_plan_resolution(ring):
    # 10 plan spans of 30 ms, all idle but for a 5 ms op inside the first.
    run = _run(ring, ops_in_plan=[(0.020, 0.025)])
    idle_in_plan = harness.reader("idle_in_plan_pct.cg")(run)
    assert idle_in_plan == pytest.approx(100 * (0.300 - 0.005) / 1.0, rel=1e-6)
    assert idle_in_plan <= harness.reader("device_idle_pct.cg")(run)


def test_disagreeing_offsets_refuse_the_alignment(ring):
    jitter = [0.0] * ITERS
    jitter[4] = 1.5e6  # one serve span 1.5 ms off the others
    run = _run(ring, jitter)
    assert program_spans.clock_offset(run) is None
    assert harness.reader("idle_in_plan_pct.cg")(run) is None


def test_a_program_without_tracing_reads_nothing(ring, monkeypatch):
    run = _run(ring)
    monkeypatch.delattr(sys.modules["repro.core"], "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    for name in ("plan_fingerprint_ms.cg", "launch_host_ms.cg", "h2d_mb_per_spmv.cg",
                 "idle_in_plan_pct.cg"):
        assert harness.reader(name)(run) is None, name


def test_a_window_the_ring_dropped_reads_nothing(monkeypatch):
    small = tracing.Ring(size=8)
    monkeypatch.setattr(tracing, "_RING", small)
    run = _run(small)
    assert harness.reader("launch_host_ms.cg")(run) is None


@pytest.mark.parametrize("kernel,module", [(OLD_KERNEL, "jit__unknown(1)"),
                                           (NEW_KERNEL, "jit_ep_spmv_plan(1)")])
def test_kernel_and_roofline_readers_find_the_named_program(kernel, module):
    """Naming the kernel ``ep_spmv`` and its program ``jit_ep_spmv_plan``
    leaves ``ep_kernel_ms.cg`` and ``spmv_roofline`` reading what they read."""
    summary = trace.TraceSummary(
        window=trace.Event("bench.window", 0.0, 1e8),
        ops={PLANE: [trace.Event(kernel, 0.0, 1e6)]},
        modules={PLANE: [trace.Event(module, 0.0, 3e6),
                         trace.Event("jit_bench_cg_step(2)", 4e6, 5e6)]},
        spans=[])
    run = harness.Run(cell=None, seed=1, seconds=1.0, iterations=2)
    run.trace = summary
    run.counters.update(device_kind="TPU v5 lite", nnz=10**6, n_rows=10**5, n_cols=10**5)
    assert harness.reader("ep_kernel_ms.cg")(run) == pytest.approx(0.5)
    nbytes = 10**6 * 8 + 10**5 * 8
    assert harness.reader("spmv_roofline")(run) == pytest.approx(100 * nbytes / 819e9 / 1.5e-3)

"""The trace reduction (busy union, idle share, per-op time by name, gap
attribution) and the roofline arithmetic, on synthetic traces and on a
small trace recorded on a TPU v5e."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import harness, trace

DATA = Path(__file__).resolve().parent / "data"
CHIP_TRACE = DATA / "stencil24_small8.xplane.pb"


def _xspace(device_ops, host_spans, modules=()) -> str:
    """An XSpace text proto: ``(name, start_ns, duration_ns)`` events."""
    names: dict[str, int] = {}

    def events(evs):
        out = []
        for name, start, dur in evs:
            mid = names.setdefault(name, len(names) + 1)
            out.append(f"events {{ metadata_id: {mid} offset_ps: {int(start * 1000)} "
                       f"duration_ps: {int(dur * 1000)} }}")
        return " ".join(out)

    ops, mods, host = events(device_ops), events(modules), events(host_spans)
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for n, i in names.items())
    return (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mods} }} {meta} }} '
            f'planes {{ id: 2 name: "/host:CPU" '
            f'lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }} {meta} }}')


def _reduce(device_ops, host_spans, modules=()):
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_text_proto(_xspace(device_ops, host_spans, modules)))


KERNEL = "%k.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p)"
FUSION = "%fusion.2 = f32[1024]{0} fusion(s32[1024]{0} %i)"


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    t = _reduce(
        device_ops=[(FUSION, 0, 300), (KERNEL, 1_000, 2_000), (FUSION, 2_500, 1_000),
                    (KERNEL, 9_500, 1_000)],
        host_spans=[("bench.window", 100, 10_000)])
    # [100, 300) + [1000, 3500) + [9500, 10100): overlaps merged, edges clipped.
    assert t.window_s == pytest.approx(10_000e-9)
    assert t.busy_s == pytest.approx((200 + 2_500 + 600) * 1e-9)
    assert t.idle_share == pytest.approx(1 - 3_300 / 10_000)


def test_op_time_by_name_and_labels():
    t = _reduce(
        device_ops=[(KERNEL, 1_000, 2_000), (FUSION, 2_500, 1_000), (KERNEL, 5_000, 500)],
        host_spans=[("bench.window", 0, 10_000)],
        modules=[("jit__unknown(1)", 900, 3_700), ("jit_bench_cg_step(2)", 4_800, 800)])
    assert t.op_seconds(lambda n: " custom-call(" in n) == pytest.approx(2_500e-9)
    assert t.top_ops() == [["%k.1 = f32[8,128]", pytest.approx(2_500e-9)],
                           ["%fusion.2 = f32[1024]", pytest.approx(1_000e-9)]]
    assert t.module_seconds(lambda n: "bench_" not in n) == pytest.approx(3_700e-9)
    assert t.module_seconds(lambda n: "bench_" in n) == pytest.approx(800e-9)


def test_idle_gaps_go_to_the_span_that_covers_them():
    t = _reduce(
        device_ops=[(KERNEL, 2_000, 1_000), (KERNEL, 6_000, 1_000)],
        host_spans=[("bench.window", 0, 10_000), ("bench.serve", 0, 6_000),
                    ("bench.plan_for", 0, 1_900), ("bench.cg_update", 7_000, 3_000)])
    gaps = t.gaps()
    # [0, 2000): plan_for overlaps 1900 ns of it, serve all 2000: serve wins.
    assert gaps == [("bench.serve", pytest.approx(3_000e-9)),
                    ("bench.cg_update", pytest.approx(3_000e-9)),
                    ("bench.serve", pytest.approx(2_000e-9))]
    assert t.idle_by_span() == {"bench.serve": pytest.approx(5_000e-9),
                                "bench.cg_update": pytest.approx(3_000e-9)}
    b = t.breakdown()
    assert len(b["device_ops"]) <= trace.TOP and len(b["idle_gaps"]) <= trace.TOP


def test_an_idle_device_is_all_gap():
    t = _reduce(device_ops=[(KERNEL, 20_000, 10)],
                host_spans=[("bench.window", 0, 10_000), ("bench.launch", 0, 10_000)])
    assert t.busy_s == 0.0 and t.idle_share == 1.0
    assert t.gaps() == [("bench.launch", pytest.approx(10_000e-9))]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        _reduce(device_ops=[(KERNEL, 0, 10)], host_spans=[("bench.serve", 0, 10)])


def test_the_chip_trace_reduces_to_its_recorded_numbers():
    """A trace recorded on one TPU v5e: a 24^3 stencil served twice, then
    two rounds of 12 small graphs through the micro-batcher."""
    from jax.profiler import ProfileData

    assert CHIP_TRACE.stat().st_size < 500_000
    t = trace.reduce(ProfileData.from_file(str(CHIP_TRACE)))
    assert t.busy_planes == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.983610472, rel=1e-9)
    assert t.busy_s == pytest.approx(0.267963856, rel=1e-9)
    assert t.idle_share == pytest.approx(1 - 0.267963856 / 0.983610472, rel=1e-9)
    assert t.op_seconds(lambda n: " custom-call(" in n) == pytest.approx(0.081589533, rel=1e-9)
    assert sum(len(evs) for evs in t.modules.values()) == 13
    top = t.top_ops()
    assert top[0] == ["%fusion.1 = f32[131080]", pytest.approx(0.096247603, rel=1e-9)]
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    # Busy never exceeds the modules that hold the ops, nor the window.
    assert t.busy_s <= t.module_seconds(lambda n: True) + 1e-9 <= t.window_s
    assert {n for n, _ in t.gaps()} <= {"none", "bench.serve", "bench.submit"}
    assert sum(s for _, s in t.gaps()) == pytest.approx(t.window_s - t.busy_s, rel=1e-9)


def _run_with_trace(summary, **counters):
    run = harness.Run(cell=None, seed=1, seconds=1.0, iterations=4)
    run.trace = summary
    run.counters.update(counters)
    return run


def test_spmv_roofline_counts_the_matrix_bytes_over_all_served_programs():
    summary = _reduce(
        device_ops=[(KERNEL, 0, 1_000_000)],
        host_spans=[("bench.window", 0, 10_000_000)],
        modules=[("jit__unknown(1)", 0, 2_000_000), ("jit__unknown(1)", 3_000_000, 2_000_000),
                 ("jit_bench_cg_step(2)", 6_000_000, 3_000_000)])
    run = _run_with_trace(summary, device_kind="TPU v5 lite",
                          nnz=1_000_000, n_rows=100_000, n_cols=100_000)
    nbytes = 1_000_000 * 8 + 100_000 * 4 * 2
    per_spmv = 4_000_000e-9 / 4  # the bench_ module is not the server's
    assert harness.reader("spmv_roofline")(run) == pytest.approx(
        100 * nbytes / 819e9 / per_spmv)
    assert harness.reader("ep_kernel_ms.cg")(run) == pytest.approx(1.0 / 4)
    assert harness.reader("device_idle_pct.cg")(run) == pytest.approx(90.0)


def test_spmv_roofline_refuses_a_device_missing_from_the_peaks():
    summary = _reduce(device_ops=[(KERNEL, 0, 10)], host_spans=[("bench.window", 0, 100)],
                      modules=[("jit__unknown(1)", 0, 10)])
    run = _run_with_trace(summary, device_kind="TPU v9", nnz=1, n_rows=1, n_cols=1)
    with pytest.raises(KeyError, match="TPU v9"):
        harness.reader("spmv_roofline")(run)


def test_a_window_of_many_requests_reduces_fast():
    """A 51 s window of a served stream can hold ~10^4 launches and ~2·10^4
    spans; putting every gap down to a span must not cost gaps × spans."""
    import time

    n = 20_000
    ops = [trace.Event(KERNEL, 10_000.0 * i, 10_000.0 * i + 2_000) for i in range(n)]
    spans = [trace.Event("bench.launch", 10_000.0 * i + 1_000, 10_000.0 * i + 3_000)
             for i in range(n)]
    spans += [trace.Event("bench.plan_for", 10_000.0 * i + 3_000, 10_000.0 * i + 9_000)
              for i in range(n)]
    t = trace.TraceSummary(window=trace.Event("bench.window", 0.0, 10_000.0 * n),
                           ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": []},
                           spans=spans)
    t0 = time.perf_counter()
    by_span = t.idle_by_span()
    assert time.perf_counter() - t0 < 10.0
    assert by_span == {"bench.plan_for": pytest.approx(n * 8_000e-9)}

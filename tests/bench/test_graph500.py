"""The Graph500 Kronecker generator, its personalized-PageRank matrix, and a
tiny run of ``graph500-16.ppr_cg`` on the CPU."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.gen import kronecker
from repro.runtime import request as program
from repro.runtime.request import ServeResult

INITIATOR = (0.57, 0.19, 0.19)
ALPHA = 0.85
SEED = 2**33 + 29  # wider than 32 bits, as the check's seeds are


def config(scale: int, graph_seed: int = 1) -> dict:
    return dict(scale=scale, edgefactor=16, initiator=list(INITIATOR),
                damping=ALPHA, graph_seed=graph_seed)


def dense(n, rows, cols, vals) -> np.ndarray:
    a = np.zeros((n, n))
    a[rows, cols] = vals
    return a


def test_the_generator_draws_edgefactor_times_n_edges_deterministically():
    src, dst = kronecker.edges(9, 16, INITIATOR, seed=4)
    assert src.size == dst.size == 16 * 2**9
    assert src.min() >= 0 and max(src.max(), dst.max()) < 2**9
    again = kronecker.edges(9, 16, INITIATOR, seed=4)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    other = kronecker.edges(9, 16, INITIATOR, seed=5)
    assert not np.array_equal(src, other[0])


def test_the_initiator_skews_the_quadrants():
    # One level: the first bit of (src, dst) before the permutation falls in
    # quadrant (0,0) with probability A, (0,1) B, (1,0) C, (1,1) D.
    src, dst = kronecker.edges(1, 50_000, INITIATOR, seed=6)
    # Labels are permuted: count the quadrants under both labelings.
    share = np.bincount(2 * src + dst, minlength=4) / src.size
    want = np.array([0.57, 0.19, 0.19, 0.05])
    assert np.allclose(share, want, atol=0.01) or np.allclose(share, want[::-1], atol=0.01)


def test_the_matrix_is_symmetric_without_self_loops_or_duplicates():
    n, rows, cols, vals, degree = kronecker.graph500_ppr(config(9))
    a = dense(n, rows, cols, vals)
    assert np.array_equal(a, a.T)
    assert np.all(np.diag(a) == 1.0)
    off = rows != cols
    assert np.all(vals[off] < 0)  # every edge is −α / sqrt(d_i d_j)
    assert np.unique(rows * n + cols).size == rows.size
    assert np.array_equal(np.bincount(rows[off], minlength=n), degree)
    assert np.all(np.diff(rows) >= 0)
    i, j = rows[off][0], cols[off][0]
    assert vals[off][0] == np.float32(-ALPHA / np.sqrt(degree[i] * degree[j]))
    again = kronecker.graph500_ppr(config(9))
    assert all(np.array_equal(x, y) for x, y in zip(again[1:], (rows, cols, vals, degree)))


def test_scale_16_has_the_sizes_the_configuration_states():
    n, rows, _, _, degree = kronecker.graph500_ppr(config(16))
    assert n == 65_536 and rows.size == 1_885_936
    assert (degree == 0).sum() == 18_802 and degree.max() == 9_729
    assert rows.size > 2**20  # past BucketPolicy.max_nnz: the dedicated lane


def test_the_ppr_matrix_is_positive_definite_within_one_plus_minus_alpha():
    n, rows, cols, vals, _ = kronecker.graph500_ppr(config(8))
    eig = np.linalg.eigvalsh(dense(n, rows, cols, vals.astype(np.float64)))
    assert eig.min() >= 1 - ALPHA - 1e-6 and eig.max() <= 1 + ALPHA + 1e-6


def cg64(n, rows, cols, vals, b, iterations):
    """Plain float64 CG from x = 0."""
    def mul(v):
        return np.bincount(rows, weights=vals * v[cols], minlength=n)

    x, r = np.zeros(n), b.copy()
    p, rr = r.copy(), r @ r
    for _ in range(iterations):
        ap = mul(p)
        alpha = rr / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        rr, rr_old = r @ r, rr
        p = r + rr / rr_old * p
    return x


def test_cg_on_the_symmetric_form_is_personalized_pagerank():
    n, rows, cols, vals, degree = kronecker.graph500_ppr(config(10))
    s = kronecker.personalization(degree, 16, np.random.default_rng(7))
    assert s.sum() == pytest.approx(1.0) and np.all(degree[s > 0] > 0)
    y = cg64(n, rows, cols, vals.astype(np.float64), kronecker.ppr_rhs(degree, s, ALPHA), 60)
    pi = np.sqrt(degree) * y
    # Power iteration of (I − α W D⁻¹) π = (1 − α) s in float64.
    off = rows != cols
    w_rows, w_cols = rows[off], cols[off]
    want = (1 - ALPHA) * s
    for _ in range(300):
        walk = np.bincount(w_rows, weights=want[w_cols] / degree[w_cols], minlength=n)
        want = ALPHA * walk + (1 - ALPHA) * s
    assert np.abs(pi - want).sum() < 1e-6
    assert pi.sum() == pytest.approx(1.0, abs=1e-6)  # no dangling mass: isolated rows get none


CELL = "graph500-16.ppr_cg"


def tiny() -> harness.Cell:
    return harness.tiny(harness.load_benchmark(), CELL)


def run(cell: harness.Cell) -> dict:
    return harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(), log=lambda m: None)


def test_the_cell_sends_twenty_iteration_queries_from_sixteen_sources():
    assert harness.resolve(harness.load_benchmark(), CELL).traffic == {
        "driver": "ppr_cg", "iterations_per_set": 20, "sources_per_query": 16,
        "rhs_pool": 4, "check_sample": 8}
    cell = tiny()
    drv = cell.driver.Driver(cell, harness.Spans())
    drv.n, _, _, _, drv.degree = kronecker.graph500_ppr(cell.config)
    drv.prepare(SEED, 1.0)
    b = np.asarray(drv.rhs[0])
    assert len(drv.rhs) == 4 and np.count_nonzero(b) == 16
    again = cell.driver.Driver(cell, harness.Spans())
    again.n, again.degree = drv.n, drv.degree
    again.prepare(SEED, 1.0)
    assert all(np.array_equal(np.asarray(u), np.asarray(v)) for u, v in zip(drv.rhs, again.rhs))


def test_a_tiny_run_is_correct():
    out = run(tiny())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "cg_iter_ms"}


def test_a_tiny_run_answered_by_the_control_is_not(monkeypatch):
    serve = program.GraphServer.serve

    def control(self, req):
        res = serve(self, req)
        y = reference.control_spmv(req.n_rows, req.rows, req.cols, req.vals, req.x)
        return ServeResult(y=jnp.asarray(y), info=res.info)

    monkeypatch.setattr(program.GraphServer, "serve", control)
    out = run(tiny())
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] > out["checks"]["max_rel_err"]["limit"]


def test_the_fill_metrics_read_the_programs_counters():
    cell = tiny()
    drv = cell.driver.Driver(cell, harness.Spans())
    drv.setup()
    try:
        r = harness.Run(cell=cell, seed=SEED, seconds=0.1)
        r.t0 = time.perf_counter()
        drv.prepare(SEED, 0.1)
        drv.measure(0.1, r)
        r.t1 = time.perf_counter()
        spec = next(iter(drv.server._specs.values()))
        plan = drv.svc.plan_cache.peek(drv.fingerprint).plan
    finally:
        drv.close()
    task = harness.reader("task_fill_pct")(r)
    xy = harness.reader("xy_fill_pct")(r)
    assert task == pytest.approx(100.0 * drv.rows.size / (spec.k * spec.e_max))
    assert xy == pytest.approx(100.0 * plan.modeled_loads() / (spec.k * (spec.x_max + spec.y_max)))
    assert 0 < task <= 100 and 0 < xy <= 100

"""The benchmark's copied generator, the drivers' right-hand sides, and the
float64 reference."""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from bench import harness, reference
from bench.gen import stencil

RHS_SEED = 2**33 + 41
# sha256 of each cell's right-hand-side pool (float32, in pool order) at its
# tiny size from RHS_SEED, as the drivers drew it before cg.Driver had its
# matrix hooks: a refactor of the drivers keeps every seed's inputs.
RHS_POOL_SHA256 = {
    "hpcg-48.cg": "fd61a0d1bb351e25d70cf3c6a34a8f68c5a383c4432ecf232cddaf269b0f47bc",
    "graph500-16.ppr_cg": "be19368c461aa0a34c6d2acab5ae7d0ef495f4852cc1720116e1ab49c0ba1ae2",
}


def test_hpcg_48_has_the_stated_rows_and_nonzeros():
    n, rows, cols = stencil.coo(48, 48, 48)
    assert n == 110_592
    assert rows.size == cols.size == 2_863_288 == stencil.nnz(48, 48, 48)
    assert np.all(np.diff(rows) >= 0)
    assert stencil.nnz(104, 104, 104) == 29_791_000  # HPCG's default local grid


def test_stencil_is_hpcgs_symmetric_positive_definite_matrix():
    n, rows, cols = stencil.coo(4, 3, 5)
    vals = stencil.hpcg_values(rows, cols)
    dense = np.zeros((n, n))
    dense[rows, cols] = vals
    assert np.array_equal(dense, dense.T)
    assert np.all(np.diag(dense) == 26.0)
    assert set(np.unique(vals)) == {-1.0, 26.0}
    assert np.linalg.eigvalsh(dense).min() > 0
    # An interior row couples to all 27 neighbours, itself included.
    assert np.bincount(rows).max() == 27


def test_reference_matches_a_dense_product():
    rng = np.random.default_rng(5)
    n_rows, n_cols = 37, 23
    dense = np.where(rng.random((n_rows, n_cols)) < 0.2, rng.standard_normal((n_rows, n_cols)), 0)
    rows, cols = np.nonzero(dense)
    vals = dense[rows, cols].astype(np.float32)
    x = rng.standard_normal(n_cols).astype(np.float32)
    want = dense.astype(np.float32).astype(np.float64) @ x.astype(np.float64)
    assert np.allclose(reference.spmv(n_rows, rows, cols, vals, x), want, rtol=1e-12, atol=1e-12)
    assert np.allclose(reference.magnitude(n_rows, rows, cols, vals, x),
                       np.abs(dense.astype(np.float32)) @ np.abs(x), rtol=1e-6)
    assert reference.rel_err(want, n_rows, rows, cols, vals, x) == pytest.approx(0.0, abs=1e-15)
    f32 = (dense.astype(np.float32) @ x).astype(np.float32)
    assert 0 < reference.rel_err(f32, n_rows, rows, cols, vals, x) < 1e-6


def test_reference_reads_wrong_shapes_and_nans_as_the_largest_float():
    rows, cols = np.array([0, 1]), np.array([0, 0])
    vals, x = np.ones(2, np.float32), np.ones(1, np.float32)
    assert reference.rel_err(np.ones(3), 2, rows, cols, vals, x) == reference.WRONG
    assert reference.rel_err(np.array([1.0, np.nan]), 2, rows, cols, vals, x) == reference.WRONG
    assert json.loads(json.dumps(reference.WRONG)) == reference.WRONG


def test_control_reads_far_above_float32():
    rng = np.random.default_rng(11)
    n, rows, cols = stencil.coo(8, 8, 8)
    vals = stencil.hpcg_values(rows, cols)
    x = rng.standard_normal(n).astype(np.float32)
    ctrl = reference.control_spmv(n, rows, cols, vals, x)
    assert reference.rel_err(ctrl, n, rows, cols, vals, x) > 1e-3


@pytest.mark.parametrize("name", sorted(RHS_POOL_SHA256))
def test_a_seed_draws_the_right_hand_sides_and_samples_it_always_drew(name):
    cell = harness.tiny(harness.load_benchmark(), name)
    drv = cell.driver.Driver(cell, harness.Spans())
    drv.n, drv.rows, drv.cols, drv.vals = drv.matrix()
    drv.prepare(RHS_SEED, 1.0)
    assert len(drv.rhs) == cell.traffic["rhs_pool"]
    pool = b"".join(np.asarray(b, np.float32).tobytes() for b in drv.rhs)
    assert hashlib.sha256(pool).hexdigest() == RHS_POOL_SHA256[name]
    assert [int(drv.sample_rng.integers(0, 1000)) for _ in range(3)] == [389, 399, 816]

"""A power-law matrix through GraphServer, and the tile-fill counters.

A Graph500 Kronecker graph's hub rows are split over many clusters, so the
fullest cluster sets every cluster's padded tiles.  Both lanes must still
serve it exactly, and each launch counts the slots it walks
(``serve.task_slots``, ``serve.xy_slots``) beside what its plans fill
(``serve.tasks``, ``serve.xy_loads``).
"""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import PartitionService, tracing
from repro.runtime import BucketPolicy, GraphRequest, GraphServer

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.gen import kronecker  # noqa: E402

FILL = ("serve.task_slots", "serve.tasks", "serve.xy_slots", "serve.xy_loads")


@pytest.fixture(scope="module")
def ppr10():
    """The scale-10 Kronecker graph's PPR matrix (α = 0.85)."""
    cfg = dict(scale=10, edgefactor=16, initiator=[0.57, 0.19, 0.19],
               damping=0.85, graph_seed=3)
    return kronecker.graph500_ppr(cfg)


@pytest.fixture()
def service():
    with PartitionService() as svc:
        yield svc


def _request(ppr, seed):
    n, rows, cols, vals, _ = ppr
    x = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    return GraphRequest(n, n, rows, cols, vals, x)


def _reference(req):
    terms = req.vals.astype(np.float64) * req.x.astype(np.float64)[req.cols]
    return np.bincount(req.rows, weights=terms, minlength=req.n_rows)


def _fill(lo):
    hi = time.perf_counter()
    return {n: sum(a for _, _, a in tracing.records(n, lo, hi)) for n in FILL}


def test_the_graph_is_skewed(ppr10):
    n, rows, _, _, degree = ppr10
    assert rows.size > 16 * n  # edgefactor 16, symmetrised, less duplicates
    assert degree.max() > 20 * degree[degree > 0].mean()
    assert (degree == 0).sum() > 0  # isolated vertices stay in the matrix


@pytest.mark.parametrize("lane", ["dedicated", "bucket"])
def test_both_lanes_serve_it_exactly_and_count_their_tiles(service, ppr10, lane):
    server = GraphServer(service, k=16, start_batcher=False,
                         bucketing=None if lane == "dedicated" else BucketPolicy())
    req = _request(ppr10, seed=1)
    server.serve(req)  # plan build and compile
    lo = time.perf_counter()
    res = server.serve(req)
    fill = _fill(lo)
    assert (res.info.bucket is None) == (lane == "dedicated")

    ref = _reference(req)
    scale = np.bincount(req.rows, weights=np.abs(req.vals.astype(np.float64))
                        * np.abs(req.x.astype(np.float64))[req.cols], minlength=req.n_rows)
    err = np.abs(np.asarray(res.y, np.float64) - ref) / np.where(scale > 0, scale, 1.0)
    assert err.max() < 1e-6

    sp = service.plan_cache.peek(res.info.fingerprint)
    plan = sp.plan
    tiles = plan if lane == "dedicated" else server._specs[res.info.bucket]
    assert fill == {
        "serve.task_slots": tiles.k * tiles.e_max,
        "serve.tasks": req.rows.size,
        "serve.xy_slots": tiles.k * (tiles.x_max + tiles.y_max),
        "serve.xy_loads": int(plan.x_count.sum() + plan.y_count.sum()),
    }
    assert sp.padding.xy_loads == plan.modeled_loads()
    assert fill["serve.tasks"] <= fill["serve.task_slots"]
    assert fill["serve.xy_loads"] <= fill["serve.xy_slots"]


def test_a_batched_launch_counts_each_request_and_no_empty_slot(service, ppr10):
    with GraphServer(service, k=16, max_batch=8, max_wait_ms=200.0) as server:
        reqs = [_request(ppr10, seed=s) for s in range(3)]
        first = server.serve(reqs[0])
        spec = server._specs[first.info.bucket]
        lo = time.perf_counter()
        handles = [server.submit(r) for r in reqs]
        results = [h.wait(120.0) for h in handles]
        fill = _fill(lo)
    assert [r.info.batch_size for r in results] == [3, 3, 3]
    plan = service.plan_cache.peek(first.info.fingerprint).plan
    assert fill == {
        "serve.task_slots": 3 * spec.k * spec.e_max,
        "serve.tasks": 3 * reqs[0].rows.size,
        "serve.xy_slots": 3 * spec.k * (spec.x_max + spec.y_max),
        "serve.xy_loads": 3 * plan.modeled_loads(),
    }
    for req, res in zip(reqs, results):
        assert np.allclose(np.asarray(res.y), _reference(req), rtol=1e-5, atol=1e-5)

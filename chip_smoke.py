"""Smoke run of the served EP-SpMV path on one TPU chip.

    python chip_smoke.py [--seed 0]

Two phases in one process, through the entry points a user calls
(``PartitionService`` + ``GraphServer``):

(a) stencil — HPCG's 27-point stencil on a 64^3 grid (262,144 rows,
    6,859,000 non-zeros) served at k=1024.  It is past the bucket caps, so
    it takes the dedicated-compile lane: three requests with different x,
    then a uniform 1% churn through ``update_async`` and one request
    served from the new plan, checked against the churned matrix built
    from the batch alone.
(b) small graphs — 32 requests over the ``benchmarks/svc_batched.py``
    families (k=8, three tenants): each once through the synchronous lane,
    then all through ``GraphServer.submit`` with the micro-batcher on, so
    same-bucket requests share launches across several shape buckets.

Every served y is checked against a float64 numpy COO SpMV of the same
matrix and x: relative L2 error at most 1e-5.  The lines before the last
are informational, labelled with the device; they are not a benchmark.
The last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero before any phase and prints no ``ok`` line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.svc_batched import FAMILIES, N_TENANTS  # noqa: E402
from benchmarks.svc_batched import K as SMALL_K  # noqa: E402
from repro.core import (  # noqa: E402
    PartitionService,
    stencil_27pt_coo,
    synthetic_bipartite_graph,
)
from repro.kernels.backend import use_compile_cache  # noqa: E402
from repro.runtime import BucketPolicy, GraphRequest, GraphServer  # noqa: E402

GRID = 64  # stencil grid side: 262,144 rows, 6,859,000 non-zeros
STENCIL_K = 1024
STENCIL_REQUESTS = 3
CHURN = 0.01  # fraction of the stencil's non-zeros deleted, and as many inserted
SMALL_REQUESTS = 32
MAX_BATCH = 8
MAX_WAIT_MS = 20.0
MAX_REL_ERR = 1e-5


class CompileClock:
    """Sums the XLA backend-compile seconds JAX reports; register it with
    ``jax.monitoring.register_event_duration_secs_listener``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def stencil_values(rows: np.ndarray, cols: np.ndarray, seed: int) -> np.ndarray:
    """HPCG's values (26 on the diagonal, -1 off it), each scaled by a
    seeded factor in [0.9, 1.1) that depends only on (row, col) — so a
    churned matrix keeps the values of the entries it keeps."""
    h = (rows * 2654435761 + cols * 2246822519 + (seed + 1) * 3266489917) % (1 << 32)
    scale = 0.9 + 0.2 * (h / float(1 << 32))
    return np.where(rows == cols, 26.0, -1.0) * scale


def uniform_churn(n: int, nnz: int, rng):
    """Delete ``CHURN`` of the non-zeros, chosen uniformly, and insert as
    many uniformly random ``(row, col)`` couplings — the churn convention of
    ``benchmarks/svc_service.py``.  Returns ``(delete_ids, rows, cols)``."""
    count = max(int(CHURN * nnz), 1)
    delete_ids = np.sort(rng.choice(nnz, count, replace=False))
    return delete_ids, rng.integers(0, n, count), rng.integers(0, n, count)


def rel_err(req: GraphRequest, y) -> float:
    """Relative L2 error of ``y`` against a float64 COO SpMV of ``req``."""
    ref = np.bincount(
        req.rows,
        weights=req.vals.astype(np.float64) * req.x.astype(np.float64)[req.cols],
        minlength=req.n_rows,
    )
    diff = np.asarray(y, dtype=np.float64) - ref
    return float(np.linalg.norm(diff) / max(np.linalg.norm(ref), 1e-30))


def _serve_timed(server: GraphServer, req: GraphRequest):
    t0 = time.perf_counter()
    res = server.serve(req)
    res.y.block_until_ready()
    return res, time.perf_counter() - t0


def stencil_phase(svc, seed: int, log, clock: CompileClock) -> float:
    """Phase (a); returns the worst relative error."""
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    n, rows, cols = stencil_27pt_coo(GRID)
    vals = stencil_values(rows, cols, seed)
    log(f"stencil {GRID}^3: rows={n} nnz={rows.size} k={STENCIL_K} "
        f"generate_s={time.perf_counter() - t0:.3f}")
    server = GraphServer(svc, k=STENCIL_K, bucketing=BucketPolicy(), start_batcher=False)
    worst = 0.0
    base = None
    for i in range(STENCIL_REQUESTS):
        req = GraphRequest(n, n, rows, cols, vals, rng.standard_normal(n))
        c0 = clock.seconds
        res, dt = _serve_timed(server, req)
        _check(res.info.bucket is None, "the stencil takes the dedicated lane")
        err = rel_err(req, res.y)
        worst = max(worst, err)
        base = res.info
        log(f"stencil request {i}: seconds={dt:.4f} "
            f"partition_s={res.info.partition_time_s:.3f} "
            f"compile_s={clock.seconds - c0:.3f} plan_hit={res.info.cache_hit} "
            f"kernel_hit={res.info.kernel_cache_hit} rel_err={err:.3e}")

    delete_ids, ins_rows, ins_cols = uniform_churn(n, rows.size, rng)
    n_churn = delete_ids.size
    t0 = time.perf_counter()
    ticket = svc.update_async(base.fingerprint, STENCIL_K, insert_u=ins_cols,
                              insert_v=n + ins_rows, delete_ids=delete_ids)
    new_plan = ticket.result()
    update_s = time.perf_counter() - t0
    # The churned matrix, built here from the batch alone: the kept entries
    # in order, then the inserted ones — what the update must have planned.
    new_rows = np.concatenate([np.delete(rows, delete_ids), ins_rows])
    new_cols = np.concatenate([np.delete(cols, delete_ids), ins_cols])
    _, _, plan_rows, plan_cols = new_plan.coo
    _check(np.array_equal(plan_rows, new_rows) and np.array_equal(plan_cols, new_cols),
           "the updated plan holds exactly the churned matrix")
    req = GraphRequest(n, n, new_rows, new_cols, stencil_values(new_rows, new_cols, seed),
                       rng.standard_normal(n))
    c0 = clock.seconds
    res, dt = _serve_timed(server, req)
    _check(res.info.fingerprint == new_plan.fingerprint and res.info.cache_hit,
           "the post-churn request is served from the updated plan")
    err = rel_err(req, res.y)
    worst = max(worst, err)
    log(f"stencil churn {n_churn}+{n_churn} nnz: update_s={update_s:.3f} "
        f"gear={new_plan.source} seconds={dt:.4f} "
        f"compile_s={clock.seconds - c0:.3f} rel_err={err:.3e}")
    log(f"stencil compile cache: {_cache_line(server.stats())}")
    return worst


def _cache_line(stats: dict) -> str:
    return (f"hits={stats['hits']} misses={stats['misses']} "
            f"evictions={stats['evictions']} batch_hist={stats['batch_hist']}")


def _small_requests(seed: int) -> list[GraphRequest]:
    rng = np.random.default_rng(seed + 1)
    reqs = []
    for i in range(SMALL_REQUESTS):
        fam = i % len(FAMILIES)
        n_rows, n_cols, nnz_per_row = FAMILIES[fam]
        _, rows, cols = synthetic_bipartite_graph(
            n_rows, n_cols, nnz_per_row, seed=100_000 * seed + 1000 * fam + i)
        reqs.append(GraphRequest(
            n_rows, n_cols, rows, cols,
            rng.standard_normal(rows.size), rng.standard_normal(n_cols),
            tenant=f"tenant{i % N_TENANTS}"))
    return reqs


def small_graph_phase(svc, seed: int, log, clock: CompileClock) -> float:
    """Phase (b); returns the worst relative error."""
    reqs = _small_requests(seed)
    worst = 0.0
    with GraphServer(svc, k=SMALL_K, max_batch=MAX_BATCH,
                     max_wait_ms=MAX_WAIT_MS) as server:
        c0 = clock.seconds
        t0 = time.perf_counter()
        for req in reqs:  # plans, bucket kernels, and the synchronous lane
            res, _ = _serve_timed(server, req)
            _check(res.info.bucket is not None, "small graphs take a bucket")
            worst = max(worst, rel_err(req, res.y))
        log(f"small graphs: {SMALL_REQUESTS} sync serves seconds={time.perf_counter() - t0:.3f} "
            f"compile_s={clock.seconds - c0:.3f}")
        t0 = time.perf_counter()
        handles = [server.submit(req) for req in reqs]
        results = [h.wait(300.0) for h in handles]
        for res in results:
            res.y.block_until_ready()
        dt = time.perf_counter() - t0
        for req, res in zip(reqs, results):
            worst = max(worst, rel_err(req, res.y))
        stats = server.stats()
    buckets = sorted({res.info.bucket for res in results})
    widest = max(res.info.batch_size for res in results)
    log(f"small graphs: {SMALL_REQUESTS} queued serves seconds={dt:.4f} "
        f"per_request_s={dt / SMALL_REQUESTS:.5f} buckets={len(buckets)} widest_batch={widest}")
    log(f"small graphs compile cache: {_cache_line(stats)}")
    for label in buckets:
        log(f"  bucket {label}: hits={stats['buckets'][label]['hits']}")
    _check(len(buckets) >= 2, "the queued requests span at least two buckets")
    _check(widest > 1, "a launch carried more than one request")
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    label = f"[{dev.platform} {dev.device_kind}]"

    def log(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    cache_dir = use_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    log(f"jax={jax.__version__} devices={len(jax.devices())} compile_cache={cache_dir}")
    with PartitionService() as svc:
        err_a = stencil_phase(svc, args.seed, log, clock)
        _check(err_a <= MAX_REL_ERR, f"stencil rel_err {err_a:.3e} <= {MAX_REL_ERR}")
        err_b = small_graph_phase(svc, args.seed, log, clock)
        _check(err_b <= MAX_REL_ERR, f"small-graph rel_err {err_b:.3e} <= {MAX_REL_ERR}")
    log(f"worst rel_err: stencil={err_a:.3e} small_graphs={err_b:.3e} "
        f"total_compile_s={clock.seconds:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

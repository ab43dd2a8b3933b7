"""svc_batched: bucketed compilation + micro-batching vs per-shape compiles.

The many-small-graphs serving scenario the ROADMAP targets ("millions of
users, thousands of small graphs"): a pool of >100 distinct matrix
structures drawn from 4 shape families, served by 3 tenants.  Two phases
over the *same* warm plan cache (partitioning is off the measured path in
both — this bench isolates the kernel-compilation axis):

  * **unbatched** — the pre-PR design: a dedicated jit per structure
    through a bounded compile cache (capacity 32 « pool size), served
    sequentially.  The pool thrashes the cache, so steady state recompiles
    on every request — first-request p99 everywhere, forever.
  * **batched** — the bucketed path: every structure falls into one of
    <= 4 geometric shape buckets; 3 client threads push requests through
    ``GraphServer.submit`` and same-bucket arrivals coalesce into stacked
    kernel launches.  The same 32-entry compile cache now holds the entire
    working set (one executable per bucket), so steady state never
    compiles.

Claims gated by CI (``scripts/check_bench_regression.py``):

  * distinct kernel compiles in the batched phase <= n_buckets + 1;
  * steady-state requests/sec >= 3x the unbatched baseline;
  * batched results byte-identical (after de-padding) to per-request
    dedicated serving, for every structure in the pool;
  * bucket-cache hit rate does not regress vs the committed baseline.

Row keys (CI baseline stable): ``batched`` for the summary claims,
``bucket=<label>`` per compile bucket (compiles/hits/operand elems),
``batch_hist`` for the batch-size histogram rendered by
``scripts/print_stage_times.py``.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from repro.core import PartitionService
from repro.core.graph import synthetic_bipartite_graph
from repro.runtime import BucketPolicy, GraphRequest, GraphServer

#: 4 shape families x GRAPHS_PER_FAMILY distinct structures; with the
#: default BucketPolicy (floors 256/1024, growth 2) the families land in
#: exactly 4 buckets: (256,256,e1024), (256,256,e2048), (512,512,e2048),
#: (512,512,e1024).
FAMILIES = [
    # (n_rows, n_cols, nnz_per_row) — nnz below is post-dedup, what the
    # generator actually emits.
    (150, 150, 4),    # ~500 nnz   -> r256 c256 e1024
    (150, 150, 16),   # ~1130 nnz  -> r256 c256 e2048
    (300, 300, 5),    # ~1170 nnz  -> r512 c512 e2048
    (300, 300, 3),    # ~800 nnz   -> r512 c512 e1024
]
GRAPHS_PER_FAMILY = 26  # 104 distinct structures >= the 100-graph floor
N_TENANTS = 3
K = 8
COMPILE_CACHE_ENTRIES = 32  # both phases; << pool size, >= bucket count
MAX_BATCH = 8
MAX_WAIT_MS = 4.0
PASSES_BATCHED = 2  # pass 1 doubles as the byte-identity check


def _pcts(samples_s: list[float]) -> tuple[float, float]:
    xs = sorted(samples_s)
    if not xs:
        return 0.0, 0.0
    return (
        xs[min(len(xs) - 1, int(0.50 * len(xs)))] * 1e3,
        xs[min(len(xs) - 1, int(0.99 * len(xs)))] * 1e3,
    )


def _build_pool(seed: int = 0) -> list[dict]:
    """The request pool: (structure, vals, deterministic x) per graph."""
    rng = np.random.default_rng(seed)
    pool = []
    for fam, (n_rows, n_cols, nnz_per_row) in enumerate(FAMILIES):
        for g in range(GRAPHS_PER_FAMILY):
            _, rows, cols = synthetic_bipartite_graph(
                n_rows, n_cols, nnz_per_row, seed=1000 * fam + g
            )
            pool.append({
                "n_rows": n_rows,
                "n_cols": n_cols,
                "rows": rows,
                "cols": cols,
                "vals": rng.standard_normal(rows.shape[0]).astype(np.float32),
                "x": rng.standard_normal(n_cols).astype(np.float32),
                "tenant": f"tenant{(fam * GRAPHS_PER_FAMILY + g) % N_TENANTS}",
            })
    return pool


def _request(entry: dict) -> GraphRequest:
    return GraphRequest(
        entry["n_rows"], entry["n_cols"], entry["rows"], entry["cols"],
        entry["vals"], entry["x"], tenant=entry["tenant"],
    )


def _unbatched_phase(svc: PartitionService, pool: list[dict]):
    """Sequential pass, dedicated compile per structure (bucketing off)."""
    server = GraphServer(
        svc, k=K, bucketing=None,
        compile_cache_entries=COMPILE_CACHE_ENTRIES, start_batcher=False,
    )
    lat: list[float] = []
    y_ref: list[np.ndarray] = []
    t_all = time.perf_counter()
    for entry in pool:
        t0 = time.perf_counter()
        res = server.serve(_request(entry))
        lat.append(time.perf_counter() - t0)
        y_ref.append(np.asarray(res.y))
    elapsed = time.perf_counter() - t_all
    return elapsed, lat, y_ref, server.stats()


def _batched_phase(svc: PartitionService, pool: list[dict], y_ref: list[np.ndarray]):
    """Concurrent clients through submit(); pass 1 checks byte identity."""
    identical = [True]
    lat: list[float] = []
    lock = threading.Lock()
    with GraphServer(
        svc, k=K, bucketing=BucketPolicy(),
        compile_cache_entries=COMPILE_CACHE_ENTRIES,
        max_batch=MAX_BATCH, max_wait_ms=MAX_WAIT_MS,
    ) as server:

        def client(cid: int) -> None:
            mine = [i for i, e in enumerate(pool) if e["tenant"] == f"tenant{cid}"]
            for pass_no in range(PASSES_BATCHED):
                for i in mine:
                    entry = pool[i]
                    t0 = time.perf_counter()
                    res = server.submit(_request(entry)).wait(120.0)
                    dt = time.perf_counter() - t0
                    ok = (
                        pass_no != 0
                        or np.array_equal(np.asarray(res.y), y_ref[i])
                    )
                    with lock:
                        lat.append(dt)
                        if not ok:
                            identical[0] = False

        threads = [threading.Thread(target=client, args=(c,)) for c in range(N_TENANTS)]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_all
        stats = server.stats()
    return elapsed, lat, identical[0], stats


def main(scale: float = 0.3) -> list[dict]:
    # The pool is intentionally scale-independent: the scenario is *many
    # small* graphs — shrinking them further would leave nothing to bucket,
    # growing them changes the story to few-large (covered by svc).
    del scale
    pool = _build_pool()
    n_graphs = len(pool)
    print(f"\n== svc_batched: bucketed compiles + micro-batching "
          f"({n_graphs} graphs / {len(FAMILIES)} families, {N_TENANTS} tenants, "
          f"compile cache {COMPILE_CACHE_ENTRIES}) ==")

    with PartitionService(max_entries=n_graphs + 16) as svc:
        # Warm the plan cache outside both measured phases: this bench is
        # about kernel compilation, and §4.2 already keeps partitioning off
        # the request path.
        for entry in pool:
            svc.get_spmv_plan(
                entry["n_rows"], entry["n_cols"], entry["rows"], entry["cols"],
                K, tenant=entry["tenant"],
            )

        un_elapsed, un_lat, y_ref, un_stats = _unbatched_phase(svc, pool)
        b_elapsed, b_lat, identical, b_stats = _batched_phase(svc, pool, y_ref)

    un_rps = n_graphs / max(un_elapsed, 1e-9)
    n_req_b = n_graphs * PASSES_BATCHED
    b_rps = n_req_b / max(b_elapsed, 1e-9)
    un_p50, un_p99 = _pcts(un_lat)
    b_p50, b_p99 = _pcts(b_lat)
    n_buckets = len(b_stats["buckets"])
    compiles = b_stats["misses"]
    hit_rate = b_stats["hits"] / max(b_stats["hits"] + b_stats["misses"], 1)

    rows: list[dict] = [{
        "graph": "batched",
        "n_graphs": n_graphs,
        "n_tenants": N_TENANTS,
        "requests_unbatched": n_graphs,
        "requests_batched": n_req_b,
        "req_per_s_unbatched": un_rps,
        "req_per_s_batched": b_rps,
        "speedup": b_rps / max(un_rps, 1e-9),
        "p50_ms_unbatched": un_p50,
        "p99_ms_unbatched": un_p99,
        "p50_ms_batched": b_p50,
        "p99_ms_batched": b_p99,
        "n_buckets": n_buckets,
        "kernel_compiles_batched": compiles,
        "kernel_compiles_unbatched": un_stats["misses"],
        "kernel_evictions_unbatched": un_stats["evictions"],
        "compiles_ok": compiles <= n_buckets + 1,
        "hit_rate_batched": hit_rate,
        "byte_identical": bool(identical),
    }]
    for label, b in sorted(b_stats["buckets"].items()):
        rows.append({
            "graph": f"bucket={label}",
            "label": label,
            "batch": b["batch"],
            "e_max": b["e_max"],
            "n_rows": b["n_rows"],
            "operand_elems": b["operand_elems"],
            "hits": b["hits"],
            "compiled": b["compiled"],
        })
    rows.append({
        "graph": "batch_hist",
        "hist": {str(k): v for k, v in b_stats["batch_hist"].items()},
    })

    r = rows[0]
    print(f"{'phase':12s} {'req/s':>9s} {'p50_ms':>8s} {'p99_ms':>8s} "
          f"{'compiles':>9s} {'evict':>6s}")
    print(f"{'unbatched':12s} {un_rps:9.1f} {un_p50:8.2f} {un_p99:8.2f} "
          f"{un_stats['misses']:9d} {un_stats['evictions']:6d}")
    print(f"{'batched':12s} {b_rps:9.1f} {b_p50:8.2f} {b_p99:8.2f} "
          f"{compiles:9d} {b_stats['evictions']:6d}")
    print(f"claims: {r['speedup']:.2f}x req/s (gate >= 3x); "
          f"{compiles} compiles for {n_buckets} buckets "
          f"(gate <= {n_buckets + 1}); byte-identical: {identical}; "
          f"bucket hit rate {hit_rate:.3f}")
    return rows


if __name__ == "__main__":
    main()

"""The program's spans and counters (``repro.core.tracing``): the bounded
ring, spans from many threads, and what one request on each of
``GraphServer``'s lanes records."""
import threading
import time

import numpy as np
import pytest

from repro.core import PartitionService, synthetic_bipartite_graph, tracing
from repro.runtime import BucketPolicy, GraphRequest, GraphServer


class TestRing:
    def test_keeps_order_by_start(self):
        ring = tracing.Ring(size=8)
        # An outer span is added after the inner one it holds.
        ring.add("s", 2.0, 3.0, 1.0)
        ring.add("s", 1.0, 4.0, 3.0)
        ring.add("c", 2.5, 64.0, 64.0)
        assert ring.records("s", 0.0, 10.0) == [("s", 1.0, 4.0), ("s", 2.0, 3.0)]
        assert ring.records("s", 1.5, 10.0) == [("s", 2.0, 3.0)]
        assert ring.records("c", 0.0, 2.5) == []
        assert ring.records("none", 0.0, 10.0) == []
        assert ring.snapshot() == {"c": {"count": 1, "total": 64.0},
                                   "s": {"count": 2, "total": 4.0}}

    def test_drops_the_oldest_and_refuses_a_window_it_lost(self):
        ring = tracing.Ring(size=4)
        for t in range(6):
            ring.add("s", float(t), t + 0.5, 0.5)
        # Entries starting at 0 and 1 were overwritten.
        assert ring.records("s", 0.0, 10.0) is None
        assert ring.records("s", 1.0, 10.0) is None
        assert [a for _, a, _ in ring.records("s", 1.5, 10.0)] == [2.0, 3.0, 4.0, 5.0]
        assert ring.snapshot()["s"] == {"count": 6, "total": 3.0}


def test_spans_from_many_threads_are_all_recorded():
    name = f"test.threads.{time.perf_counter_ns()}"
    threads, per_thread = 8, 300
    lo = time.perf_counter()

    def work():
        for _ in range(per_thread):
            with tracing.span(name):
                pass

    pool = [threading.Thread(target=work) for _ in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in pool)
    recs = tracing.records(name, lo, time.perf_counter())
    assert len(recs) == threads * per_thread
    assert all(a <= b for _, a, b in recs)
    assert [a for _, a, _ in recs] == sorted(a for _, a, _ in recs)
    assert tracing.snapshot()[name]["count"] == threads * per_thread


def _request(n, seed):
    _, rows, cols = synthetic_bipartite_graph(n, n, 4, seed=seed)
    rng = np.random.default_rng(seed)
    return GraphRequest(n, n, rows, cols, rng.standard_normal(rows.shape[0]),
                        rng.standard_normal(n))


def _window(lo):
    hi = time.perf_counter()
    names = ("plan.resolve", "plan.affinity", "plan.fingerprint", "plan.warm_hits",
             "serve.launch", "serve.h2d_bytes", "serve.queue_wait")
    return {n: tracing.records(n, lo, hi) for n in names}


@pytest.fixture()
def service():
    with PartitionService() as svc:
        yield svc


@pytest.mark.parametrize("lane", ["dedicated", "bucket"])
def test_one_serve_records_plan_resolution_then_launch(service, lane):
    server = GraphServer(service, k=4, pad=8, start_batcher=False,
                         bucketing=None if lane == "dedicated" else BucketPolicy())
    req = _request(96, seed=3)

    def inside(rec, resolve):
        """Each name's entries that start (and, for spans, end) in it."""
        return {n: [r for r in rec[n] if resolve[1] <= r[1]
                    and (n == "plan.warm_hits" or r[2] <= resolve[2])]
                for n in ("plan.affinity", "plan.fingerprint", "plan.warm_hits")}

    # Cold: the COO key, then the affinity graph for the plan build.
    lo = time.perf_counter()
    server.serve(req)  # plan build, compile and the plan's own uploads
    rec = _window(lo)
    (resolve,) = rec["plan.resolve"]
    cold = inside(rec, resolve)
    (key,), (affinity,) = cold["plan.fingerprint"], cold["plan.affinity"]
    assert key[2] <= affinity[1] and cold["plan.warm_hits"] == []

    # Warm: the key alone, answered from the cache with no affinity graph.
    lo = time.perf_counter()
    res = server.serve(req)
    rec = _window(lo)
    assert (res.info.bucket is None) == (lane == "dedicated")
    assert res.info.cache_hit

    (resolve,) = rec["plan.resolve"]
    (launch,) = rec["serve.launch"]
    warm = inside(rec, resolve)
    assert len(warm["plan.fingerprint"]) == 1
    assert rec["plan.affinity"] == []
    (hit,) = warm["plan.warm_hits"]
    assert hit[2] == 1
    assert resolve[2] <= launch[1]

    staged = sum(b for _, _, b in rec["serve.h2d_bytes"])
    if lane == "dedicated":
        assert staged == req.x.nbytes
    else:
        # vals, x_lidx, y_lidx, x_gidx, y_gidx and x, all 4-byte elements.
        assert staged == 4 * server._specs[res.info.bucket].operand_elems()
    assert rec["serve.queue_wait"] == []
    spans = server.stats()["spans"]
    assert spans["plan.resolve"]["count"] >= 2 and spans["serve.launch"]["total"] > 0
    assert spans["plan.warm_hits"]["count"] >= 1


@pytest.mark.parametrize("size", ["inline", "pooled"])
def test_a_serve_counts_the_plan_keys_bytes_and_pooled_leaves(service, size):
    # 36,864 rows of degree ~4: more than one 1 MiB leaf of int64 in each
    # of rows and cols, so at least three leaves in all.
    req = _request(96 if size == "inline" else 36864, seed=5)
    leaves = 2 * -(-req.rows.nbytes // (1 << 20))
    assert (leaves >= 3) == (size == "pooled")
    server = GraphServer(service, k=4, pad=8, start_batcher=False, bucketing=None)
    before = server.stats()["spans"]
    lo = time.perf_counter()
    server.serve(req)
    hi = time.perf_counter()
    after = server.stats()["spans"]

    def added(name):
        was = before.get(name, {"count": 0, "total": 0.0})
        now = after.get(name, {"count": 0, "total": 0.0})
        return now["count"] - was["count"], now["total"] - was["total"]

    key_bytes = req.rows.nbytes + req.cols.nbytes
    assert added("plan.fingerprint_bytes") == (1, key_bytes)
    assert [b for _, _, b in tracing.records("plan.fingerprint_bytes", lo, hi)] == [key_bytes]
    pooled = 1 if size == "pooled" else 0
    assert added("plan.fingerprint_pooled") == (pooled, pooled)


def test_submit_records_the_queue_wait(service):
    req = _request(96, seed=4)
    with GraphServer(service, k=4, pad=8, max_wait_ms=1.0) as server:
        server.serve(req)
        lo = time.perf_counter()
        handle = server.submit(req)
        handle.wait(60.0)
        rec = _window(lo)
    (wait,) = rec["serve.queue_wait"]
    assert wait[1] == handle.t_enqueue and wait[2] >= wait[1]
    # Bucket choice on the caller's thread, staging on the batcher's.
    assert len(rec["serve.launch"]) == 2


@pytest.mark.parametrize("size", ["inline", "pooled"])
def test_a_serve_counts_the_value_digests_bytes_and_pooled_leaves(service, size):
    # 135,168 rows of degree ~4: more than two 1 MiB leaves of float32 vals.
    req = _request(96 if size == "inline" else 135168, seed=6)
    leaves = -(-req.vals.nbytes // (1 << 20))
    assert (leaves >= 3) == (size == "pooled")
    key_leaves = 2 * -(-req.rows.nbytes // (1 << 20))
    server = GraphServer(service, k=4, pad=8, start_batcher=False, bucketing=None)
    before = server.stats()["spans"]
    lo = time.perf_counter()
    server.serve(req)
    hi = time.perf_counter()
    after = server.stats()["spans"]

    def added(name):
        was = before.get(name, {"count": 0, "total": 0.0})
        now = after.get(name, {"count": 0, "total": 0.0})
        return now["count"] - was["count"], now["total"] - was["total"]

    (digest,) = tracing.records("serve.vals_digest", lo, hi)
    (launch,) = tracing.records("serve.launch", lo, hi)
    assert launch[1] <= digest[1] <= digest[2] <= launch[2]
    assert added("serve.vals_digest")[0] == 1
    assert added("serve.vals_digest_bytes") == (1, req.vals.nbytes)
    pooled = 1 if size == "pooled" else 0
    assert added("serve.vals_digest_pooled") == (pooled, pooled)
    # The plan key's pooled counter counts the plan key alone.
    key_pooled = 1 if key_leaves >= 3 else 0
    assert added("plan.fingerprint_pooled") == (key_pooled, key_pooled)
    assert {"serve.vals_digest", "serve.vals_digest_bytes"} <= after.keys()

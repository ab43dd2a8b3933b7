"""Request API + bucketed compilation + micro-batched serving (GraphServer).

Covers the serve-path guarantees:
  * bucket ceilings are exact at the boundary (a dim exactly at a geometric
    ceiling stays there; one past it doubles) and oversized requests fall
    back to a dedicated compile instead of crashing;
  * zero-padded operand tails are invisible — bucketed results match the
    kernels/ref oracle and are byte-identical to dedicated serving;
  * one compiled kernel serves every structure in a bucket;
  * the compile cache evicts by (size, recency) and surfaces counters
    through ``GraphServer.metrics()``;
  * micro-batched requests keep per-request tenant/batch attribution.
"""
import numpy as np
import pytest

from repro.core import PartitionService, PlanPadding, synthetic_bipartite_graph
from repro.kernels import pad_plan_operands
from repro.runtime import (
    BucketKey,
    BucketPolicy,
    CompileCache,
    GraphRequest,
    GraphServer,
)


@pytest.fixture()
def service():
    with PartitionService() as svc:
        yield svc


def _entry(n_rows, n_cols, nnz_per_row, seed):
    _, rows, cols = synthetic_bipartite_graph(n_rows, n_cols, nnz_per_row, seed=seed)
    rng = np.random.default_rng(seed + 1000)
    vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
    x = rng.standard_normal(n_cols).astype(np.float32)
    return GraphRequest(n_rows, n_cols, rows, cols, vals, x)


def _ref(req: GraphRequest) -> np.ndarray:
    import jax.numpy as jnp

    from repro.kernels.ref import spmv_coo_ref

    return np.asarray(spmv_coo_ref(
        req.n_rows, jnp.asarray(req.rows), jnp.asarray(req.cols),
        jnp.asarray(req.vals), jnp.asarray(req.x),
    ))


def _padding(n_rows, n_cols, nnz, k=4):
    return PlanPadding(pad=8, k=k, n_rows=n_rows, n_cols=n_cols, nnz=nnz,
                       e_max=0, x_max=0, y_max=0)


class TestBucketPolicy:
    def test_floors_and_growth(self):
        pol = BucketPolicy()
        key = pol.bucket_for(_padding(10, 10, 10), "software")
        assert (key.n_rows, key.n_cols, key.nnz) == (256, 256, 1024)
        key = pol.bucket_for(_padding(300, 600, 3000), "software")
        assert (key.n_rows, key.n_cols, key.nnz) == (512, 1024, 4096)

    def test_exactly_at_ceiling_stays(self):
        pol = BucketPolicy()
        key = pol.bucket_for(_padding(256, 512, 2048), "software")
        assert (key.n_rows, key.n_cols, key.nnz) == (256, 512, 2048)
        # One past any ceiling doubles that dim only.
        key = pol.bucket_for(_padding(257, 512, 2048), "software")
        assert (key.n_rows, key.n_cols, key.nnz) == (512, 512, 2048)
        key = pol.bucket_for(_padding(256, 512, 2049), "software")
        assert (key.n_rows, key.n_cols, key.nnz) == (256, 512, 4096)

    def test_oversized_returns_none(self):
        pol = BucketPolicy(max_rows=64, max_cols=64, max_nnz=128)
        assert pol.bucket_for(_padding(65, 10, 10), "software") is None
        assert pol.bucket_for(_padding(10, 10, 129), "software") is None
        assert pol.bucket_for(_padding(64, 64, 128), "software") is not None

    def test_key_identity_and_label(self):
        pol = BucketPolicy()
        a = pol.bucket_for(_padding(150, 150, 900), "software")
        b = pol.bucket_for(_padding(200, 130, 1000), "software")
        assert a == b and a.label == b.label  # shared compile key
        assert a.label == "r256c256e1024k4-software"
        assert pol.bucket_for(_padding(150, 150, 900), "streaming") != a


class TestBucketSpec:
    def test_fits_and_pad_rejects_too_small(self, service):
        _, rows, cols = synthetic_bipartite_graph(96, 96, 4, seed=0)
        sp = service.get_spmv_plan(96, 96, rows, cols, k=4, pad=8)
        key = BucketPolicy().bucket_for(sp.padding, "software")
        spec = key.spec(batch=2, pad=8)
        assert spec.fits(sp.plan)
        vals = np.ones(rows.shape[0], dtype=np.float32)
        ops = pad_plan_operands(sp.plan, vals, spec)
        assert ops[0].shape == (spec.k, spec.e_max)
        # Tail slots are zero vals / sentinel rows — nothing to contribute.
        e_counts = np.asarray(sp.plan.e_count)
        for c in range(spec.k):
            assert not ops[0][c, e_counts[c]:].any()
        small = BucketKey(8, 8, 8, k=4, mode="software").spec(batch=1, pad=8)
        assert not small.fits(sp.plan)
        with pytest.raises(ValueError):
            pad_plan_operands(sp.plan, vals, small)


class TestGraphRequest:
    def test_normalizes_dtypes(self):
        req = _entry(32, 32, 2, seed=0)
        req2 = GraphRequest(32, 32, req.rows.astype(np.int32),
                            req.cols.astype(np.int32),
                            req.vals.astype(np.float64),
                            req.x.astype(np.float64))
        assert req2.rows.dtype == np.int64 and req2.vals.dtype == np.float32
        assert req2.x.dtype == np.float32

    def test_rejects_bad_shapes(self):
        req = _entry(32, 32, 2, seed=0)
        with pytest.raises(ValueError):
            GraphRequest(32, 32, req.rows, req.cols, req.vals, req.x[:-1])
        with pytest.raises(ValueError):
            GraphRequest(32, 32, req.rows, req.cols, req.vals[:-1], req.x)

    def test_vals_digest_tracks_values(self):
        req = _entry(32, 32, 2, seed=0)
        d1 = req.vals_digest()
        req.vals = req.vals + 1.0
        assert req.vals_digest() != d1


class TestCompileCache:
    def test_hit_miss_counters_and_single_build(self):
        cache = CompileCache(capacity=4)
        built = []
        for _ in range(3):
            fn = cache.get_or_build("k", 10, lambda: built.append(1) or "fn")
        assert fn == "fn" and len(built) == 1
        assert cache.misses == 1 and cache.hits == 2
        assert cache.hits_for("k") == 2

    def test_evicts_largest_of_oldest_quarter(self):
        cache = CompileCache(capacity=4)
        for key, size in [("a", 1), ("b", 10), ("c", 1), ("d", 1)]:
            cache.get_or_build(key, size, lambda: key)
        cache.get_or_build("a", 1, lambda: "a")  # refresh a's recency
        cache.get_or_build("e", 1, lambda: "e")  # overflow -> evict
        # Victim cohort is the oldest quarter {b, c}; b is bigger.
        assert "b" not in cache and "a" in cache and "c" in cache
        assert cache.evictions == 1
        assert len(cache) == 4

    def test_rejects_zero_capacity(self):
        with pytest.raises(ValueError):
            CompileCache(capacity=0)


class TestGraphServerServe:
    def test_bucketed_matches_ref_across_sweep(self, service):
        server = GraphServer(service, k=4, pad=8, start_batcher=False)
        for n_rows, n_cols, npr in [(64, 64, 3), (96, 80, 4), (150, 150, 5)]:
            for seed in range(2):
                req = _entry(n_rows, n_cols, npr, seed=seed)
                res = server.serve(req)
                assert res.info.bucket is not None
                assert res.y.shape == (n_rows,)  # de-padded
                np.testing.assert_allclose(np.asarray(res.y), _ref(req),
                                           rtol=1e-5, atol=1e-5)

    def test_bucketed_byte_identical_to_dedicated(self, service):
        bucketed = GraphServer(service, k=4, pad=8, start_batcher=False)
        dedicated = GraphServer(service, k=4, pad=8, bucketing=None,
                                start_batcher=False)
        for seed in range(3):
            req = _entry(120, 120, 4, seed=seed)
            y_b = np.asarray(bucketed.serve(req).y)
            y_d = np.asarray(dedicated.serve(req).y)
            assert np.array_equal(y_b, y_d)  # byte-identical, not just close

    def test_same_bucket_shares_one_compile(self, service):
        server = GraphServer(service, k=4, pad=8, start_batcher=False)
        r1 = server.serve(_entry(150, 150, 4, seed=0))
        r2 = server.serve(_entry(150, 150, 4, seed=1))  # distinct structure
        assert r1.info.bucket == r2.info.bucket
        assert not r1.info.kernel_cache_hit and r2.info.kernel_cache_hit
        stats = server.stats()
        assert stats["misses"] == 1 and stats["hits"] >= 1
        assert len(stats["buckets"]) == 1

    def test_family_sweep_compiles_once_per_bucket(self, service):
        # Four shape families of small graphs, two structures each, at k=8:
        # each family fills its own bucket, every bucket compiles once, and
        # every bucketed answer is byte-identical to a dedicated compile.
        families = [(150, 150, 4), (150, 150, 16), (300, 300, 5), (300, 300, 3)]
        bucketed = GraphServer(service, k=8, start_batcher=False)
        dedicated = GraphServer(service, k=8, bucketing=None,
                                start_batcher=False)
        for fam, (n_rows, n_cols, npr) in enumerate(families):
            for g in range(2):
                req = _entry(n_rows, n_cols, npr, seed=1000 * fam + g)
                y_b = np.asarray(bucketed.serve(req).y)
                assert np.array_equal(y_b, np.asarray(dedicated.serve(req).y))
        stats = bucketed.stats()
        assert len(stats["buckets"]) == len(families)
        assert stats["misses"] <= len(stats["buckets"]) + 1
        assert stats["hits"] == 2 * len(families) - stats["misses"]

    def test_exactly_at_ceiling_request_serves(self, service):
        server = GraphServer(service, k=4, pad=8, start_batcher=False)
        req = _entry(256, 256, 3, seed=0)  # n_rows/n_cols exactly at floor
        res = server.serve(req)
        assert res.info.bucket.startswith("r256c256")
        np.testing.assert_allclose(np.asarray(res.y), _ref(req),
                                   rtol=1e-5, atol=1e-5)

    def test_oversized_falls_back_to_dedicated(self, service):
        pol = BucketPolicy(max_rows=64, max_cols=64, max_nnz=128)
        server = GraphServer(service, k=4, pad=8, bucketing=pol,
                             start_batcher=False)
        req = _entry(96, 96, 4, seed=0)
        res = server.serve(req)
        assert res.info.bucket is None and res.info.batch_size == 1
        np.testing.assert_allclose(np.asarray(res.y), _ref(req),
                                   rtol=1e-5, atol=1e-5)
        assert server.stats()["buckets"] == {}

    def test_eviction_surfaced_in_metrics(self, service):
        server = GraphServer(service, k=4, pad=8, bucketing=None,
                             compile_cache_entries=1, start_batcher=False)
        server.serve(_entry(64, 64, 3, seed=0))
        server.serve(_entry(64, 64, 3, seed=1))
        cc = server.metrics().compile_cache
        assert cc["misses"] == 2 and cc["evictions"] >= 1
        assert cc["entries"] == 1

    def test_submit_requires_batcher(self, service):
        server = GraphServer(service, k=4, pad=8, start_batcher=False)
        with pytest.raises(RuntimeError):
            server.submit(_entry(64, 64, 3, seed=0))


class TestGraphServerBatching:
    def test_mixed_tenant_batch_attribution(self, service):
        reqs = []
        for i, tenant in enumerate(["acme", "globex", "initech"]):
            req = _entry(100, 100, 4, seed=20 + i)
            req.tenant = tenant
            reqs.append(req)
        with GraphServer(service, k=4, pad=8, max_batch=4,
                         max_wait_ms=300.0) as server:
            # Warm plans + the bucket executable so the submits below land
            # inside one batch window.
            warm = {id(r): np.asarray(server.serve(r).y) for r in reqs}
            handles = [server.submit(r) for r in reqs]
            results = [h.wait(60.0) for h in handles]
            hist = server.stats()["batch_hist"]
        for req, res in zip(reqs, results):
            assert res.info.tenant == req.tenant  # per-request attribution
            assert res.info.bucket is not None
            assert res.info.batch_size == 3
            # Stacked launch, de-padded: byte-identical to the batch-of-1.
            assert np.array_equal(np.asarray(res.y), warm[id(req)])
        assert hist.get(3, 0) >= 1

    def test_submit_after_close_raises(self, service):
        server = GraphServer(service, k=4, pad=8)
        server.close()
        with pytest.raises(RuntimeError):
            server.submit(_entry(64, 64, 3, seed=0))


class TestBrownout:
    def test_ladder_levels_and_window_recovery(self, service):
        import time

        server = GraphServer(service, k=4, pad=8, start_batcher=False,
                             brownout_window_s=0.25, brownout_hedge_off=2,
                             brownout_stale_only=4)
        assert server.brownout_level() == 0
        server._note_rejection()
        assert server.brownout_level() == 0  # below the first rung
        server._note_rejection()
        assert server.brownout_level() == 1  # hedging off
        server._note_rejection()
        server._note_rejection()
        assert server.brownout_level() == 2  # stale-only for low priority
        assert server.stats()["brownout_level"] == 2
        time.sleep(0.3)
        # Rejections aged out of the window: recovery is automatic.
        assert server.brownout_level() == 0

    def test_hedge_rung_disables_and_restores_group_hedging(self):
        import time

        from repro.core import ReplicaGroup

        with ReplicaGroup(2) as g:
            server = GraphServer(service=g, k=4, start_batcher=False,
                                 brownout_window_s=0.25,
                                 brownout_hedge_off=1,
                                 brownout_stale_only=99)
            req = _entry(96, 96, 3, seed=0)
            server.serve(req)
            assert g.hedge  # level 0: hedging untouched
            server._note_rejection()
            server.serve(req)
            assert not g.hedge  # level 1: hedging saved + disabled
            time.sleep(0.3)
            server.serve(req)
            assert g.hedge  # pressure aged out: hedging restored

    def test_stale_only_serves_cached_degraded_and_rejects_cold(self):
        from repro.core import AdmissionRejectedError, ReplicaGroup

        with ReplicaGroup(2, hedge=False) as g:
            server = GraphServer(service=g, k=4, start_batcher=False,
                                 brownout_window_s=5.0,
                                 brownout_hedge_off=1,
                                 brownout_stale_only=2,
                                 brownout_priority_floor=1)
            hot = _entry(96, 96, 3, seed=1)  # default priority 0 < floor
            res = server.serve(hot)
            assert not res.info.degraded
            for _ in range(2):
                server._note_rejection()  # push to the stale-only rung
            # The warmed graph still answers — from cache, flagged.
            res2 = server.serve(hot)
            assert res2.info.degraded
            assert res2.info.as_dict()["degraded"] is True
            np.testing.assert_array_equal(np.asarray(res2.y),
                                          np.asarray(res.y))
            # An uncached graph from a low-priority tenant is refused with
            # the typed brownout rejection (retry ~ the pressure window).
            cold = _entry(96, 96, 3, seed=2)
            with pytest.raises(AdmissionRejectedError) as ei:
                server.serve(cold)
            assert ei.value.reason == "brownout"
            assert ei.value.retry_after_s == 5.0
            # Priority at/above the floor bypasses the rung entirely.
            vip = _entry(96, 96, 3, seed=3)
            vip.priority = 1
            res3 = server.serve(vip)
            assert not res3.info.degraded
            stats = server.stats()
            assert stats["degraded_serves"] >= 1
            assert stats["brownout_rejects"] >= 1
            assert stats["brownout_level"] == 2


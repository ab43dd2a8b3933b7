"""PartitionService: cache semantics, incremental repartition bounds, kernels.

Covers the serving-path guarantees:
  * warm cache hits return the identical plan object without re-running the
    partitioner (and are orders of magnitude faster than a cold run);
  * incremental repartition preserves the (1+eps) balance bound and stays
    within tolerance of a full repartition's vertex cut;
  * EP-SpMV under a service-supplied plan matches the kernels/ref oracle;
  * async tickets + double buffer publish exactly the computed plan.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (
    DoubleBuffer,
    MultilevelOptions,
    PartitionService,
    ServiceClosedError,
    edge_partition,
    evaluate_edge_partition,
    graph_fingerprint,
    incremental_repartition,
    incremental_repartition_reference,
    synthetic_banded_graph,
    synthetic_bipartite_graph,
    synthetic_mesh_graph,
    synthetic_powerlaw_graph,
    synthetic_random_graph,
)


@pytest.fixture()
def service():
    with PartitionService() as svc:
        yield svc


def _churn(edges, frac, seed=0, n=None):
    """Half deletions, half insertions totalling ``frac * m`` tasks."""
    rng = np.random.default_rng(seed)
    n = n if n is not None else edges.n
    n_half = max(int(frac * edges.m / 2), 1)
    delete_ids = rng.choice(edges.m, size=n_half, replace=False)
    ins_u = rng.integers(0, n, n_half).astype(np.int64)
    ins_v = rng.integers(0, n, n_half).astype(np.int64)
    return ins_u, ins_v, delete_ids


class TestCache:
    def test_warm_hit_identical_plan_no_recompute(self, service):
        e = synthetic_mesh_graph(24, seed=0)
        p1 = service.get(e, 8)
        runs_after_cold = service.stats.full_runs
        p2 = service.get(e, 8)
        assert p2 is p1  # the very same object, not an equal recomputation
        assert service.stats.full_runs == runs_after_cold
        assert service.stats.hits >= 1

    def test_fingerprint_sensitivity(self):
        e = synthetic_mesh_graph(12, seed=0)
        base = graph_fingerprint(e, 4)
        assert graph_fingerprint(e, 8) != base  # k changes the plan
        assert graph_fingerprint(e, 4, pad=8) != base
        e2 = synthetic_mesh_graph(12, seed=0)
        assert graph_fingerprint(e2, 4) == base  # content-addressed, not id

    def test_distinct_graphs_distinct_plans(self, service):
        a = synthetic_mesh_graph(16, seed=0)
        b = synthetic_powerlaw_graph(200, 600, seed=1)
        pa = service.get(a, 4)
        pb = service.get(b, 4)
        assert pa.fingerprint != pb.fingerprint
        assert service.stats.misses == 2

    def test_cost_scored_eviction_at_entry_cap(self):
        with PartitionService(max_entries=2) as svc:
            graphs = [synthetic_mesh_graph(10 + i, seed=i) for i in range(3)]
            plans = [svc.get(g, 2) for g in graphs]
            assert len(svc) == 2
            assert svc.stats.evictions == 1
            # Cost-aware policy: of the two resident plans, the one buying
            # the fewest recompute-seconds per byte is evicted (ties fall
            # back to LRU); the fresh insert is never its own victim.
            scores = {
                p.fingerprint: p.compute_time_s / max(p.nbytes(), 1)
                for p in plans[:2]
            }
            victim = min(scores, key=scores.get)
            survivor = next(fp for fp in scores if fp != victim)
            assert svc.lookup(victim) is None
            assert svc.lookup(survivor) is not None
            assert svc.lookup(plans[2].fingerprint) is plans[2]

    def test_warm_lookup_much_faster_than_cold(self, service):
        """A warm lookup does no partitioning work: it returns the cached
        plan object, counts one hit and starts no partition run — what made
        it hundreds of times faster than the cold build, without timing."""
        e, _, _ = synthetic_bipartite_graph(1024, 1024, 6, seed=0)
        p1 = service.get(e, 16)
        assert (service.stats.full_runs, service.stats.hits) == (1, 0)
        for i in range(1, 6):
            assert service.get(e, 16) is p1
            assert (service.stats.full_runs, service.stats.hits) == (1, i)


class TestAsync:
    def test_ticket_and_double_buffer(self, service):
        e = synthetic_mesh_graph(20, seed=0)
        buf = DoubleBuffer()
        assert buf.current() == (None, 0)
        ticket = service.submit(e, 4, buffer=buf)
        plan = ticket.result(timeout=60)
        assert ticket.done()
        published, gen = buf.current()
        assert published is plan
        assert gen == 1

    def test_inflight_dedup(self, service):
        e = synthetic_mesh_graph(28, seed=1)
        t1 = service.submit(e, 8)
        t2 = service.submit(e, 8)
        p1, p2 = t1.result(60), t2.result(60)
        assert p1 is p2
        assert service.stats.full_runs == 1

    def test_inflight_dedup_publishes_to_every_buffer(self, service):
        e = synthetic_powerlaw_graph(600, 2400, seed=3)
        buf1, buf2 = DoubleBuffer(), DoubleBuffer()
        t1 = service.submit(e, 8, buffer=buf1)
        t2 = service.submit(e, 8, buffer=buf2)  # deduped onto t1's computation
        plan = t2.result(60)
        t1.result(60)
        # Both callers' serving loops must observe the swap.
        assert buf1.current()[0] is plan
        assert buf2.current()[0] is plan

    def test_update_does_not_inflate_hit_stats(self, service):
        e = synthetic_powerlaw_graph(800, 3200, seed=8)
        plan = service.get(e, 8)
        hits_before = service.stats.hits
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=9)
        service.update(plan.fingerprint, 8, insert_u=ins_u, insert_v=ins_v,
                       delete_ids=delete_ids)
        # A cold update is a miss; resolving the base must not count as a hit.
        assert service.stats.hits == hits_before

    def test_update_after_eviction_raises_keyerror(self):
        with PartitionService(max_entries=1) as svc:
            a = synthetic_mesh_graph(12, seed=0)
            b = synthetic_mesh_graph(14, seed=1)
            pa = svc.get(a, 2)
            svc.get(b, 2)  # evicts a
            with pytest.raises(KeyError, match="resubmit"):
                svc.update(pa.fingerprint, 2, insert_u=np.array([0]),
                           insert_v=np.array([1]))

    def test_worker_error_propagates(self, service):
        e = synthetic_mesh_graph(8, seed=0)
        ticket = service.submit(e, 0)  # invalid k
        with pytest.raises(ValueError):
            ticket.result(timeout=60)
        # Service survives and keeps serving.
        assert service.get(e, 2).result.k == 2

    def test_close_fails_pending_tickets(self):
        svc = PartitionService(start=False)  # no worker: requests stay queued
        e = synthetic_mesh_graph(16, seed=0)
        ticket = svc.submit(e, 4)
        svc.close()
        # Queued tickets fail with the dedicated error (a RuntimeError
        # subclass), not a hang.
        with pytest.raises(ServiceClosedError, match="closed"):
            ticket.result(timeout=5)
        # Submitting after close fails fast instead of hanging.
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit(synthetic_mesh_graph(8, seed=1), 2).result(timeout=5)

    def test_close_is_idempotent(self):
        svc = PartitionService(start=False)
        ticket = svc.submit(synthetic_mesh_graph(12, seed=0), 4)
        svc.close()
        svc.close()  # second close: no-op, no error, no new failures
        assert svc.closed
        with pytest.raises(ServiceClosedError):
            ticket.result(timeout=5)

    def test_context_manager_double_exit_safe(self):
        svc = PartitionService(start=False)
        with svc:
            pass
        svc.close()  # explicit close after __exit__ already closed

    def test_service_reusable_after_close(self):
        """Old behavior preserved: start() (or re-entering the context
        manager) revives a closed service and it serves again."""
        svc = PartitionService()
        e = synthetic_mesh_graph(12, seed=6)
        with svc:
            plan = svc.get(e, 4)
        assert svc.closed
        with svc:  # __enter__ -> start() reopens
            assert not svc.closed
            assert svc.get(e, 4) is plan  # cache survived the close
            f = synthetic_mesh_graph(14, seed=7)
            assert svc.get(f, 4).result.k == 4  # fresh compute works too

    def test_close_during_inflight_churn_fails_queued_updates(self):
        """close() while a churn job is mid-flight: the running update
        completes and resolves; the incremental tickets *queued behind it*
        fail with ServiceClosedError instead of hanging their get()."""
        svc = PartitionService(workers=1)
        e = synthetic_powerlaw_graph(600, 2400, seed=5)
        plan = svc.get(e, 8)
        started, release = threading.Event(), threading.Event()

        def hold(_key):  # keeps the first churn job "in flight"
            started.set()
            release.wait(10)

        svc.scheduler.pre_job_hook = hold
        iu1, iv1, dele1 = _churn(e, 0.01, seed=6)
        t_inflight = svc.update_async(plan.fingerprint, 8, insert_u=iu1,
                                      insert_v=iv1, delete_ids=dele1)
        assert started.wait(10)
        iu2, iv2, _ = _churn(e, 0.02, seed=7)
        t_q1 = svc.update_async(plan.fingerprint, 8, insert_u=iu2, insert_v=iv2)
        t_q2 = svc.update_async(plan.fingerprint, 8, delete_ids=dele1)
        closer = threading.Thread(target=svc.close)
        closer.start()
        # close() drains the queue first, then blocks on the in-flight job.
        with pytest.raises(ServiceClosedError):
            t_q1.result(timeout=10)
        with pytest.raises(ServiceClosedError):
            t_q2.result(timeout=10)
        assert not t_inflight.done()
        release.set()
        closer.join(30)
        assert not closer.is_alive()
        assert t_inflight.result(timeout=10).source in ("incremental", "full")

    def test_ticket_cache_hit_flag(self, service):
        e = synthetic_mesh_graph(20, seed=0)
        t1 = service.submit(e, 4)
        t1.result(60)
        assert not t1.cache_hit
        t2 = service.submit(e, 4)
        assert t2.cache_hit and t2.done()


class TestIncremental:
    @pytest.mark.parametrize("graph_seed", [0, 1])
    def test_balance_bound_preserved(self, graph_seed):
        e = synthetic_powerlaw_graph(1500, 6000, seed=graph_seed)
        k, eps = 16, 0.03
        res = edge_partition(e, k, method="ep")
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=graph_seed)
        new_e, labels, stats = incremental_repartition(
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
            delete_ids=delete_ids, eps=eps,
        )
        assert labels.shape == (new_e.m,)
        assert labels.min() >= 0 and labels.max() < k
        counts = np.bincount(labels, minlength=k)
        cap = (1 + eps) * np.ceil(new_e.m / k) + 1
        assert counts.max() <= cap
        assert stats.balance_ok

    def test_blocked_scoring_matches_unblocked(self, monkeypatch):
        """Scoring rows in bounded (rows, k) blocks — what keeps the gear
        inside host memory at k=1024 on millions of tasks — must not change
        a single label."""
        from repro.core import partition_service as ps

        e = synthetic_powerlaw_graph(1500, 6000, seed=2)
        k = 16
        res = edge_partition(e, k, method="ep")
        ins_u, ins_v, delete_ids = _churn(e, 0.05, seed=2)
        run = lambda: incremental_repartition(  # noqa: E731
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids)
        _, whole, st_whole = run()
        monkeypatch.setattr(ps, "_SCORE_BLOCK_CELLS", 3 * k)  # 3-row blocks
        _, blocked, st_blocked = run()
        assert st_whole.moves > 0 and st_blocked.moves == st_whole.moves
        np.testing.assert_array_equal(blocked, whole)

    def test_cut_within_tolerance_of_full(self):
        e = synthetic_mesh_graph(40, seed=0)
        k = 16
        res = edge_partition(e, k, method="ep")
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=3)
        new_e, labels, stats = incremental_repartition(
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids
        )
        inc_cut = evaluate_edge_partition(new_e, labels, k).vertex_cut
        full_cut = edge_partition(new_e, k, method="ep").quality.vertex_cut
        # Localized refinement from a good start must not lose much ground
        # against a from-scratch multilevel run (often it's slightly ahead).
        assert inc_cut <= 1.35 * full_cut + 5

    def test_edge_list_composition(self):
        e = synthetic_mesh_graph(10, seed=0)
        res = edge_partition(e, 4, method="ep")
        delete_ids = np.array([0, 5])
        ins_u = np.array([1, 2], dtype=np.int64)
        ins_v = np.array([3, 4], dtype=np.int64)
        new_e, labels, _ = incremental_repartition(
            e, res.labels, 4, insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids
        )
        assert new_e.m == e.m  # -2 deletions +2 insertions
        keep = np.ones(e.m, dtype=bool)
        keep[delete_ids] = False
        np.testing.assert_array_equal(new_e.u[:-2], e.u[keep])
        np.testing.assert_array_equal(new_e.v[-2:], ins_v)

    def test_pure_deletion_and_pure_insertion(self):
        e = synthetic_mesh_graph(12, seed=0)
        res = edge_partition(e, 4, method="ep")
        new_e, labels, stats = incremental_repartition(
            e, res.labels, 4, delete_ids=np.arange(5)
        )
        assert new_e.m == e.m - 5 and labels.shape == (new_e.m,)
        new_e2, labels2, _ = incremental_repartition(
            e, res.labels, 4, insert_u=np.array([0, 1]), insert_v=np.array([2, 3])
        )
        assert new_e2.m == e.m + 2 and labels2.shape == (new_e2.m,)

    def test_service_update_uses_incremental_under_threshold(self, service):
        e = synthetic_powerlaw_graph(1200, 5000, seed=2)
        k = 8
        plan = service.get(e, k)
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=4)
        upd = service.update(
            plan.fingerprint, k, insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids
        )
        assert upd.source == "incremental"
        assert service.stats.incremental_runs == 1
        assert upd.result.quality.balance <= 1.03 + k / upd.edges.m + 0.01

    def test_repeated_identical_update_hits_cache(self, service):
        e = synthetic_powerlaw_graph(800, 3000, seed=6)
        k = 8
        plan = service.get(e, k)
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=7)
        u1 = service.update(plan.fingerprint, k, insert_u=ins_u, insert_v=ins_v,
                            delete_ids=delete_ids)
        runs = service.stats.incremental_runs + service.stats.full_runs
        u2 = service.update(plan.fingerprint, k, insert_u=ins_u, insert_v=ins_v,
                            delete_ids=delete_ids)
        assert u2 is u1  # churn memo: no recompute, identical plan object
        assert service.stats.incremental_runs + service.stats.full_runs == runs

    def test_service_update_falls_back_on_heavy_churn(self, service):
        e = synthetic_mesh_graph(24, seed=0)
        k = 4
        plan = service.get(e, k)
        # 50% churn >> churn_threshold -> full multilevel rerun.
        ins_u, ins_v, delete_ids = _churn(e, 0.5, seed=5)
        upd = service.update(
            plan.fingerprint, k, insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids
        )
        assert upd.source == "full"
        assert service.stats.incremental_runs == 0

    def test_incremental_faster_than_full(self, service):
        e, rows, cols = synthetic_bipartite_graph(2048, 2048, 8, seed=0)
        k = 32
        plan = service.get_spmv_plan(2048, 2048, rows, cols, k=k)
        rng = np.random.default_rng(9)
        n_half = max(int(0.005 * e.m), 1)
        delete_ids = rng.choice(e.m, size=n_half, replace=False)
        ins_rows = rng.integers(0, 2048, n_half)
        ins_cols = rng.integers(0, 2048, n_half)
        t0 = time.perf_counter()
        upd = service.update(
            plan.fingerprint, k,
            insert_u=ins_cols.astype(np.int64),
            insert_v=(2048 + ins_rows).astype(np.int64),
            delete_ids=delete_ids,
        )
        inc_t = time.perf_counter() - t0
        assert upd.source == "incremental"
        t0 = time.perf_counter()
        edge_partition(upd.edges, k, method="ep")
        full_t = time.perf_counter() - t0
        # Bar is 3x: the batched dirty-region sweep runs 5-14x ahead of a
        # full rerun at bench scale (see the svc bench); 3x leaves headroom
        # for noisy shared CI runners while still catching a fallback to
        # Python-loop-era latencies.
        assert full_t / inc_t >= 3, f"full {full_t:.3f}s / incremental {inc_t:.3f}s"


class TestIncrementalValidation:
    @pytest.mark.parametrize(
        "impl", [incremental_repartition, incremental_repartition_reference]
    )
    def test_delete_ids_out_of_range_raise(self, impl):
        """Out-of-range ids must fail loudly: a negative id would silently
        wrap around to a real task, a past-the-end id is not a task."""
        e = synthetic_mesh_graph(10, seed=0)
        res = edge_partition(e, 4, method="ep")
        with pytest.raises(ValueError, match="delete_ids"):
            impl(e, res.labels, 4, delete_ids=np.array([e.m]))
        with pytest.raises(ValueError, match="wrap"):
            impl(e, res.labels, 4, delete_ids=np.array([-1]))
        with pytest.raises(ValueError, match="delete_ids"):
            impl(e, res.labels, 4, delete_ids=np.array([0, 3, e.m + 7]))
        # In-range ids still work after the same-call validation.
        new_e, labels, _ = impl(e, res.labels, 4, delete_ids=np.array([0, 3]))
        assert new_e.m == e.m - 2 and labels.shape == (new_e.m,)

    def test_service_update_propagates_validation_error(self, service):
        e = synthetic_mesh_graph(12, seed=0)
        plan = service.get(e, 4)
        with pytest.raises(ValueError, match="delete_ids"):
            service.update(plan.fingerprint, 4, delete_ids=np.array([-5]))
        # The worker survives a poisoned request and keeps serving.
        assert service.get(e, 4) is plan


def _graph_cases():
    return [
        ("mesh", lambda: synthetic_mesh_graph(24, seed=0)),
        ("powerlaw", lambda: synthetic_powerlaw_graph(800, 3000, seed=1)),
        ("banded", lambda: synthetic_banded_graph(2000, band=8, seed=2)),
        ("random", lambda: synthetic_random_graph(1500, 5000, seed=3)),
    ]


class TestBatchedVsReference:
    """The batched pipeline against the scalar dict/set oracle.

    Placement is defined round-for-round identically in both, so with
    ``refine_passes=0`` the labels must match byte for byte; with refinement
    the sequential and whole-pass sweeps legitimately diverge, but both must
    keep the composed edge list, the balance cap, and near-identical
    vertex-cut quality.
    """

    @pytest.mark.parametrize("name,make", _graph_cases())
    @pytest.mark.parametrize("seed", [0, 1])
    def test_placement_only_byte_identical(self, name, make, seed):
        e = make()
        k = 16
        res = edge_partition(e, k, method="ep")
        ins_u, ins_v, delete_ids = _churn(e, 0.02, seed=seed)
        out_b = incremental_repartition(
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
            delete_ids=delete_ids, refine_passes=0,
        )
        out_r = incremental_repartition_reference(
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
            delete_ids=delete_ids, refine_passes=0,
        )
        np.testing.assert_array_equal(out_b[0].u, out_r[0].u)
        np.testing.assert_array_equal(out_b[0].v, out_r[0].v)
        np.testing.assert_array_equal(out_b[1], out_r[1])

    @pytest.mark.parametrize("name,make", _graph_cases())
    def test_refined_invariants_and_cut_tolerance(self, name, make):
        e = make()
        k = 16
        eps = 0.03
        res = edge_partition(e, k, method="ep")
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=5)
        new_b, lab_b, st_b = incremental_repartition(
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
            delete_ids=delete_ids, eps=eps,
        )
        new_r, lab_r, st_r = incremental_repartition_reference(
            e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
            delete_ids=delete_ids, eps=eps,
        )
        np.testing.assert_array_equal(new_b.u, new_r.u)
        np.testing.assert_array_equal(new_b.v, new_r.v)
        cap = (1 + eps) * np.ceil(new_b.m / k) + 1
        for lab, st in ((lab_b, st_b), (lab_r, st_r)):
            assert lab.shape == (new_b.m,)
            assert lab.min() >= 0 and lab.max() < k
            assert st.balance_ok
            assert np.bincount(lab, minlength=k).max() <= cap
        cut_b = evaluate_edge_partition(new_b, lab_b, k).vertex_cut
        cut_r = evaluate_edge_partition(new_r, lab_r, k).vertex_cut
        assert cut_b <= 1.1 * cut_r + 5, f"batched cut {cut_b} vs reference {cut_r}"
        assert cut_r <= 1.1 * cut_b + 5, f"reference cut {cut_r} vs batched {cut_b}"

    def test_self_loops_new_vertices_and_heavy_deletion(self):
        """Edge cases the dense table must survive: loop tasks, insertions
        minting brand-new vertex ids, and deleting most of the graph."""
        e = synthetic_mesh_graph(12, seed=0)
        k = 4
        res = edge_partition(e, k, method="ep")
        rng = np.random.default_rng(11)
        delete_ids = rng.choice(e.m, size=e.m // 2, replace=False)
        ins_u = np.array([0, e.n + 3, 5, e.n + 7], dtype=np.int64)
        ins_v = np.array([0, e.n + 3, 5, e.n + 9], dtype=np.int64)  # two loops
        for passes in (0, 3):
            out_b = incremental_repartition(
                e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
                delete_ids=delete_ids, refine_passes=passes,
            )
            out_r = incremental_repartition_reference(
                e, res.labels, k, insert_u=ins_u, insert_v=ins_v,
                delete_ids=delete_ids, refine_passes=passes,
            )
            assert out_b[0].n == out_r[0].n == e.n + 10
            np.testing.assert_array_equal(out_b[0].u, out_r[0].u)
            if passes == 0:
                np.testing.assert_array_equal(out_b[1], out_r[1])

    def test_stage_times_populated(self):
        e = synthetic_powerlaw_graph(600, 2400, seed=4)
        res = edge_partition(e, 8, method="ep")
        ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=6)
        _, _, st = incremental_repartition(
            e, res.labels, 8, insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids
        )
        assert st.dirty_s >= 0 and st.place_s >= 0 and st.refine_s >= 0
        assert st.time_s >= st.dirty_s + st.place_s + st.refine_s - 1e-6


class TestServicePlanKernel:
    def test_ep_spmv_allclose_ref_with_service_plan(self, service):
        import jax.numpy as jnp

        from repro.kernels import make_ep_spmv_fn
        from repro.kernels.ref import spmv_coo_ref

        n_rows = n_cols = 96
        _, rows, cols = synthetic_bipartite_graph(n_rows, n_cols, 4, seed=1)
        sp = service.get_spmv_plan(n_rows, n_cols, rows, cols, k=8, pad=8)
        assert sp.plan is not None
        rng = np.random.default_rng(0)
        vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
        x = rng.standard_normal(n_cols).astype(np.float32)
        y = make_ep_spmv_fn(sp.plan, vals, mode="software")(jnp.asarray(x))
        ref = spmv_coo_ref(n_rows, jnp.asarray(rows), jnp.asarray(cols),
                           jnp.asarray(vals), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_ep_spmv_allclose_ref_after_incremental_update(self, service):
        import jax.numpy as jnp

        from repro.kernels import make_ep_spmv_fn
        from repro.kernels.ref import spmv_coo_ref

        n_rows = n_cols = 128
        _, rows, cols = synthetic_bipartite_graph(n_rows, n_cols, 5, seed=2)
        sp = service.get_spmv_plan(n_rows, n_cols, rows, cols, k=8, pad=8)
        m = rows.shape[0]
        rng = np.random.default_rng(1)
        delete_ids = rng.choice(m, size=3, replace=False)
        ins_rows = rng.integers(0, n_rows, 3)
        ins_cols = rng.integers(0, n_cols, 3)
        upd = service.update(
            sp.fingerprint, 8,
            insert_u=ins_cols.astype(np.int64),
            insert_v=(n_cols + ins_rows).astype(np.int64),
            delete_ids=delete_ids, pad=8,
        )
        assert upd.plan is not None
        # COO of the churned matrix, in the service's composition order.
        new_rows = np.concatenate([np.delete(rows, delete_ids), ins_rows])
        new_cols = np.concatenate([np.delete(cols, delete_ids), ins_cols])
        n_rows_c, n_cols_c, svc_rows, svc_cols = upd.coo
        np.testing.assert_array_equal(svc_rows, new_rows)
        np.testing.assert_array_equal(svc_cols, new_cols)
        vals = rng.standard_normal(new_rows.shape[0]).astype(np.float32)
        x = rng.standard_normal(n_cols).astype(np.float32)
        y = make_ep_spmv_fn(upd.plan, vals)(jnp.asarray(x))
        ref = spmv_coo_ref(n_rows, jnp.asarray(new_rows), jnp.asarray(new_cols),
                           jnp.asarray(vals), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_graph_serve_fn_rebinds_on_new_vals(self, service):
        import jax.numpy as jnp

        from repro.kernels.ref import spmv_coo_ref
        from repro.runtime import GraphRequest, GraphServer

        n_rows = n_cols = 64
        _, rows, cols = synthetic_bipartite_graph(n_rows, n_cols, 3, seed=4)
        server = GraphServer(service, k=4, pad=8, start_batcher=False)
        rng = np.random.default_rng(2)
        x = rng.standard_normal(n_cols).astype(np.float32)
        vals_a = rng.standard_normal(rows.shape[0]).astype(np.float32)
        vals_b = rng.standard_normal(rows.shape[0]).astype(np.float32)
        res_a = server.serve(GraphRequest(n_rows, n_cols, rows, cols, vals_a, x))
        res_b = server.serve(GraphRequest(n_rows, n_cols, rows, cols, vals_b, x))
        y_a, y_b = res_a.y, res_b.y
        assert not res_a.info.cache_hit and res_b.info.cache_hit
        # Same structure, new values: the kernel must serve B's values, not A's.
        ref_b = spmv_coo_ref(n_rows, jnp.asarray(rows), jnp.asarray(cols),
                             jnp.asarray(vals_b), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y_b), np.asarray(ref_b),
                                   rtol=1e-5, atol=1e-5)
        assert not np.allclose(np.asarray(y_a), np.asarray(y_b))

    def test_resolve_plan_ticket(self, service):
        from repro.runtime import resolve_plan

        n_rows = n_cols = 64
        _, rows, cols = synthetic_bipartite_graph(n_rows, n_cols, 3, seed=3)
        from repro.core.graph import affinity_graph_from_coo

        edges = affinity_graph_from_coo(n_rows, n_cols, rows, cols)
        ticket = service.submit(
            edges, 4, pad=8, coo=(n_rows, n_cols, rows.astype(np.int64), cols.astype(np.int64))
        )
        plan = resolve_plan(ticket)
        assert plan.k == 4

    def test_resolve_plan_rejects_labels_only(self, service):
        from repro.runtime import resolve_plan

        e = synthetic_mesh_graph(8, seed=0)
        sp = service.get(e, 2)  # no coo -> no PackPlan
        with pytest.raises(ValueError):
            resolve_plan(sp)
        with pytest.raises(TypeError):
            resolve_plan(42)  # not a plan-shaped handle at all


class TestEdgePartitionServiceParam:
    def test_edge_partition_delegates_to_service(self, service):
        e = synthetic_mesh_graph(16, seed=0)
        r1 = edge_partition(e, 4, service=service)
        r2 = edge_partition(e, 4, service=service)
        assert r1 is r2  # cached EdgePartitionResult
        assert service.stats.hits >= 1

    def test_matches_direct_call(self, service):
        e = synthetic_mesh_graph(16, seed=0)
        opts = MultilevelOptions(seed=0)
        via_service = edge_partition(e, 4, opts=opts, service=service)
        direct = edge_partition(e, 4, opts=opts)
        np.testing.assert_array_equal(via_service.labels, direct.labels)

    def test_tenant_and_priority_thread_through(self, service):
        e = synthetic_mesh_graph(14, seed=2)
        r = edge_partition(e, 4, service=service, tenant="teamA", priority=3)
        assert r.k == 4
        snap = service.metrics()
        assert snap.tenants["teamA"]["misses"] == 1
        assert snap.tenants["teamA"]["entries"] == 1


class TestMultiTenant:
    def test_budget_isolation_flood_cannot_evict_victim(self):
        """The headline multi-tenant guarantee: one tenant flooding the
        cache evicts its own entries only; the victim's warm hits stay."""
        victim_graph = synthetic_powerlaw_graph(500, 2000, seed=0)
        with PartitionService(default_tenant_budget=None) as probe:
            plan_bytes = probe.get(victim_graph, 8).nbytes()
        budget = int(plan_bytes * 2.5)
        with PartitionService(default_tenant_budget=budget) as svc:
            victim_plan = svc.get(victim_graph, 8, tenant="victim")
            # Flood: 6 one-shot graphs from another tenant through a budget
            # that holds ~2 plans.
            for i in range(6):
                svc.get(synthetic_powerlaw_graph(500, 2000, seed=10 + i), 8,
                        tenant="flooder")
            again = svc.get(victim_graph, 8, tenant="victim")
            assert again is victim_plan  # still the cached object: warm hit
            snap = svc.metrics()
            assert snap.tenants["victim"]["evictions"] == 0
            assert snap.tenants["flooder"]["evictions"] >= 4
            assert snap.tenants["victim"]["hits"] == 1

    def test_budgeted_victims_stay_warm_across_flood_rounds(self):
        """Three victims re-request their hot graph after every burst of a
        flooder wider than the whole cache, on a two-worker pool: with
        per-tenant budgets of 2.5 hot plans, every victim request after
        warm-up is a cache hit and the flood evicts only its own plans."""
        k, rounds = 64, 5
        victims = {
            f"victim{i}": synthetic_powerlaw_graph(1000, 4000, alpha=2.1 + 0.1 * i,
                                                   seed=100 + i)
            for i in range(3)
        }
        flood = [synthetic_powerlaw_graph(1000, 4000, seed=200 + i) for i in range(12)]
        with PartitionService() as probe:
            budget = int(probe.get(victims["victim0"], k).nbytes() * 2.5)
        with PartitionService(workers=2, default_tenant_budget=budget) as svc:
            for name, g in victims.items():
                svc.get(g, k, tenant=name)
            for _ in range(rounds):
                for g in flood:
                    svc.get(g, k, tenant="flooder", priority=-1)
                for name, g in victims.items():
                    ticket = svc.submit(g, k, tenant=name, priority=1)
                    ticket.result(timeout=120)
                    assert ticket.cache_hit, name
            snap = svc.metrics()
        for name in victims:
            assert snap.tenants[name]["evictions"] == 0
            assert snap.tenants[name]["hits"] == rounds
        assert snap.tenants["flooder"]["evictions"] > 0

    def test_lineage_pinned_base_survives_own_tenant_flood(self):
        base_graph = synthetic_powerlaw_graph(600, 2400, seed=1)
        with PartitionService(default_tenant_budget=None) as probe:
            plan_bytes = probe.get(base_graph, 8).nbytes()
        with PartitionService(default_tenant_budget=int(plan_bytes * 2.5)) as svc:
            base = svc.get(base_graph, 8, tenant="t")
            ins_u, ins_v, delete_ids = _churn(base_graph, 0.01, seed=2)
            svc.update(base.fingerprint, 8, insert_u=ins_u, insert_v=ins_v,
                       delete_ids=delete_ids, tenant="t")
            # Same-tenant flood would normally evict the (cheap) base plan.
            for i in range(5):
                svc.get(synthetic_powerlaw_graph(600, 2400, seed=30 + i), 8,
                        tenant="t")
            # The churn stream's base is pinned: a further update still works.
            upd = svc.update(base.fingerprint, 8, insert_u=ins_u, insert_v=ins_v,
                             delete_ids=delete_ids, tenant="t")
            assert upd.edges.m == base.edges.m + len(ins_u) - len(delete_ids)

    def test_pinned_anchor_lru_bounds_pin_leakage(self):
        """Streams must not leak pins: anchors live in an LRU of
        max_pinned_bases, so dead streams' pins age out while the active
        stream's anchor stays pinned (refreshed on every update)."""
        with PartitionService(max_pinned_bases=2) as service:
            graphs = [synthetic_powerlaw_graph(500, 2000, seed=70 + i)
                      for i in range(3)]
            plans = [service.get(g, 8, tenant="t") for g in graphs]
            churns = [_churn(g, 0.01, seed=80 + i) for i, g in enumerate(graphs)]
            for plan, (iu, iv, de) in zip(plans, churns):
                u = service.update(plan.fingerprint, 8, insert_u=iu,
                                   insert_v=iv, delete_ids=de, tenant="t")
                assert u.lineage == plan.fingerprint
            # Three anchors through a 2-slot pin LRU: the oldest expired.
            assert not service._cache._entries[plans[0].fingerprint].pinned
            assert service._cache._entries[plans[1].fingerprint].pinned
            assert service._cache._entries[plans[2].fingerprint].pinned
            # Re-updating stream 0 re-pins it (active streams never expire).
            iu, iv, de = churns[0]
            service.update(plans[0].fingerprint, 8, insert_u=iu, insert_v=iv,
                           delete_ids=de, tenant="t")
            assert service._cache._entries[plans[0].fingerprint].pinned
            # Ending a stream releases its anchor explicitly.
            assert service.unpin_plan(plans[0].fingerprint)
            assert not service._cache._entries[plans[0].fingerprint].pinned

    def test_service_persistence_restores_warm_hits(self, tmp_path):
        """Persistence round-trip: a restarted service answers its first
        request for a previously-cached graph from the snapshot, warm."""
        path = str(tmp_path / "plans.pkl")
        e = synthetic_powerlaw_graph(700, 2800, seed=3)
        with PartitionService(persist_path=path) as svc:
            plan = svc.get(e, 8, tenant="t")
            fp = plan.fingerprint
        # close() saved the cache.  A fresh service loads it at construction.
        with PartitionService(persist_path=path) as svc2:
            t0 = time.perf_counter()
            ticket = svc2.submit(e, 8, tenant="t")
            warm = ticket.result(timeout=60)
            dt = time.perf_counter() - t0
            assert ticket.cache_hit
            assert warm.fingerprint == fp
            np.testing.assert_array_equal(warm.result.labels, plan.result.labels)
            assert svc2.stats.full_runs == 0  # no recompute
            assert dt < 1.0  # fingerprint + dict probe, not a partition

    def test_restored_pins_adopted_into_bounded_lru(self, tmp_path):
        """Pins surviving a restart must re-enter the anchor LRU, so a dead
        stream's pin still ages out instead of becoming immortal."""
        path = str(tmp_path / "pins.pkl")
        e = synthetic_powerlaw_graph(600, 2400, seed=15)
        with PartitionService(persist_path=path) as svc:
            base = svc.get(e, 8, tenant="t")
            ins_u, ins_v, delete_ids = _churn(e, 0.01, seed=16)
            svc.update(base.fingerprint, 8, insert_u=ins_u, insert_v=ins_v,
                       delete_ids=delete_ids, tenant="t")
            assert svc._cache._entries[base.fingerprint].pinned
        with PartitionService(persist_path=path, max_pinned_bases=2) as svc2:
            # The restored pin is tracked, not orphaned.
            assert base.fingerprint in svc2._pinned_bases
            # Two newer anchors expire it through the same LRU.
            for i in range(2):
                g = synthetic_powerlaw_graph(600, 2400, seed=20 + i)
                p = svc2.get(g, 8, tenant="t")
                iu, iv, de = _churn(g, 0.01, seed=25 + i)
                svc2.update(p.fingerprint, 8, insert_u=iu, insert_v=iv,
                            delete_ids=de, tenant="t")
            assert not svc2._cache._entries[base.fingerprint].pinned

    def test_save_load_cache_explicit_paths(self, tmp_path):
        path = str(tmp_path / "snap.pkl")
        e = synthetic_mesh_graph(18, seed=4)
        with PartitionService() as svc:
            svc.get(e, 4)
            assert svc.save_cache(path) == 1
        with PartitionService() as svc2:
            assert svc2.load_cache(path) == 1
            assert svc2.submit(e, 4).cache_hit

    def test_save_cache_without_path_raises(self):
        with PartitionService() as svc:
            with pytest.raises(ValueError, match="persist_path"):
                svc.save_cache()


class TestSchedulerThroughService:
    def test_priority_orders_cold_requests(self):
        """Under a saturated single-worker queue, a high-priority request
        completes before earlier-submitted low-priority ones."""
        svc = PartitionService(start=False)
        graphs = [synthetic_powerlaw_graph(400, 1600, seed=40 + i) for i in range(3)]
        low = [svc.submit(g, 8, priority=0) for g in graphs[:2]]
        high = svc.submit(graphs[2], 8, priority=10)
        svc.start()
        try:
            plan_high = high.result(timeout=120)
            # When the high ticket resolves, at most one low ticket (the one
            # a worker may have grabbed first... none here: workers started
            # after all submits, so strict priority order holds).
            assert plan_high.result.k == 8
            done_low = [t for t in low if t.done()]
            assert len(done_low) == 0
            for t in low:
                t.result(timeout=120)
        finally:
            svc.close()

    def test_cancel_queued_request_via_ticket(self):
        svc = PartitionService(start=False)
        g1 = synthetic_powerlaw_graph(400, 1600, seed=50)
        g2 = synthetic_powerlaw_graph(400, 1600, seed=51)
        keep = svc.submit(g1, 8)
        victim = svc.submit(g2, 8)
        assert victim.cancel()
        svc.start()
        try:
            keep.result(timeout=120)
            from repro.core import PlanCancelledError

            with pytest.raises(PlanCancelledError):
                victim.result(timeout=5)
            assert svc.stats.full_runs == 1  # the cancelled work never ran
        finally:
            svc.close()

    def test_multiworker_service_serves_concurrent_colds(self):
        with PartitionService(workers=2) as svc:
            graphs = [synthetic_powerlaw_graph(400, 1600, seed=60 + i)
                      for i in range(4)]
            tickets = [svc.submit(g, 8) for g in graphs]
            plans = [t.result(timeout=120) for t in tickets]
            assert len({p.fingerprint for p in plans}) == 4
            assert svc.stats.full_runs == 4

    def test_process_executor_service_runs_on_a_process_pool(self):
        """``executor="process"`` really moves cold plans onto a pool of
        spawned processes, and their plans are byte-identical to the
        in-process worker's."""
        import multiprocessing.pool

        graphs = [synthetic_powerlaw_graph(400, 1600, seed=90 + i) for i in range(2)]
        with PartitionService() as ref:
            expected = [ref.get(g, 8) for g in graphs]
        with PartitionService(workers=2, executor="process") as svc:
            assert isinstance(svc.scheduler._pool, multiprocessing.pool.Pool)
            tickets = [svc.submit(g, 8) for g in graphs]
            plans = [t.result(timeout=120) for t in tickets]
            assert svc.metrics().executor == "process"
        for got, want in zip(plans, expected):
            assert got.fingerprint == want.fingerprint
            np.testing.assert_array_equal(got.result.labels, want.result.labels)

    def test_metrics_snapshot_through_service(self, service):
        e = synthetic_mesh_graph(16, seed=5)
        service.get(e, 4, tenant="m")
        service.get(e, 4, tenant="m")
        snap = service.metrics()
        assert snap.workers == 1 and snap.queue_depth == 0
        assert snap.jobs_completed >= 1
        assert snap.tenants["m"]["hits"] == 1
        assert snap.tenants["m"]["misses"] == 1
        assert snap.latency_s["count"] >= 1

"""ReplicaGroup: failover, hedging, shared store, stale serve, fault injection.

These tests drive the group with a deterministic :class:`FaultInjector`
(seeded crash/stall/heartbeat-drop schedules).  Routing is deterministic:
with two healthy replicas the first request's primary lane always lands on
``r1`` (round-robin starts past ``r0``), so schedules can pre-target the
primary.  Where a schedule stalls both replicas symmetrically, the primary
is discovered from the injector's event log instead (the first recorded
stall names the dispatching replica).
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (
    AdmissionRejectedError,
    FaultInjector,
    PartitionService,
    ReplicaExhaustedError,
    ReplicaGroup,
    ServiceClosedError,
    affinity_graph_from_coo,
    synthetic_mesh_graph,
    synthetic_powerlaw_graph,
    synthetic_random_graph,
)
from repro.runtime.request import GraphRequest, GraphServer


def _coo(n_rows, n_cols, shift, nnz_per_row=3):
    """Hand-rolled COO with exactly ``n_rows * nnz_per_row`` entries.

    Different ``shift`` values give structurally different graphs with the
    SAME shape and nnz — what the stale-serve compatibility gate needs.
    """
    rows = np.repeat(np.arange(n_rows), nnz_per_row)
    offs = np.tile(np.arange(nnz_per_row) * (shift + 1) + shift, n_rows)
    cols = (rows + offs) % n_cols
    return rows.astype(np.int64), cols.astype(np.int64)


def _wait(pred, timeout=10.0, dt=0.005):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(dt)
    return pred()


def _primary_rid(injector, timeout=10.0):
    """The replica that dispatched the first (stalled) job."""
    assert _wait(lambda: any(e[0] == "stall" for e in injector.events), timeout)
    return next(e[1] for e in injector.events if e[0] == "stall")


def _other(group, rid):
    return next(r for r in group.replica_ids() if r != rid)


class TestBasics:
    def test_cold_then_warm_and_anti_entropy(self):
        with ReplicaGroup(2, sync_interval_s=0.0) as g:
            e = synthetic_random_graph(96, 300, seed=1)
            t1 = g.submit(e, 4)
            sp = t1.result(60)
            assert not t1.cache_hit and not t1.stale
            assert t1.replica in g.replica_ids()
            # Second submit: warm from the shared store, no recompute.
            t2 = g.submit(e, 4)
            assert t2.cache_hit and t2.done()
            assert t2.result(5) is sp
            # Anti-entropy: the pump copies the plan into every replica's
            # local cache, not just the one that computed it.
            g.pump()
            for rid in g.replica_ids():
                assert g._by_rid[rid].svc.plan_cache.peek(sp.fingerprint) is not None
            rm = g.replica_metrics()
            assert rm.lost == 0 and rm.store_publishes == 1
            assert sum(r.jobs_completed for r in rm.replicas) == 1

    def test_coalescing_shares_one_driver(self):
        inj = FaultInjector().stall_jobs("r0", 0.3).stall_jobs("r1", 0.3)
        with ReplicaGroup(2, injector=inj, hedge=False) as g:
            e = synthetic_mesh_graph(24, seed=2)
            results = []
            ts = [threading.Thread(target=lambda: results.append(
                g.get(e, 4, timeout=60))) for _ in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60)
            assert len(results) == 3
            assert results[0] is results[1] is results[2]
            rm = g.replica_metrics()
            assert rm.coalesced == 2 and rm.submitted == 3 and rm.resolved == 3
            assert g.stats.full_runs == 1

    def test_submit_after_close_fails_typed(self):
        g = ReplicaGroup(2)
        g.close()
        t = g.submit(synthetic_mesh_graph(12, seed=0), 4)
        with pytest.raises(ServiceClosedError):
            t.result(5)

    def test_explicit_services_and_update_path(self):
        svcs = [PartitionService(max_entries=16) for _ in range(2)]
        with ReplicaGroup(svcs) as g:
            e = synthetic_random_graph(200, 800, seed=3)
            sp = g.get(e, 4, timeout=60)
            up = g.update(sp.fingerprint, 4, insert_u=np.array([0, 1]),
                          insert_v=np.array([5, 6]), timeout=60)
            assert up.fingerprint != sp.fingerprint
            # The updated plan is published to the store too.
            assert g.store.peek(up.fingerprint) is not None

    def test_update_unknown_base_raises_keyerror(self):
        with ReplicaGroup(2) as g:
            with pytest.raises(KeyError):
                g.update_async("no-such-fingerprint", 4,
                               insert_u=np.array([0]), insert_v=np.array([1]))


class TestFailover:
    def test_kill_primary_midflight_fails_over(self):
        # Both replicas stall their first job so the primary lane is
        # reliably still in flight when we kill its replica.
        inj = (FaultInjector().stall_jobs("r0", 0.4, first=0, last=0)
               .stall_jobs("r1", 0.4, first=0, last=0))
        with ReplicaGroup(2, injector=inj, hedge=False) as g:
            e = synthetic_random_graph(128, 500, seed=4)
            t = g.submit(e, 4)
            primary = _primary_rid(inj)
            g.kill(primary)
            sp = t.result(60)
            assert sp.result.k == 4
            assert t.replica == _other(g, primary)
            assert t.retries >= 1
            rm = g.replica_metrics()
            assert rm.failovers >= 1 and rm.lost == 0
            row = next(r for r in rm.replicas if r.replica == primary)
            assert row.state == "crashed" and row.weight == 0.0
            assert row.failovers_from >= 1

    def test_failover_stream_byte_identical_to_fault_free_run(self):
        """A three-tenant stream; the primary crashes after two completed
        jobs while stalled work is mid-V-cycle.  No ticket is lost, and
        every response is byte-identical to a fault-free run's."""
        graphs = [synthetic_powerlaw_graph(200, 800, seed=400 + i) for i in range(10)]
        tenants = ("tenant-a", "tenant-b", "tenant-c")
        with PartitionService() as ref:
            expected = [ref.get(e, 16) for e in graphs]
        inj = (FaultInjector(seed=0)
               .stall_jobs("r0", 0.15, first=0, last=3)
               .stall_jobs("r1", 0.15, first=0, last=3)
               .crash_after_jobs("r1", 2))
        with ReplicaGroup(2, injector=inj, hedge=False) as g:
            tickets = [g.submit(e, 16, tenant=tenants[i % len(tenants)])
                       for i, e in enumerate(graphs)]
            plans = [t.result(60) for t in tickets]
            rm = g.replica_metrics()
        assert any(e[0] == "crash" for e in inj.events)
        assert rm.lost == 0 and rm.failovers >= 1
        for got, want in zip(plans, expected):
            assert got.fingerprint == want.fingerprint
            np.testing.assert_array_equal(got.result.labels, want.result.labels)

    def test_queued_tickets_on_killed_replica_fail_over(self):
        """kill() drains the dead replica's queue (ServiceClosedError),
        which drivers treat as a failover signal — no ticket is lost."""
        inj = (FaultInjector().stall_jobs("r0", 0.3, first=0, last=0)
               .stall_jobs("r1", 0.3, first=0, last=0))
        with ReplicaGroup(2, injector=inj, hedge=False) as g:
            graphs = [synthetic_mesh_graph(14 + 2 * i, seed=i) for i in range(4)]
            tickets = [g.submit(e, 4) for e in graphs]
            primary = _primary_rid(inj)
            g.kill(primary)
            plans = [t.result(60) for t in tickets]
            assert all(p.result.k == 4 for p in plans)
            assert g.replica_metrics().lost == 0

    def test_stalled_primary_goes_suspect_and_drains_routing(self):
        # The primary (deterministically r1) sits on a 0.8s straggler and
        # never beats; with a 0.15s deadline the pump marks it suspect
        # mid-job and the driver resubmits to r0.
        inj = FaultInjector().stall_jobs("r1", 0.8, first=0, last=0)
        with ReplicaGroup(2, injector=inj, hedge=False,
                          heartbeat_deadline_s=0.15) as g:
            e = synthetic_random_graph(128, 500, seed=5)
            t = g.submit(e, 4)
            primary = _primary_rid(inj)
            assert primary == "r1"
            # The driver's pump declares r1 suspect while it sits on the
            # straggler (routing weight 0 — observed via the registry).
            assert _wait(lambda: "r1" in g.registry.dead, timeout=10.0)
            sp = t.result(60)
            assert sp.result.k == 4
            assert t.replica == "r0"
            rm = g.replica_metrics()
            assert rm.failovers >= 1 and rm.lost == 0
            # Suspect is not a death sentence: once the straggler drains and
            # r1 goes idle, the pump's beat resurrects it.
            assert _wait(lambda: (g.pump(), "r1" not in g.registry.dead)[1],
                         timeout=10.0)

    def test_dropped_heartbeats_mark_suspect_then_recover_on_beat(self):
        inj = FaultInjector().drop_heartbeats("r0", 8).drop_heartbeats("r1", 8)
        with ReplicaGroup(2, injector=inj, heartbeat_deadline_s=0.05) as g:
            def states():
                g.pump()
                return {r.replica: r.state for r in g.replica_metrics().replicas}
            # Beats are swallowed: both idle replicas blow the deadline.
            assert _wait(lambda: all(s == "suspect" for s in states().values()),
                         timeout=10.0, dt=0.01)
            # Drop schedule exhausted: idle beats get through again and the
            # registry resurrects both replicas.
            assert _wait(lambda: all(s == "healthy" for s in states().values()),
                         timeout=10.0, dt=0.01)
            assert any(e[0] == "drop_beat" for e in inj.events)

    def test_coalesced_ticket_failover_multiple_waiters(self):
        """Failover of a coalesced ticket: several callers share one group
        request; the crash costs ONE failover, and every waiter gets the
        same recovered plan."""
        inj = FaultInjector().stall_jobs("r0", 0.4).stall_jobs("r1", 0.4)
        with ReplicaGroup(2, injector=inj, hedge=False) as g:
            e = synthetic_random_graph(150, 600, seed=6)
            results = []
            ts = [threading.Thread(target=lambda: results.append(
                g.get(e, 4, timeout=60))) for _ in range(3)]
            for th in ts:
                th.start()
            primary = _primary_rid(inj)
            assert _wait(lambda: g.replica_metrics().coalesced == 2)
            g.kill(primary)
            for th in ts:
                th.join(60)
            assert len(results) == 3
            assert results[0] is results[1] is results[2]
            rm = g.replica_metrics()
            assert rm.failovers == 1  # one shared request, one failover
            assert rm.submitted == 3 and rm.resolved == 3 and rm.lost == 0

    def test_retry_budget_exhaustion_raises_typed_error(self):
        with ReplicaGroup(2, retry_budget=2, backoff_base_s=0.001,
                          hedge=False) as g:
            def boom(*a, **kw):
                raise RuntimeError("injected submit failure")
            for rid in g.replica_ids():
                g._by_rid[rid].svc.submit = boom
            t = g.submit(synthetic_mesh_graph(16, seed=7), 4)
            with pytest.raises(ReplicaExhaustedError, match="budget"):
                t.result(30)
            rm = g.replica_metrics()
            assert rm.failed == 1 and rm.retries >= 2 and rm.lost == 0


class TestHedging:
    def test_hedge_wins_over_straggler(self):
        # Primary (r1) stalls 0.6s; the hedge fires onto clean r0 after
        # 30ms and wins by a wide margin.
        inj = FaultInjector().stall_jobs("r1", 0.6, first=0, last=0)
        with ReplicaGroup(2, injector=inj, hedge_delay_s=0.03) as g:
            e = synthetic_random_graph(128, 500, seed=8)
            t0 = time.monotonic()
            t = g.submit(e, 4)
            sp = t.result(60)
            dt = time.monotonic() - t0
            assert sp.result.k == 4
            assert t.hedged and t.replica == "r0"
            assert dt < 0.55  # beat the 0.6s straggler
            rm = g.replica_metrics()
            assert rm.hedges_fired == 1 and rm.hedges_won == 1
            assert rm.hedges_lost == 0 and rm.lost == 0

    def test_hedge_fires_but_primary_wins(self):
        """Satellite case: both lanes stall 0.3s, but the hedge starts 50ms
        behind the primary — the primary finishes first, the loser is
        cancelled through the PlanScheduler path, and the shared store sees
        exactly one publish (no double-publish)."""
        inj = (FaultInjector().stall_jobs("r0", 0.3, first=0, last=0)
               .stall_jobs("r1", 0.3, first=0, last=0))
        with ReplicaGroup(2, injector=inj, hedge_delay_s=0.05) as g:
            e = synthetic_random_graph(150, 600, seed=9)
            t = g.submit(e, 4)
            sp = t.result(60)
            assert sp.result.k == 4
            assert t.hedged and t.replica == "r1"  # primary won
            rm = g.replica_metrics()
            assert rm.hedges_fired == 1
            assert rm.hedges_won == 0 and rm.hedges_lost == 1
            assert rm.store_publishes == 1 and len(g.store) == 1
            # The losing lane on r0 was cancelled, not left to run blind.
            m = g._by_rid["r0"].svc.metrics()
            assert m.cancelled_queued + m.cancelled_inflight >= 1

    def test_hedge_delay_derives_from_p99(self):
        with ReplicaGroup(2, hedge_min_delay_s=0.02, hedge_p99_factor=2.0) as g:
            assert g._hedge_delay() == pytest.approx(0.02)  # no samples yet
            with g._lock:
                for _ in range(100):
                    g._latencies.append(0.05)
            assert g._hedge_delay() == pytest.approx(0.10)

    def test_hedge_clamped_to_request_deadline(self):
        """Regression: a request whose remaining deadline budget is below
        ``hedge_min_delay_s`` must never hedge — a secondary lane opened
        that close to expiry cannot win, it only burns a replica slot.
        Identical setup to test_hedge_wins_over_straggler (where the hedge
        fires and wins) except the deadline budget is below the floor."""
        inj = FaultInjector().stall_jobs("r1", 0.3, first=0, last=0)
        with ReplicaGroup(2, injector=inj, hedge_delay_s=0.02,
                          hedge_min_delay_s=10.0) as g:
            e = synthetic_random_graph(128, 500, seed=21)
            t = g.submit(e, 4, timeout=5.0)  # budget 5s < 10s floor
            sp = t.result(60)
            assert sp.result.k == 4
            # The primary rode out its 0.3s stall alone.
            assert not t.hedged and t.replica == "r1"
            assert g.replica_metrics().hedges_fired == 0

    def test_no_hedge_when_single_healthy_replica(self):
        inj = FaultInjector().stall_jobs("r0", 0.2, first=0, last=0)
        with ReplicaGroup(2, injector=inj, hedge_delay_s=0.0) as g:
            g.kill("r1")
            sp = g.get(synthetic_mesh_graph(20, seed=10), 4, timeout=60)
            assert sp.result.k == 4
            assert g.replica_metrics().hedges_fired == 0


class TestStaleServe:
    def test_all_down_serves_freshest_compatible_plan_stale(self):
        with ReplicaGroup(2, retry_budget=1, backoff_base_s=0.001) as g:
            n = 96
            rows_a, cols_a = _coo(n, n, shift=0)
            sp_a = g.get_spmv_plan(n, n, rows_a, cols_a, 4, timeout=60)
            for rid in g.replica_ids():
                g.kill(rid)
            # Same shape/nnz, different structure: served stale from store.
            rows_b, cols_b = _coo(n, n, shift=5)
            assert len(rows_b) == len(rows_a)
            tb = g.submit(affinity_graph_from_coo(n, n, rows_b, cols_b), 4,
                          coo=(n, n, rows_b, cols_b))
            sp_b = tb.result(30)
            assert tb.stale and sp_b is sp_a
            assert g.replica_metrics().stale_serves == 1
            # Exact-fingerprint rerequest of A: a warm store hit, NOT stale.
            ta = g.submit(affinity_graph_from_coo(n, n, rows_a, cols_a), 4,
                          coo=(n, n, rows_a, cols_a))
            assert ta.cache_hit and not ta.stale
            assert ta.result(5) is sp_a

    def test_incompatible_shape_is_never_served_stale(self):
        """The degraded path must not hand back a plan whose operands would
        not even fit the request — wrong shape raises instead."""
        with ReplicaGroup(2, retry_budget=1, backoff_base_s=0.001) as g:
            rows, cols = _coo(96, 96, shift=0)
            g.get_spmv_plan(96, 96, rows, cols, 4, timeout=60)
            for rid in g.replica_ids():
                g.kill(rid)
            rows2, cols2 = _coo(64, 64, shift=0)  # different dims + nnz
            t = g.submit(affinity_graph_from_coo(64, 64, rows2, cols2), 4,
                         coo=(64, 64, rows2, cols2))
            with pytest.raises(ReplicaExhaustedError):
                t.result(30)

    def test_all_down_update_serves_base_stale(self):
        with ReplicaGroup(2) as g:
            e = synthetic_random_graph(200, 800, seed=13)
            sp = g.get(e, 4, timeout=60)
            for rid in g.replica_ids():
                g.kill(rid)
            t = g.update_async(sp.fingerprint, 4, insert_u=np.array([0]),
                               insert_v=np.array([3]))
            got = t.result(30)
            assert t.stale and got is sp  # freshest known state of the graph

    def test_all_down_nothing_compatible_raises_exhausted(self):
        with ReplicaGroup(2, retry_budget=1, backoff_base_s=0.001) as g:
            for rid in g.replica_ids():
                g.kill(rid)
            t = g.submit(synthetic_mesh_graph(18, seed=14), 4)
            with pytest.raises(ReplicaExhaustedError):
                t.result(30)

    def test_stale_disabled_raises_even_with_store(self):
        with ReplicaGroup(2, retry_budget=1, backoff_base_s=0.001,
                          allow_stale=False) as g:
            e = synthetic_random_graph(96, 300, seed=15)
            sp = g.get(e, 4, timeout=60)
            for rid in g.replica_ids():
                g.kill(rid)
            t = g.update_async(sp.fingerprint, 4, insert_u=np.array([0]),
                               insert_v=np.array([1]))
            with pytest.raises(ReplicaExhaustedError):
                t.result(30)


class TestRequestDeadline:
    def test_deadline_beats_retry_budget(self):
        """``timeout`` is an end-to-end deadline: with every replica
        stalled past it, the driver gives up when the clock expires — not
        after burning a (here deliberately huge) retry budget — and the
        error names the deadline."""
        inj = (FaultInjector(seed=0)
               .stall_jobs("r0", 0.6).stall_jobs("r1", 0.6))
        with ReplicaGroup(2, injector=inj, hedge=False, retry_budget=100,
                          backoff_base_s=0.001, allow_stale=False) as g:
            t = g.submit(synthetic_mesh_graph(18, seed=3), 4, timeout=0.15)
            t0 = time.monotonic()
            with pytest.raises(ReplicaExhaustedError, match="deadline"):
                t.result(30)
            # It did not wait out the 0.6s stall, let alone 100 retries.
            assert time.monotonic() - t0 < 0.5

    def test_completed_result_wins_over_expired_deadline(self):
        """The deadline is checked after reaping, so a result that landed
        just in time is returned even if the clock has since expired."""
        with ReplicaGroup(2, hedge=False, backoff_base_s=0.001) as g:
            e = synthetic_mesh_graph(16, seed=9)
            sp = g.get(e, 4, timeout=60)
            # Warm store: resolved before the driver ever checks the clock.
            t = g.submit(e, 4, timeout=60)
            assert t.result(30) is sp


class TestOverloadBreakers:
    def test_sustained_rejections_trip_breaker_fail_fast_then_recover(self):
        """A tenant that keeps blowing the replica's queue bound trips the
        per-(replica, tenant) breaker; while it is open the driver answers
        the typed rejection immediately (reason="breaker_open") without
        dispatching; after the cooldown one half-open probe re-closes it."""
        g = ReplicaGroup(1, hedge=False, allow_stale=False, retry_budget=1,
                         backoff_base_s=0.001, backoff_cap_s=0.002,
                         breaker_failures=4, breaker_cooldown_s=0.25,
                         workers=1, max_queue_depth=1)
        try:
            gate = threading.Event()
            started = threading.Event()
            sched = g._replicas[0].svc.scheduler

            def hook(_key):
                started.set()
                gate.wait(10)

            sched.pre_job_hook = hook
            graphs = [synthetic_mesh_graph(14 + 2 * i, seed=30 + i)
                      for i in range(5)]
            t_run = g.submit(graphs[0], 4)  # picked up: stalls in the hook
            assert started.wait(10)
            t_q = g.submit(graphs[1], 4)  # queued: holds the single slot
            assert _wait(lambda: sched.metrics_snapshot()
                         .admission["occupancy"].get("default", 0) == 1)
            # First over-bound request: the replica answers queue_full
            # rejections until the retry budget burns (primary + one
            # failover re-dispatch = two breaker failures, still closed).
            t = g.submit(graphs[2], 4)
            with pytest.raises(AdmissionRejectedError) as ei:
                t.result(30)
            assert ei.value.reason == "queue_full"
            assert ei.value.retry_after_s > 0
            # Second: its own rejected dispatches are the breaker's third
            # and fourth consecutive failures — the breaker trips
            # mid-request and the driver fails fast on the next pass.
            t = g.submit(graphs[3], 4)
            with pytest.raises(AdmissionRejectedError) as ei:
                t.result(30)
            assert ei.value.reason == "breaker_open"
            assert g.breaker_states()["r0"] == "open"
            # Open breaker: rejected without ever touching the replica.
            t = g.submit(graphs[4], 4)
            with pytest.raises(AdmissionRejectedError) as ei:
                t.result(30)
            assert ei.value.reason == "breaker_open"
            assert ei.value.retry_after_s > 0
            row = g.replica_metrics().replicas[0]
            assert row.rejections == 4  # the fail-fast path never dispatched
            assert row.breakers_open == 1 and row.breaker_trips >= 1
            # Drain the queue, ride out the cooldown: the half-open probe
            # dispatch succeeds and re-closes the breaker.
            gate.set()
            t_run.result(30)
            t_q.result(30)
            time.sleep(0.3)
            sp = g.get(graphs[4], 4, timeout=30)
            assert sp.result.k == 4
            assert g.breaker_states()["r0"] == "closed"
        finally:
            g.close()


class TestGraphServerIntegration:
    def test_serve_through_replica_group_and_stale_flag(self):
        n = 96
        rows, cols = _coo(n, n, shift=0)
        rng = np.random.default_rng(0)
        vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
        x = rng.standard_normal(n).astype(np.float32)
        with ReplicaGroup(2, retry_budget=1, backoff_base_s=0.001) as g:
            server = GraphServer(service=g, k=4, start_batcher=False)
            res = server.serve(GraphRequest(n, n, rows, cols, vals, x))
            assert res.info.stale is False
            y_ref = np.zeros(n, np.float32)
            np.add.at(y_ref, rows, vals * x[cols])
            np.testing.assert_allclose(np.asarray(res.y), y_ref, rtol=1e-4,
                                       atol=1e-4)
            # Kill everything; a same-shape different-structure request is
            # served from the stale plan and flagged on ServeInfo.
            for rid in g.replica_ids():
                g.kill(rid)
            rows2, cols2 = _coo(n, n, shift=5)
            res2 = server.serve(GraphRequest(n, n, rows2, cols2, vals, x))
            assert res2.info.stale is True
            # The flag round-trips through the legacy dict view too.
            assert res.info.as_dict()["stale"] is False
            assert res2.info.as_dict()["stale"] is True
            # Metrics still flow through the aggregated group snapshot.
            snap = server.metrics()
            assert snap.workers == 2

    def test_stale_disabled_server_raises_when_all_down(self):
        """``allow_stale=False`` is a correctness contract: with no healthy
        replica, GraphServer.serve surfaces ReplicaExhaustedError rather
        than silently answering from a stale plan."""
        n = 96
        rows, cols = _coo(n, n, shift=0)
        rng = np.random.default_rng(1)
        vals = rng.standard_normal(rows.shape[0]).astype(np.float32)
        x = rng.standard_normal(n).astype(np.float32)
        with ReplicaGroup(2, retry_budget=1, backoff_base_s=0.001,
                          allow_stale=False) as g:
            server = GraphServer(service=g, k=4, start_batcher=False)
            res = server.serve(GraphRequest(n, n, rows, cols, vals, x))
            assert res.info.stale is False
            for rid in g.replica_ids():
                g.kill(rid)
            # Same shape, different structure: exactly what the stale path
            # would have served had it been allowed.
            rows2, cols2 = _coo(n, n, shift=5)
            with pytest.raises(ReplicaExhaustedError):
                server.serve(GraphRequest(n, n, rows2, cols2, vals, x))

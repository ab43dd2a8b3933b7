"""Cross-process transport: frame codec, handshake, PlanServer, RemoteReplica.

The cheap tests run the server in-process (``PlanServer.start()`` on a
daemon thread) so protocol behaviour — truncation, deadlines, severed
connections, gossip — is exercised without paying a subprocess spawn.  The
``TestWorkerProcess`` class then crosses a real process boundary via
``spawn_worker`` / ``spawn_process_group`` and checks the property the
whole design rests on: plans that travel the wire are byte-identical to
plans computed locally, and a ``kill -9``-ed worker loses no submitted
work once the group fails over.
"""
import os
import pickle
import socket
import struct
import time

import numpy as np
import pytest

from repro.core import (
    FaultInjector,
    PartitionService,
    ReplicaGroup,
    synthetic_mesh_graph,
    synthetic_random_graph,
)
from repro.core.transport import (
    WIRE_MAGIC,
    DeadlineExceeded,
    PlanServer,
    ProtocolError,
    RemoteReplica,
    ReplicaConnection,
    WireError,
    _check_handshake,
    recv_frame,
    send_frame,
)
from repro.launch.replica_worker import spawn_process_group, spawn_worker

_LEN = struct.Struct(">I")


def _wait(pred, timeout=10.0, dt=0.005):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(dt)
    return pred()


class TestFrameCodec:
    def test_round_trip_preserves_arrays(self):
        a, b = socket.socketpair()
        try:
            payload = {"labels": np.arange(257, dtype=np.int32),
                       "nested": {"k": 4, "fp": "abc" * 40}}
            send_frame(a, payload)
            got = recv_frame(b, deadline_s=5.0)
            np.testing.assert_array_equal(got["labels"], payload["labels"])
            assert got["nested"] == payload["nested"]
        finally:
            a.close()
            b.close()

    def test_truncated_frame_raises_protocol_error(self):
        a, b = socket.socketpair()
        try:
            # Promise 1 MiB, deliver 7 bytes, hang up — the mid-frame sever.
            a.sendall(_LEN.pack(1 << 20) + b"severed")
            a.close()
            with pytest.raises(ProtocolError, match="mid-frame"):
                recv_frame(b, deadline_s=5.0)
        finally:
            b.close()

    def test_oversized_length_prefix_rejected(self):
        a, b = socket.socketpair()
        try:
            a.sendall(_LEN.pack((1 << 30) + 1))
            with pytest.raises(ProtocolError, match="exceeds cap"):
                recv_frame(b, deadline_s=5.0)
        finally:
            a.close()
            b.close()

    def test_undecodable_body_raises_protocol_error(self):
        a, b = socket.socketpair()
        try:
            body = b"\x00\x01not a pickle"
            a.sendall(_LEN.pack(len(body)) + body)
            with pytest.raises(ProtocolError, match="undecodable"):
                recv_frame(b, deadline_s=5.0)
        finally:
            a.close()
            b.close()

    def test_recv_deadline_raises_deadline_exceeded(self):
        a, b = socket.socketpair()
        try:
            with pytest.raises(DeadlineExceeded):
                recv_frame(b, deadline_s=0.05)
        finally:
            a.close()
            b.close()

    def test_handshake_version_and_magic_checked(self):
        with pytest.raises(ProtocolError, match="version"):
            _check_handshake({"magic": WIRE_MAGIC, "version": 99}, "peer")
        with pytest.raises(ProtocolError, match="protocol"):
            _check_handshake({"magic": "something-else", "version": 1}, "peer")
        with pytest.raises(ProtocolError):
            _check_handshake(b"GET / HTTP/1.1", "peer")


@pytest.fixture
def inproc_server():
    svc = PartitionService(workers=1)
    server = PlanServer(svc).start()
    yield svc, server
    server.shutdown()
    svc.close()


class TestPlanServerInProcess:
    def test_submit_over_wire_is_byte_identical(self, inproc_server):
        svc, server = inproc_server
        rep = RemoteReplica(server.address)
        edges = synthetic_mesh_graph(18, seed=7)
        t = rep.submit(edges, 4)
        sp = t.result(60)
        # The wire copy must match the server-resident original bit for bit.
        local = svc.plan_cache.peek(sp.fingerprint)
        assert local is not None and local is not sp
        assert sp.fingerprint == local.fingerprint
        np.testing.assert_array_equal(sp.result.labels, local.result.labels)
        rep.close()

    def test_bad_handshake_dropped_server_keeps_serving(self, inproc_server):
        _svc, server = inproc_server
        raw = socket.create_connection(server.address, timeout=5)
        try:
            send_frame(raw, {"magic": WIRE_MAGIC, "version": 99}, 5.0)
            raw.settimeout(5)
            assert raw.recv(1) == b""  # server hung up without answering
        finally:
            raw.close()
        # A well-behaved client on a fresh connection is unaffected.
        conn = ReplicaConnection(server.address)
        assert conn.call("ping")["pid"] == os.getpid()
        conn.close()

    def test_severed_connection_keeps_tickets(self, inproc_server):
        _svc, server = inproc_server
        rep = RemoteReplica(server.address)
        edges = synthetic_random_graph(150, 500, seed=11)
        t = rep.submit(edges, 4)
        # Cut the socket mid-frame: the server handler must survive the
        # truncated read, and the ticket must outlive the connection.
        rep.sever_connection(mid_frame=True)
        sp = t.result(60)
        assert sp is not None and sp.fingerprint
        assert rep._conn.reconnects >= 1
        rep.close()

    def test_gossip_pull_push_round_trip(self, inproc_server):
        svc, server = inproc_server
        rep = RemoteReplica(server.address)
        sp = rep.submit(synthetic_mesh_graph(16, seed=3), 4).result(60)
        fps = rep.gossip_fingerprints()
        assert sp.fingerprint in fps
        entries = rep.gossip_pull([sp.fingerprint])
        assert [e[0] for e in entries] == [sp.fingerprint]

        svc2 = PartitionService(workers=1)
        server2 = PlanServer(svc2).start()
        rep2 = RemoteReplica(server2.address)
        try:
            assert rep2.gossip_push(entries) == 1
            assert sp.fingerprint in rep2.gossip_fingerprints()
            pulled = rep2.gossip_pull([sp.fingerprint])[0][3]
            np.testing.assert_array_equal(pulled.result.labels,
                                          sp.result.labels)
        finally:
            rep2.close()
            server2.shutdown()
            svc2.close()
        rep.close()

    def test_unknown_op_and_unknown_ticket_raise_wire_error(self, inproc_server):
        _svc, server = inproc_server
        conn = ReplicaConnection(server.address)
        with pytest.raises(WireError, match="unknown op"):
            conn.call("bogus")
        with pytest.raises(WireError, match="unknown ticket"):
            conn.call("poll", {"ticket": 999_999})
        # Transported errors do not cost the connection.
        assert conn.call("ping")["closed"] is False
        conn.close()

    def test_group_gossip_anti_entropy_over_wire(self):
        svc_a = PartitionService(workers=1)
        svc_b = PartitionService(workers=1)
        srv_a = PlanServer(svc_a).start()
        srv_b = PlanServer(svc_b).start()
        reps = [RemoteReplica(srv_a.address), RemoteReplica(srv_b.address)]
        try:
            with ReplicaGroup(reps, backoff_base_s=0.001) as g:
                e = synthetic_random_graph(120, 400, seed=5)
                sp = g.get(e, 4, timeout=60)

                # Pairwise gossip converges both worker caches on the plan.
                # pump() is driven manually: sync rounds piggyback on live
                # request traffic, and this group is now idle.
                def synced():
                    g.pump()
                    return (sp.fingerprint in svc_a.plan_cache.fingerprints()
                            and sp.fingerprint
                            in svc_b.plan_cache.fingerprints())

                assert _wait(synced, 20)
        finally:
            srv_a.shutdown()
            srv_b.shutdown()
            svc_a.close()
            svc_b.close()


class TestOverloadOverWire:
    def test_rejection_is_typed_frame_not_severed_connection(self):
        """An admission rejection answers as a typed error frame: the
        client re-raises :class:`AdmissionRejectedError` with the hint and
        tenant intact, the connection survives (no reconnect backoff), and
        the same socket serves the next request once the queue drains."""
        import threading

        from repro.core import AdmissionRejectedError

        svc = PartitionService(workers=1, max_queue_depth=1)
        gate = threading.Event()
        started = threading.Event()

        def hook(_key):
            started.set()
            gate.wait(10)

        svc.scheduler.pre_job_hook = hook
        server = PlanServer(svc).start()
        rep = RemoteReplica(server.address)
        try:
            graphs = [synthetic_mesh_graph(14 + 2 * i, seed=40 + i)
                      for i in range(3)]
            t0 = rep.submit(graphs[0], 4)  # picked up: stalls in the hook
            assert started.wait(10)
            t1 = rep.submit(graphs[1], 4)  # queued: holds the single slot
            with pytest.raises(AdmissionRejectedError) as ei:
                rep.submit(graphs[2], 4)
            err = ei.value
            assert err.reason == "queue_full"
            assert err.tenant == "default"
            assert err.retry_after_s > 0
            # The typed frame crossed the wire as data, not as a sever:
            # no reconnect happened and no backoff clock is armed.
            assert rep._conn.reconnects == 0
            assert rep._conn._fails == 0
            # Round trip: re-pickling the transported error is lossless.
            back = pickle.loads(pickle.dumps(err))
            assert back.__reduce__()[1] == err.__reduce__()[1]
            # The same connection keeps serving once the queue drains.
            gate.set()
            assert t0.result(60).fingerprint
            assert t1.result(60).fingerprint
            sp = rep.submit(graphs[2], 4).result(60)
            assert sp.fingerprint
            assert rep._conn.reconnects == 0
        finally:
            rep.close()
            server.shutdown()
            svc.close()

    def test_worker_process_surfaces_rejection(self):
        """Across a real process boundary: a worker spawned with a queue
        bound answers the typed rejection through spawn_worker's wire, with
        the same constructor args (tenant, slot accounting, retry hint) as
        the rejection an in-process service raises in the same state."""
        import threading

        from repro.core import AdmissionRejectedError

        graphs = [synthetic_mesh_graph(14 + 2 * i, seed=50 + i)
                  for i in range(3)]
        svc = PartitionService(workers=1, max_queue_depth=1)
        gate = threading.Event()
        started = threading.Event()

        def hook(_key):
            started.set()
            gate.wait(10)

        svc.scheduler.pre_job_hook = hook
        try:
            svc.submit(graphs[0], 4)  # picked up: stalls in the hook
            assert started.wait(10)
            svc.submit(graphs[1], 4)  # queued: holds the single slot
            with pytest.raises(AdmissionRejectedError) as local:
                svc.submit(graphs[2], 4)
        finally:
            gate.set()
            svc.close()

        h = spawn_worker(queue_bound=1, stalls=[(1.0, 0, 1 << 30)])
        rep = RemoteReplica(h.address, process=h.proc, pid=h.pid)
        try:
            assert _wait(rep.heartbeat, 10)
            rep.submit(graphs[0], 4)  # picked up: sits in the 1s stall
            time.sleep(0.25)          # let the worker reach the stall
            rep.submit(graphs[1], 4)  # queued: holds the single slot
            with pytest.raises(AdmissionRejectedError) as ei:
                rep.submit(graphs[2], 4)
            assert ei.value.reason == "queue_full"
            assert ei.value.retry_after_s > 0
            assert rep._conn.reconnects == 0
        finally:
            rep.close()
        assert h.proc.poll() is not None
        assert (pickle.dumps(ei.value.__reduce__()[1])
                == pickle.dumps(local.value.__reduce__()[1]))


class TestWorkerProcess:
    def test_remote_worker_byte_identical_and_kill(self):
        edges = synthetic_mesh_graph(18, seed=7)
        local = PartitionService(workers=1)
        try:
            ref = local.submit(edges, 4).result(120)
        finally:
            local.close()

        h = spawn_worker()
        rep = RemoteReplica(h.address, process=h.proc, pid=h.pid)
        try:
            assert _wait(rep.heartbeat, 10)
            assert rep.pid != os.getpid()
            sp = rep.submit(edges, 4).result(120)
            assert sp.fingerprint == ref.fingerprint
            np.testing.assert_array_equal(sp.result.labels, ref.result.labels)
            rep.sigkill()
            assert _wait(lambda: not rep.heartbeat(), 10)
            with pytest.raises((WireError, ConnectionError, OSError)):
                rep.submit(edges, 8)
        finally:
            rep.close()
        assert h.proc.poll() is not None

    def test_process_group_sigkill_failover_loses_nothing(self):
        inj = FaultInjector(seed=0).sigkill_after_jobs("r1", 1)
        stalls = [[(0.15, 0, 3)], [(0.15, 0, 3)]]
        with spawn_process_group(
                2, injector=inj, hedge=False, retry_budget=5,
                backoff_base_s=0.01, heartbeat_deadline_s=1.0,
                stalls_per_replica=stalls) as g:
            graphs = [synthetic_random_graph(150 + 10 * i, 500, seed=20 + i)
                      for i in range(6)]
            tickets = [g.submit(e, 4, tenant=f"t{i % 2}")
                       for i, e in enumerate(graphs)]
            plans = [t.result(180) for t in tickets]
            assert all(sp is not None and sp.fingerprint for sp in plans)
            assert g.replica_metrics().lost == 0
            # Six distinct graphs -> six distinct plans, none served stale.
            assert len({sp.fingerprint for sp in plans}) == len(plans)
            assert not any(t.stale for t in tickets)
            assert any(e[0] == "sigkill" for e in inj.events)
        # Crossing the wire and losing a worker changes no byte: every plan
        # matches the fault-free in-process one.
        with PartitionService(workers=1) as local:
            for e, sp in zip(graphs, plans):
                ref = local.get(e, 4)
                assert sp.fingerprint == ref.fingerprint
                np.testing.assert_array_equal(sp.result.labels, ref.result.labels)

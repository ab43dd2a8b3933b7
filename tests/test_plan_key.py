"""The SpMV plan key (``spmv_plan_key``): one content key over the request's
own COO, the same on every SpMV path, and a warm serve that never builds
the affinity graph; and the value digest (``GraphRequest.vals_digest``), a
tree of the same kind over the request's values."""
import dataclasses
import hashlib
import sys
import threading

import numpy as np
import pytest

import repro.core.partition_service as ps
from repro.core import (
    AdmissionRejectedError,
    PartitionService,
    ReplicaGroup,
    digest,
    spmv_plan_key,
    synthetic_bipartite_graph,
)
from repro.runtime import BucketPolicy, GraphRequest, GraphServer

N, K, PAD = 96, 4, 8


def _coo(seed=0, n=N):
    _, rows, cols = synthetic_bipartite_graph(n, n, 3, seed=seed)
    return rows.astype(np.int64), cols.astype(np.int64)


def _request(rows, cols, n=N, seed=0):
    rng = np.random.default_rng(seed)
    return GraphRequest(n, n, rows, cols, rng.standard_normal(rows.size),
                        rng.standard_normal(n))


def _ref(req):
    y = np.zeros(req.n_rows, np.float64)
    np.add.at(y, req.rows, req.vals.astype(np.float64) * req.x[req.cols])
    return y


@pytest.fixture()
def service():
    with PartitionService() as svc:
        yield svc


@pytest.fixture()
def affinity_calls(monkeypatch):
    """Counts every affinity graph the service builds."""
    calls = []
    real = ps.affinity_graph_from_coo

    def counted(*args):
        calls.append(args[:2])
        return real(*args)

    monkeypatch.setattr(ps, "affinity_graph_from_coo", counted)
    return calls


def _via_server(svc, rows, cols):
    server = GraphServer(svc, k=K, pad=PAD, start_batcher=False, bucketing=None)
    req = _request(rows, cols)
    key = server.serve(req).info.fingerprint
    assert server._fingerprint(req) == key  # the brownout probe's key
    return key


PATHS = {
    "server": _via_server,
    "submit": lambda svc, rows, cols: svc.submit(
        None, K, pad=PAD, coo=(N, N, rows, cols)).result(60).fingerprint,
    "get_spmv_plan": lambda svc, rows, cols: svc.get_spmv_plan(
        N, N, rows, cols, K, pad=PAD, timeout=60).fingerprint,
}


@pytest.mark.parametrize("group", [False, True], ids=["service", "replicas"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_spmv_path_gives_one_key(path, group):
    rows, cols = _coo(seed=1)
    key = spmv_plan_key(N, N, rows, cols, K, PAD)
    svc = ReplicaGroup(2) if group else PartitionService()
    with svc:
        assert PATHS[path](svc, rows, cols) == key
        # The same COO in new arrays, on another path: a warm hit.
        ticket = svc.submit(None, K, pad=PAD, coo=(N, N, rows.copy(), cols.copy()))
        assert ticket.cache_hit and ticket.result(60).fingerprint == key


def test_post_churn_coo_is_served_warm(service, affinity_calls):
    rows, cols = _coo(seed=2)
    server = GraphServer(service, k=K, pad=PAD, start_batcher=False, bucketing=None)
    base = server.serve(_request(rows, cols)).info
    rng = np.random.default_rng(7)
    delete_ids = np.sort(rng.choice(rows.size, 6, replace=False))
    ins_rows, ins_cols = rng.integers(0, N, 6), rng.integers(0, N, 6)
    new_plan = service.update(base.fingerprint, K, insert_u=ins_cols,
                              insert_v=N + ins_rows, delete_ids=delete_ids,
                              pad=PAD, timeout=60)
    # The churned matrix from the batch alone: kept entries, then inserts.
    new_rows = np.concatenate([np.delete(rows, delete_ids), ins_rows])
    new_cols = np.concatenate([np.delete(cols, delete_ids), ins_cols])
    assert new_plan.fingerprint == spmv_plan_key(N, N, new_rows, new_cols, K, PAD)
    built = len(affinity_calls)
    req = _request(new_rows, new_cols, seed=3)
    res = server.serve(req)
    assert res.info.cache_hit and res.info.fingerprint == new_plan.fingerprint
    assert len(affinity_calls) == built
    np.testing.assert_allclose(np.asarray(res.y), _ref(req), rtol=1e-4, atol=1e-4)


def _swap_entries(rows, cols):
    rows, cols = rows.copy(), cols.copy()
    i, j = 0, rows.size - 1
    rows[[i, j]], cols[[i, j]] = rows[[j, i]], cols[[j, i]]
    return rows, cols


CHANGES = {
    "k": lambda r, c: spmv_plan_key(N, N, r, c, K * 2, PAD),
    "pad": lambda r, c: spmv_plan_key(N, N, r, c, K, PAD * 2),
    "n_rows": lambda r, c: spmv_plan_key(N + 1, N, r, c, K, PAD),
    "n_cols": lambda r, c: spmv_plan_key(N, N + 1, r, c, K, PAD),
    "swapped_entry": lambda r, c: spmv_plan_key(N, N, *_swap_entries(r, c), K, PAD),
    "rows_cols_exchanged": lambda r, c: spmv_plan_key(N, N, c, r, K, PAD),
}


@pytest.mark.parametrize("change", sorted(CHANGES))
def test_key_changes_with_the_request(change):
    rows, cols = _coo(seed=4)
    key = spmv_plan_key(N, N, rows, cols, K, PAD)
    assert len(key) * 4 >= 128
    assert spmv_plan_key(N, N, rows.astype(np.int32), cols.copy(), K, PAD) == key
    assert CHANGES[change](rows, cols) != key


def test_in_place_edit_after_a_serve_is_a_miss(service):
    rows, cols = _coo(seed=5)
    server = GraphServer(service, k=K, pad=PAD, start_batcher=False, bucketing=None)
    req = _request(rows, cols, seed=5)
    first = server.serve(req).info
    req.rows[0] = (req.rows[0] + 1) % N  # the caller's own array, edited
    res = server.serve(req)
    assert not res.info.cache_hit and res.info.fingerprint != first.fingerprint
    np.testing.assert_allclose(np.asarray(res.y), _ref(req), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("lane", ["dedicated", "bucket"])
def test_warm_serve_builds_no_affinity_graph(service, affinity_calls, lane):
    server = GraphServer(service, k=K, pad=PAD, start_batcher=False,
                         bucketing=None if lane == "dedicated" else BucketPolicy())
    req = _request(*_coo(seed=6), seed=6)
    cold = server.serve(req)
    assert not cold.info.cache_hit and len(affinity_calls) == 1
    for _ in range(3):
        hits = service.stats.hits
        res = server.serve(req)
        assert service.stats.hits == hits + 1
        assert res.info == dataclasses.replace(cold.info, cache_hit=True,
                                               kernel_cache_hit=True)
    assert len(affinity_calls) == 1


def test_stale_only_rung_answers_cached_and_rejects_uncached(service, affinity_calls):
    server = GraphServer(service, k=K, pad=PAD, start_batcher=False, bucketing=None,
                         brownout_window_s=5.0, brownout_hedge_off=1,
                         brownout_stale_only=2, brownout_priority_floor=1)
    hot = _request(*_coo(seed=8), seed=8)
    server.serve(hot)
    for _ in range(2):
        server._note_rejection()
    hits = service.stats.hits
    res = server.serve(hot)
    assert res.info.degraded and res.info.cache_hit
    assert service.stats.hits == hits + 1
    with pytest.raises(AdmissionRejectedError) as ei:
        server.serve(_request(*_coo(seed=9), seed=9))
    assert ei.value.reason == "brownout"
    assert len(affinity_calls) == 1


# -- the key's sha256 tree ----------------------------------------------------

LEAF = 1 << 20  # bytes
LEAF_ENTRIES = LEAF // 8


def _serial_key(n_rows, n_cols, rows, cols, k, pad):
    """The key built leaf by leaf on this thread, from its definition."""
    meta = ("spmv", n_rows, n_cols, rows.size, k, pad, "ep", 0, "sha256-tree", LEAF)
    root = hashlib.sha256(b"repro.spmv_plan_key.root\x00" + repr(meta).encode())
    for a in (rows, cols):
        raw = np.ascontiguousarray(a, dtype=np.int64).tobytes()
        for i in range(0, len(raw), LEAF):
            root.update(hashlib.sha256(
                b"repro.spmv_plan_key.leaf\x00" + raw[i:i + LEAF]).digest())
    return root.hexdigest()


def _big_coo(nnz, seed=0, n=1 << 20):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n, nnz, dtype=np.int64), rng.integers(0, n, nnz, dtype=np.int64)


@pytest.mark.parametrize("nnz", [0, LEAF_ENTRIES, LEAF_ENTRIES + 1, 3 * LEAF_ENTRIES + 5],
                         ids=["empty", "one_leaf", "one_leaf_plus_one", "four_leaves"])
def test_key_equals_the_serial_tree(nnz):
    rows, cols = _big_coo(nnz, seed=nnz)
    n = 1 << 20
    assert spmv_plan_key(n, n, rows, cols, K, PAD) == _serial_key(n, n, rows, cols, K, PAD)


@pytest.mark.parametrize("leaf", ["first", "middle", "last"])
@pytest.mark.parametrize("array", ["rows", "cols"])
def test_an_edit_in_any_leaf_changes_the_key(array, leaf):
    rows, cols = _big_coo(3 * LEAF_ENTRIES, seed=11)
    n = 1 << 20
    key = spmv_plan_key(n, n, rows, cols, K, PAD)
    edited = {"rows": rows, "cols": cols}[array]
    i = {"first": 3, "middle": LEAF_ENTRIES + 17, "last": edited.size - 1}[leaf]
    edited[i] = (edited[i] + 1) % n
    assert spmv_plan_key(n, n, rows, cols, K, PAD) != key


def test_keys_from_many_threads_equal_the_serial_keys():
    n = 1 << 20
    coos = [_big_coo(2 * LEAF_ENTRIES + 1000 * t, seed=20 + t) for t in range(8)]
    want = [_serial_key(n, n, r, c, K, PAD) for r, c in coos]
    got = [[] for _ in coos]

    def work(t):
        for _ in range(4):
            got[t].append(spmv_plan_key(n, n, *coos[t], K, PAD))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(len(coos))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert got == [[w] * 4 for w in want]


@pytest.mark.parametrize("nnz", [N, 3 * LEAF_ENTRIES], ids=["inline", "pooled"])
def test_int32_and_strided_inputs_give_the_int64_key(nnz):
    rows, cols = _big_coo(nnz, seed=30)
    n = 1 << 20
    key = spmv_plan_key(n, n, rows, cols, K, PAD)
    interleaved = np.stack([rows, cols], axis=1)  # (nnz, 2): columns are strided
    s_rows, s_cols = interleaved[:, 0], interleaved[:, 1]
    assert not s_rows.flags.c_contiguous and not s_cols.flags.c_contiguous
    assert spmv_plan_key(n, n, s_rows, s_cols, K, PAD) == key
    assert spmv_plan_key(n, n, rows.astype(np.int32), cols.astype(np.int32), K, PAD) == key


# -- the value digest's sha256 tree -------------------------------------------

VALS_LEAF_ENTRIES = LEAF // 4  # float32


def _serial_vals_digest(vals):
    """The value digest built leaf by leaf on this thread, from its definition."""
    raw = np.ascontiguousarray(vals, dtype=np.float32).tobytes()
    meta = ("vals", "float32", len(raw) // 4, "sha256-tree", LEAF)
    root = hashlib.sha256(b"repro.vals_digest.root\x00" + repr(meta).encode())
    for i in range(0, len(raw), LEAF):
        root.update(hashlib.sha256(b"repro.vals_digest.leaf\x00" + raw[i:i + LEAF]).digest())
    return root.hexdigest()


def _vals_request(nnz, seed=0):
    """A request whose digest is all that is asked of it: one row, one column."""
    zeros = np.zeros(nnz, np.int64)
    vals = np.random.default_rng(seed).standard_normal(nnz)
    return GraphRequest(1, 1, zeros, zeros, vals, np.zeros(1))


@pytest.mark.parametrize("nnz", [0, VALS_LEAF_ENTRIES, VALS_LEAF_ENTRIES + 1,
                                 3 * VALS_LEAF_ENTRIES + 5],
                         ids=["empty", "one_leaf", "one_leaf_plus_one", "four_leaves"])
def test_vals_digest_equals_the_serial_tree(nnz):
    req = _vals_request(nnz, seed=nnz)
    assert req.vals_digest() == _serial_vals_digest(req.vals)


@pytest.mark.parametrize("leaf", ["first", "middle", "last"])
def test_an_edit_in_any_leaf_changes_the_vals_digest(leaf):
    req = _vals_request(3 * VALS_LEAF_ENTRIES, seed=12)
    before = req.vals_digest()
    i = {"first": 3, "middle": VALS_LEAF_ENTRIES + 17, "last": req.vals.size - 1}[leaf]
    req.vals[i] = np.nextafter(req.vals[i], np.float32(np.inf))
    assert req.vals_digest() != before


def test_vals_digests_from_many_threads_equal_the_serial_ones():
    reqs = [_vals_request(3 * VALS_LEAF_ENTRIES + 1000 * t, seed=40 + t) for t in range(8)]
    want = [_serial_vals_digest(r.vals) for r in reqs]
    got = [[] for _ in reqs]

    def work(t):
        for _ in range(4):
            got[t].append(reqs[t].vals_digest())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(len(reqs))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert got == [[w] * 4 for w in want]


@pytest.mark.parametrize("nnz", [N, 2 * LEAF_ENTRIES], ids=["inline", "pooled"])
def test_vals_digest_differs_from_the_plan_key_over_the_same_bytes(nnz):
    rows, cols = _big_coo(nnz, seed=50)
    n = 1 << 20
    # vals whose bytes are those of rows then cols, leaf for leaf.
    same = np.concatenate([rows, cols]).view(np.float32)
    req = GraphRequest(1, 1, np.zeros(same.size, np.int64), np.zeros(same.size, np.int64),
                       same, np.zeros(1))
    assert req.vals.tobytes() == rows.tobytes() + cols.tobytes()
    assert req.vals_digest() != spmv_plan_key(n, n, rows, cols, K, PAD)
    # The prefixes alone keep the trees apart, even under one meta.
    meta = ("vals", "float32", same.size, "sha256-tree", LEAF)
    key_tree, _ = digest.sha256_tree(ps._LEAF_PREFIX, ps._ROOT_PREFIX, meta, req.vals)
    assert req.vals_digest() != key_tree


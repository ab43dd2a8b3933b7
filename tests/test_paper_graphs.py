"""Pinned partition quality on the paper's five matrix families.

The graphs reproduce the structural families the paper evaluates — banded
FEM (cant), uniform random (circuit5M), power-law (in-2004, scircuit) and
mesh (mc2depi) — at 5% of their reference sizes, with the task order
shuffled so that the stored order hides the locality (the paper's
irregular setting).  Everything here is seeded, so the numbers are exact:

  * the EP vertex cut at k=64 stays within 10% of the cut the partitioner
    reached when these ceilings were pinned;
  * the V-cycle stays within two levels of its pinned depth (a blow-up
    means cluster coarsening degenerated toward pairwise matching);
  * over a seeded stream of churn batches sweeping the 1-20% band, the
    gear policy keeps full rebuilds a minority, engages the local gear in
    the mid band, and keeps every updated plan's cut within 15% of a
    same-run full rebuild.

Every pinned value was read from a CPU run of these exact graphs (scale
0.05, k=64) with the multilevel partitioner's default options.
"""
import functools

import numpy as np
import pytest

from repro.core import (
    EdgeList,
    PartitionService,
    edge_partition,
    synthetic_banded_graph,
    synthetic_mesh_graph,
    synthetic_powerlaw_graph,
    synthetic_random_graph,
)

SCALE = 0.05
K = 64

# name -> (generator, EP vertex cut, V-cycle levels) at SCALE and K.
GRAPHS = {
    "cant-like(banded)": (
        lambda s: synthetic_banded_graph(int(30_000 * s), band=12, seed=0), 3587, 4),
    "circuit5M-like(random)": (
        lambda s: synthetic_random_graph(int(90_000 * s), int(300_000 * s), seed=1),
        9737, 4),
    "in2004-like(powerlaw)": (
        lambda s: synthetic_powerlaw_graph(int(50_000 * s), int(280_000 * s),
                                           alpha=2.1, seed=2), 7170, 4),
    "mc2depi-like(mesh)": (
        lambda s: synthetic_mesh_graph(int(220 * np.sqrt(s)), seed=3), 985, 3),
    "scircuit-like(powerlaw)": (
        lambda s: synthetic_powerlaw_graph(int(30_000 * s), int(90_000 * s),
                                           alpha=2.4, seed=4), 2691, 3),
}
NAMES = list(GRAPHS)

CUT_SLACK = 0.10
LEVELS_SLACK = 2

# The churn stream: the 1-20% band swept twice per graph, each rate
# jittered by a factor drawn from one seeded generator across all graphs.
STREAM_RATES = (0.01, 0.03, 0.05, 0.08, 0.12, 0.20)
STREAM_EVENTS = 12
STREAM_SEED = 11
MID_BAND = 0.06
DRIFT_CEILING = 1.15


def paper_graph(name: str) -> EdgeList:
    """The named family at SCALE, its task order shuffled by a fixed seed."""
    g = GRAPHS[name][0](SCALE)
    perm = np.random.default_rng(NAMES.index(name) + 50).permutation(g.m)
    return EdgeList(n=g.n, u=g.u[perm], v=g.v[perm])


@functools.cache
def _ep(name: str):
    return edge_partition(paper_graph(name), K, method="ep")


@pytest.mark.parametrize("name", NAMES)
def test_ep_cut_within_pinned_ceiling(name):
    assert _ep(name).vertex_cut <= (1 + CUT_SLACK) * GRAPHS[name][1]


@pytest.mark.parametrize("name", NAMES)
def test_vcycle_levels_within_pinned_depth(name):
    stats = _ep(name).stats
    assert stats is not None and stats.levels > 0
    assert stats.levels <= GRAPHS[name][2] + LEVELS_SLACK


def _churn_batch(g: EdgeList, rate: float, seed: int):
    """Half deletions, half random insertions totalling ``rate * m`` tasks."""
    rng = np.random.default_rng(seed)
    n_half = max(int(rate * g.m / 2), 1)
    delete_ids = rng.choice(g.m, size=n_half, replace=False)
    ins_u = rng.integers(0, g.n, n_half).astype(np.int64)
    ins_v = rng.integers(0, g.n, n_half).astype(np.int64)
    return ins_u, ins_v, delete_ids


@pytest.mark.parametrize("name", NAMES)
def test_churn_stream_gear_mix_and_drift(name):
    """Each update's base is the previous update's plan, so the policy's
    accumulated drift is exercised, not only its per-batch threshold."""
    jitter = np.random.default_rng(STREAM_SEED).uniform(
        0.8, 1.25, size=(len(NAMES), STREAM_EVENTS))[NAMES.index(name)]
    gears, drifts, mid_local = [], [], 0
    with PartitionService() as svc:
        cur = svc.get(paper_graph(name), K, tenant=name)
        for i in range(STREAM_EVENTS):
            rate = STREAM_RATES[i % len(STREAM_RATES)] * jitter[i]
            ins_u, ins_v, delete_ids = _churn_batch(
                cur.edges, rate, seed=STREAM_SEED + 100 * i)
            upd = svc.update(cur.fingerprint, K, insert_u=ins_u, insert_v=ins_v,
                             delete_ids=delete_ids, tenant=name)
            full = edge_partition(upd.edges, K, method="ep")
            drifts.append(upd.result.quality.vertex_cut
                          / max(full.quality.vertex_cut, 1))
            gears.append(upd.source)
            if upd.source == "local" and 2 * len(ins_u) / upd.edges.m <= MID_BAND:
                mid_local += 1
            cur = upd
    assert gears.count("full") < STREAM_EVENTS / 2, gears
    assert gears.count("local") > 0 and mid_local > 0, gears
    assert max(drifts) <= DRIFT_CEILING, drifts

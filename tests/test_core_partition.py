"""Unit tests: core EP model — graphs, partitioner, transform, metrics."""
import numpy as np
import pytest

from repro.core import (
    EdgeList,
    MultilevelOptions,
    affinity_graph_from_coo,
    clone_and_connect,
    contracted_clone_graph,
    csr_from_edges,
    edge_partition,
    evaluate_edge_partition,
    partition_vertices,
    parts_per_vertex,
    reconstruct_edge_partition,
    stencil_27pt_coo,
    synthetic_banded_graph,
    synthetic_bipartite_graph,
    synthetic_mesh_graph,
    synthetic_powerlaw_graph,
    vertex_cut_cost,
)


def _paper_example():
    """Figure 3(a): 6 interactions over 7 particles (path-ish mesh)."""
    # Vertices 0..6; edges A..F as in the running cfd example.
    u = np.array([0, 1, 2, 3, 3, 5])
    v = np.array([1, 2, 3, 4, 5, 6])
    return EdgeList(n=7, u=u, v=v)


class TestGraph:
    def test_degrees(self):
        e = _paper_example()
        deg = e.degrees()
        assert deg.sum() == 2 * e.m
        assert e.max_degree() == 3  # vertex 3 touches edges 3,4,5? -> (2,3),(3,4),(3,5)

    def test_csr_symmetric(self):
        g = csr_from_edges(4, np.array([0, 1, 2]), np.array([1, 2, 3]))
        assert g.n == 4
        assert g.nnz == 6  # each edge stored both ways
        # neighbour sets symmetric
        def nbrs(v):
            return set(g.indices[g.indptr[v] : g.indptr[v + 1]].tolist())

        for a in range(4):
            for b in nbrs(a):
                assert a in nbrs(b)

    def test_csr_dedupes_parallel_edges(self):
        g = csr_from_edges(3, np.array([0, 0]), np.array([1, 1]))
        assert g.nnz == 2
        assert g.eweights.max() == 2.0

    def test_self_loops_dropped(self):
        g = csr_from_edges(3, np.array([0, 1]), np.array([0, 2]))
        assert g.nnz == 2

    @pytest.mark.parametrize("g", [1, 2, 5])
    def test_stencil_27pt(self, g):
        n, rows, cols = stencil_27pt_coo(g)
        assert n == g**3
        assert rows.size == (3 * g - 2) ** 3
        assert np.all(np.diff(rows * n + cols) > 0)  # row-major, no duplicates
        # Symmetric, with the diagonal, and every coupling within one step.
        assert set(zip(rows.tolist(), cols.tolist())) == set(zip(cols.tolist(), rows.tolist()))
        assert np.count_nonzero(rows == cols) == n
        pr = np.stack(np.unravel_index(rows, (g, g, g)))
        pc = np.stack(np.unravel_index(cols, (g, g, g)))
        assert np.abs(pr - pc).max() <= 1
        assert np.bincount(rows, minlength=n).max() == min(3, g) ** 3

    def test_affinity_from_coo_bipartite(self):
        e = affinity_graph_from_coo(3, 4, rows=np.array([0, 1, 2]), cols=np.array([1, 1, 3]))
        assert e.n == 7
        assert (e.u < 4).all()  # x side
        assert (e.v >= 4).all()  # y side


class TestTransform:
    def test_clone_count(self):
        e = _paper_example()
        cg = clone_and_connect(e)
        assert cg.graph.n == 2 * e.m
        # aux edges: sum_v max(d_v - 1, 0)
        deg = e.degrees()
        want_aux = int(np.maximum(deg - 1, 0).sum())
        assert cg.aux_src.shape[0] == want_aux

    def test_clone_paths_are_paths(self):
        """Each vertex's clones form a path: degree <= 2 within aux edges."""
        e = synthetic_powerlaw_graph(50, 200, seed=1)
        cg = clone_and_connect(e)
        deg = np.zeros(2 * e.m, dtype=int)
        np.add.at(deg, cg.aux_src, 1)
        np.add.at(deg, cg.aux_dst, 1)
        assert deg.max() <= 2

    def test_contracted_matches_cloned_structure(self):
        e = _paper_example()
        h = contracted_clone_graph(e)
        assert h.n == e.m
        cg = clone_and_connect(e)
        # contracted edge count (before dedupe) == aux edge count
        assert h.nnz <= 2 * cg.aux_src.shape[0]

    def test_theorem1_cutbound(self):
        """Aux-edge cut of a D' partition >= vertex-cut of the reconstructed
        edge partition (Theorem 1)."""
        rng = np.random.default_rng(0)
        e = synthetic_powerlaw_graph(60, 240, seed=3)
        cg = clone_and_connect(e)
        for k in (2, 4, 8):
            # any labeling that never cuts original edges:
            edge_labels = rng.integers(0, k, size=e.m).astype(np.int32)
            clone_labels = np.repeat(edge_labels, 2)
            aux_cut = int(
                (clone_labels[cg.aux_src] != clone_labels[cg.aux_dst]).sum()
            )
            c_ep = vertex_cut_cost(e, edge_labels, k)
            assert aux_cut >= c_ep

    def test_reconstruction_roundtrip(self):
        e = _paper_example()
        cg = clone_and_connect(e)
        edge_labels = np.array([0, 0, 0, 1, 1, 1], dtype=np.int32)
        clone_labels = np.repeat(edge_labels, 2)
        rec = reconstruct_edge_partition(cg, clone_labels)
        assert (rec == edge_labels).all()


class TestVertexPartitioner:
    def test_trivial_k1(self):
        g = csr_from_edges(10, np.arange(9), np.arange(1, 10))
        labels, stats = partition_vertices(g, 1)
        assert (labels == 0).all()

    def test_balanced_two_cliques(self):
        """Two cliques joined by one edge: optimal 2-cut is the bridge."""
        edges = []
        for base in (0, 8):
            for i in range(8):
                for j in range(i + 1, 8):
                    edges.append((base + i, base + j))
        edges.append((0, 8))
        eu = np.array([a for a, _ in edges])
        ev = np.array([b for _, b in edges])
        g = csr_from_edges(16, eu, ev)
        labels, stats = partition_vertices(g, 2, MultilevelOptions(seed=0))
        assert stats.edgecut == 1.0
        assert stats.balance <= 1.03 + 1e-9

    @pytest.mark.parametrize("k", [2, 4, 8, 16])
    def test_balance_respected_mesh(self, k):
        e = synthetic_mesh_graph(24)
        g = csr_from_edges(e.n, e.u, e.v)
        labels, stats = partition_vertices(g, k, MultilevelOptions(seed=1))
        assert labels.shape == (e.n,)
        assert labels.min() >= 0 and labels.max() < k
        assert stats.balance <= 1.10  # eps=0.03 cap + ceil slack on small parts

    def test_deterministic_given_seed(self):
        e = synthetic_powerlaw_graph(200, 800, seed=5)
        g = csr_from_edges(e.n, e.u, e.v)
        l1, _ = partition_vertices(g, 4, MultilevelOptions(seed=7))
        l2, _ = partition_vertices(g, 4, MultilevelOptions(seed=7))
        assert (l1 == l2).all()

    def test_mesh_cut_beats_random(self):
        e = synthetic_mesh_graph(32)
        g = csr_from_edges(e.n, e.u, e.v)
        labels, stats = partition_vertices(g, 4, MultilevelOptions(seed=0))
        rng = np.random.default_rng(0)
        rand = rng.integers(0, 4, size=e.n)
        from repro.core.partition import edgecut

        assert stats.edgecut < 0.3 * edgecut(g, rand)


class TestEdgePartition:
    @pytest.mark.parametrize("method", ["ep", "ep-cloned", "default", "random", "greedy", "hypergraph"])
    def test_valid_partition_all_methods(self, method):
        e = synthetic_mesh_graph(12, seed=0)
        k = 4
        res = edge_partition(e, k, method=method)
        assert res.labels.shape == (e.m,)
        assert res.labels.min() >= 0 and res.labels.max() < k
        assert res.quality.balance <= 1.25  # all methods keep rough balance

    def test_paper_example_two_way(self):
        """Figure 3(e): a 2-way EP of the cfd example with vertex cut 1 exists;
        our partitioner must find cost <= 2 (optimal is 1)."""
        e = _paper_example()
        res = edge_partition(e, 2, method="ep")
        assert res.vertex_cut <= 2
        assert res.quality.balance <= 1.34  # 4/3 with m=6,k=2

    def test_ep_beats_random_and_greedy_mesh(self):
        e = synthetic_mesh_graph(24, seed=0)
        k = 8
        ep = edge_partition(e, k, method="ep")
        rnd = edge_partition(e, k, method="random")
        grd = edge_partition(e, k, method="greedy")
        assert ep.vertex_cut < rnd.vertex_cut
        assert ep.vertex_cut <= grd.vertex_cut

    def test_ep_beats_default_on_scattered_order(self):
        """Shuffle task order: 'default' chunks lose locality, EP recovers it."""
        e = synthetic_mesh_graph(20, seed=0)
        rng = np.random.default_rng(1)
        perm = rng.permutation(e.m)
        shuffled = EdgeList(n=e.n, u=e.u[perm], v=e.v[perm])
        k = 8
        ep = edge_partition(shuffled, k, method="ep")
        default = edge_partition(shuffled, k, method="default")
        assert ep.vertex_cut < 0.7 * default.vertex_cut

    def test_cloned_and_contracted_agree_roughly(self):
        e = synthetic_banded_graph(300, band=6, seed=0)
        k = 6
        a = edge_partition(e, k, method="ep")
        b = edge_partition(e, k, method="ep-cloned")
        # Same model, two constructions: quality within 2x of each other.
        assert a.vertex_cut <= 2 * max(b.vertex_cut, 1)
        assert b.vertex_cut <= 2 * max(a.vertex_cut, 1)

    def test_bipartite_spmv_graph(self):
        e, rows, cols = synthetic_bipartite_graph(64, 64, 5, seed=2)
        res = edge_partition(e, 8, method="ep")
        q0 = edge_partition(e, 8, method="random").quality
        assert res.quality.vertex_cut < q0.vertex_cut


class TestCachedCOOView:
    def test_coo_view_matches_expansion_and_is_cached(self):
        e = synthetic_powerlaw_graph(120, 500, seed=3)
        g = csr_from_edges(e.n, e.u, e.v)
        want = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
        assert (g.coo_src == want).all()
        assert g.coo_src is g.coo_src  # cached, not rebuilt per access
        assert (g.coo_dst == g.indices.astype(np.int64)).all()
        assert g.coo_dst is g.coo_dst

    def test_coo_views_are_read_only(self):
        """The cached COO arrays are shared by every coarsening/contraction
        round on the graph — an in-place write must fail loudly instead of
        silently corrupting later rounds."""
        e = synthetic_mesh_graph(8, seed=0)
        g = csr_from_edges(e.n, e.u, e.v)
        for arr in (g.coo_src, g.coo_dst):
            assert not arr.flags.writeable
            with pytest.raises(ValueError, match="read-only"):
                arr[0] = 99
        # The underlying CSR stays as built — the guard protects, not alters.
        assert g.coo_src[0] == 0

    def test_stats_edgecut_bit_identical_to_fresh_expansion(self):
        """PartitionStats.edgecut is routed through the cached COO view; it
        must be bit-identical to the naive re-expansion computation."""
        from repro.core.partition import edgecut

        e = synthetic_mesh_graph(20, seed=0)
        g = csr_from_edges(e.n, e.u, e.v)
        labels, stats = partition_vertices(g, 8, MultilevelOptions(seed=0))
        src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
        fresh = float(g.eweights[labels[src] != labels[g.indices]].sum() / 2.0)
        assert stats.edgecut == fresh  # bit-identical, not approx
        assert edgecut(g, labels) == fresh

    def test_fig6_quality_bit_identical_recompute(self):
        """Under the default seed, the quality carried by the result equals
        an independent recomputation exactly (the cached view changes where
        the numbers come from, never what they are)."""
        for maker in (
            lambda: synthetic_mesh_graph(14, seed=3),
            lambda: synthetic_powerlaw_graph(400, 1600, seed=2),
        ):
            e = maker()
            res = edge_partition(e, 16, method="ep")
            assert res.quality == evaluate_edge_partition(e, res.labels, 16)

    def test_stage_timings_reported(self):
        e = synthetic_powerlaw_graph(300, 1200, seed=1)
        res = edge_partition(e, 8, method="ep")
        st = res.stats
        assert st is not None
        assert st.coarsen_s >= 0 and st.init_s >= 0 and st.refine_s >= 0
        # Stage times are wall-clock subsets of the total partition time.
        assert st.coarsen_s + st.init_s + st.refine_s <= res.partition_time_s
        assert edge_partition(e, 8, method="random").stats is None


class TestSyntheticGenerators:
    def test_random_generators_never_emit_self_loops(self):
        """The self-loop fixup must hold for every size/seed — including the
        tiny graphs where (v+1) % n wraps around."""
        from repro.core import synthetic_random_graph

        for n in (2, 3, 5, 50):
            for seed in range(4):
                e = synthetic_random_graph(n, 6 * n, seed=seed)
                assert not (e.u == e.v).any()
                e = synthetic_powerlaw_graph(n, 6 * n, seed=seed)
                assert not (e.u == e.v).any()

    def test_single_vertex_loop_fixup_rejected(self):
        """n=1 cannot host a loop-free edge: fail loudly, don't emit loops."""
        from repro.core import synthetic_random_graph

        with pytest.raises(ValueError, match="n >= 2"):
            synthetic_random_graph(1, 4, seed=0)
        with pytest.raises(ValueError, match="n >= 2"):
            synthetic_powerlaw_graph(1, 4, seed=0)


class TestMetrics:
    def test_parts_per_vertex_manual(self):
        e = _paper_example()
        labels = np.array([0, 0, 0, 1, 1, 1])
        pv = parts_per_vertex(e, labels, 2)
        # vertex 3 incident to edges (2,3)->0,(3,4)->1,(3,5)->1 => 2 parts
        assert pv[3] == 2
        assert vertex_cut_cost(e, labels, 2) == 1  # only vertex 3 cut

    def test_single_cluster_zero_cost(self):
        e = _paper_example()
        labels = np.zeros(e.m, dtype=np.int32)
        assert vertex_cut_cost(e, labels, 1) == 0

    def test_quality_eval_fields(self):
        e = _paper_example()
        labels = np.array([0, 0, 0, 1, 1, 1])
        q = evaluate_edge_partition(e, labels, 2)
        assert q.vertex_cut == 1
        assert q.loads_total == 8  # 7 touched vertices + 1 redundant
        assert 0 < q.redundant_fraction < 0.2

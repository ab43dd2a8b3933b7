"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_pack_plan, edge_partition
from repro.core.graph import synthetic_bipartite_graph
from repro.kernels import ep_spmv, flash_attention, make_ep_spmv_fn, moe_mlp
from repro.kernels.ref import flash_attention_ref, moe_mlp_ref, spmv_coo_ref


def _spmv_problem(n_rows, n_cols, nnz_per_row, k, seed=0, dtype=np.float32):
    edges, rows, cols = synthetic_bipartite_graph(n_rows, n_cols, nnz_per_row, seed=seed)
    res = edge_partition(edges, k, method="ep", seed=seed)
    plan = build_pack_plan(n_rows, n_cols, rows, cols, res.labels, k, pad=8)
    rng = np.random.default_rng(seed)
    vals = rng.standard_normal(rows.shape[0]).astype(dtype)
    x = rng.standard_normal(n_cols).astype(dtype)
    return plan, rows, cols, vals, x


class TestEpSpmv:
    @pytest.mark.parametrize("n_rows,n_cols,nnz,k", [
        (64, 64, 4, 4),
        (128, 96, 3, 8),
        (33, 47, 5, 3),   # ragged, non-power-of-2
        (1100, 1100, 3, 1),  # x/y tiles wider than one 1024-lane slab
    ])
    @pytest.mark.parametrize("mode", ["software", "streaming"])
    def test_matches_coo_ref(self, n_rows, n_cols, nnz, k, mode):
        plan, rows, cols, vals, x = _spmv_problem(n_rows, n_cols, nnz, k)
        y = ep_spmv(jnp.asarray(x), plan, vals, mode=mode)
        ref = spmv_coo_ref(n_rows, jnp.asarray(rows), jnp.asarray(cols),
                           jnp.asarray(vals), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_dtypes(self, dtype):
        plan, rows, cols, vals, x = _spmv_problem(64, 64, 4, 4, dtype=dtype)
        y = ep_spmv(jnp.asarray(x), plan, vals, mode="software")
        ref = spmv_coo_ref(64, jnp.asarray(rows), jnp.asarray(cols),
                           jnp.asarray(vals), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_jit_fn_reusable(self):
        plan, rows, cols, vals, x = _spmv_problem(64, 64, 4, 4)
        fn = make_ep_spmv_fn(plan, vals, mode="software")
        y1 = fn(jnp.asarray(x))
        y2 = fn(jnp.asarray(x * 2))
        np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1), rtol=1e-5)

    def test_each_bound_plan_owns_its_executable(self):
        # Two bindings of one tile shape compile twice: a binding's program
        # is dropped with it (a bounded compile cache bounds executables).
        plan, rows, cols, vals, x = _spmv_problem(64, 64, 4, 4)
        compiles = []

        def listener(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(duration)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for scale in (1.0, 2.0):
                make_ep_spmv_fn(plan, vals * scale)(jnp.asarray(x)).block_until_ready()
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)
        assert len(compiles) == 2


class TestMoeMlp:
    @pytest.mark.parametrize("e,c,d,f,tm", [
        (4, 128, 64, 128, 128),
        (2, 256, 128, 256, 128),
        (8, 128, 32, 64, 64),
    ])
    def test_matches_ref(self, e, c, d, f, tm):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)
        wg = jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32)
        wu = jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32)
        wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32)
        out = moe_mlp(x, wg, wu, wd, tm=tm)
        ref = moe_mlp_ref(x, wg, wu, wd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,s,d,qb,kc", [
        (1, 2, 128, 32, 64, 64),
        (2, 4, 256, 64, 128, 128),
        (1, 1, 128, 128, 128, 128),
        (2, 2, 192, 32, 64, 64),   # nq=3, non-power-of-two grid
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, b, h, s, d, qb, kc, causal):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, q_block=qb, kv_chunk=kc)
        ref = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_cross_attention_shape(self):
        # T != S (decoder attending to longer memory).
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
        out = flash_attention(q, k, v, causal=False, q_block=64, kv_chunk=64)
        ref = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
        )

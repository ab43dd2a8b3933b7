"""Flash attention Pallas TPU kernel (prefill/training building block).

Tiling: grid = (B·H, S/q_block); each cell owns one q tile in VMEM and
streams the K/V tiles for its (batch, head) through an in-kernel fori_loop
with the classic online-softmax recurrence.  Causal cells stop the loop at
the diagonal (≈2x fewer K/V tiles touched than a masked full sweep — the
same waste the pure-JAX layer pays; this kernel is the TPU fix).

VMEM budget per cell: q_block·D + 2·T·D floats (+ (q_block, kv_chunk)
scores).  At D=128, T=8192, q_block=256, kv_chunk=512: ~8.5 MB — inside a
v5e's ~16 MB VMEM.  Longer T wants a kv-grid axis with accumulator
scratch; documented as the scale-out variant, not needed for validation.

MXU alignment: q_block multiple of 8, D and kv_chunk multiples of 128
(enforced), f32 accumulation via preferred_element_type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import backend as _backend

__all__ = ["flash_attention"]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, kv_chunk, q_block):
    qi = pl.program_id(1)
    q = q_ref[0]  # (qb, D) VMEM tile
    t = k_ref.shape[1]
    dh = q.shape[-1]
    scale = 1.0 / np.sqrt(dh)
    nk_total = t // kv_chunk
    if causal:
        # Only tiles up to the diagonal contribute.
        last = (qi + 1) * q_block  # exclusive q end
        nk = (last + kv_chunk - 1) // kv_chunk
        nk = jnp.minimum(nk, nk_total)
    else:
        nk = nk_total

    def body(i, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(i * kv_chunk, kv_chunk), :]  # (kc, D)
        v = v_ref[0, pl.ds(i * kv_chunk, kv_chunk), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (qb, kc)
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = i * kv_chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[:, None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((q_block,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q_block,), jnp.float32)
    a0 = jnp.zeros((q_block, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, H, T, D) — already head-repeated for GQA
    v: jax.Array,  # (B, H, T, D)
    *,
    causal: bool = True,
    q_block: int = 128,
    kv_chunk: int = 128,
) -> jax.Array:
    b, h, s, dh = q.shape
    t = k.shape[2]
    q_block = min(q_block, s)
    kv_chunk = min(kv_chunk, t)
    assert s % q_block == 0 and t % kv_chunk == 0, (s, q_block, t, kv_chunk)
    bh = b * h
    qf = q.reshape(bh, s, dh)
    kf = k.reshape(bh, t, dh)
    vf = v.reshape(bh, t, dh)
    grid = (bh, s // q_block)
    kernel = functools.partial(
        _flash_kernel, causal=causal, kv_chunk=kv_chunk, q_block=q_block
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, dh), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, dh), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        interpret=_backend.pallas_interpret(),
    )(qf, kf, vf)
    return out.reshape(b, h, s, dh)

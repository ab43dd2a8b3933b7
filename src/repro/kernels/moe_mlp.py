"""Grouped expert-MLP Pallas kernel for EP-scheduled MoE dispatch.

The EP model's MoE application (DESIGN.md §3.2): routed (token, expert)
pairs are tasks; the EP scheduler packs each expert's tokens into a padded
capacity slab.  This kernel consumes the packed slabs: grid cell (e, t)
computes the SwiGLU expert FFN for token tile t of expert e, with the
expert's weights staged in VMEM for the duration of its row of tiles —
VMEM reuse of weights across a tile row is the cache-domain structure the
paper builds for x in SpMV, applied to the expert weights (the hot shared
data object of a MoE layer).

Blocking: token tiles of ``tm`` rows (multiple of 8); d_model and d_ff kept
whole per block (MoE expert d_ff in the assigned archs is small: 768/1408),
rounded up to 128 by the caller.  MXU dims (tm × d_model × d_ff) are
hardware-aligned multiples of (8, 128, 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import backend as _backend

__all__ = ["moe_mlp"]


def _moe_mlp_kernel(x_ref, wg_ref, wu_ref, wd_ref, out_ref):
    x = x_ref[0]      # (tm, d_model) token tile of expert e
    wg = wg_ref[0]    # (d_model, d_ff) gate weights, staged in VMEM
    wu = wu_ref[0]    # (d_model, d_ff)
    wd = wd_ref[0]    # (d_ff, d_model)
    gate = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = jax.nn.silu(gate) * up
    out_ref[0] = jnp.dot(h, wd, preferred_element_type=jnp.float32).astype(out_ref.dtype)


def moe_mlp(
    x_packed: jax.Array,  # (n_experts, capacity, d_model) packed token slabs
    w_gate: jax.Array,    # (n_experts, d_model, d_ff)
    w_up: jax.Array,      # (n_experts, d_model, d_ff)
    w_down: jax.Array,    # (n_experts, d_ff, d_model)
    *,
    tm: int = 128,
) -> jax.Array:
    """SwiGLU expert FFN over packed per-expert token tiles."""
    n_experts, capacity, d_model = x_packed.shape
    d_ff = w_gate.shape[-1]
    if capacity % tm:
        raise ValueError(f"capacity {capacity} must be a multiple of tm {tm}")
    grid = (n_experts, capacity // tm)
    return pl.pallas_call(
        _moe_mlp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm, d_model), lambda e, t: (e, t, 0)),
            # Expert weights: same block for every t -> stays resident in
            # VMEM across the expert's whole tile row (weight reuse).
            pl.BlockSpec((1, d_model, d_ff), lambda e, t: (e, 0, 0)),
            pl.BlockSpec((1, d_model, d_ff), lambda e, t: (e, 0, 0)),
            pl.BlockSpec((1, d_ff, d_model), lambda e, t: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tm, d_model), lambda e, t: (e, t, 0)),
        out_shape=jax.ShapeDtypeStruct(x_packed.shape, x_packed.dtype),
        interpret=_backend.pallas_interpret(),
    )(x_packed, w_gate, w_up, w_down)

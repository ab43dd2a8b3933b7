"""Mixture-of-Experts layer with scatter-based dispatch (pure JAX).

Dispatch strategy: scatter/gather with explicit capacity slabs rather than
the one-hot (T, E, C) dispatch einsum — the latter's dispatch tensor is
O(T·E·C) and cannot fit any memory at qwen3-moe scale (1M tokens × 128
experts).  Scatter-add keeps everything O(T·k + E·C·D):

  1. router logits -> softmax -> top-k (weights, ids);
  2. position-in-expert via a one-hot cumsum over the flattened (T·k) routed
     pairs (associative scan — GSPMD partitions it);
  3. tokens scatter-added into per-expert capacity slabs (E, C, D);
  4. grouped expert SwiGLU over the slabs (einsum over the E axis —
     sharded along 'model' for expert parallelism);
  5. outputs gathered back per routed pair and combined with router weights.

Overflowed tokens (beyond capacity) are dropped from that expert (standard
capacity-factor semantics); their combine weight contributes nothing.

The EP model's scheduling hook (core/moe_schedule.py) reorders tokens and
places experts *offline* so that step 3/5's all-to-all crosses as few shard
boundaries as possible; the layer itself is schedule-agnostic (it consumes
an optional ``expert_perm`` giving the EP-chosen expert placement).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .layers import init_linear

__all__ = ["init_moe_params", "moe_ffn", "router_load_balancing_loss"]


def init_moe_params(key, d_model: int, cfg, dtype) -> dict:
    """cfg is a configs.base.MoESettings."""
    keys = jax.random.split(key, 6)
    p = {
        "router": init_linear(keys[0], d_model, cfg.n_experts, jnp.float32),
        "w_gate": _init_experts(keys[1], cfg.n_experts, d_model, cfg.d_ff_expert, dtype),
        "w_up": _init_experts(keys[2], cfg.n_experts, d_model, cfg.d_ff_expert, dtype),
        "w_down": _init_experts(keys[3], cfg.n_experts, cfg.d_ff_expert, d_model, dtype),
    }
    if cfg.n_shared_experts:
        f_shared = cfg.n_shared_experts * cfg.d_ff_expert
        p["shared"] = {
            "w_gate": init_linear(keys[4], d_model, f_shared, dtype),
            "w_up": init_linear(keys[5], d_model, f_shared, dtype),
            "w_down": init_linear(keys[4], f_shared, d_model, dtype),
            "gate": init_linear(keys[5], d_model, 1, jnp.float32),
        }
    return p


def _init_experts(key, e, d_in, d_out, dtype):
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * scale).astype(dtype)


def router_load_balancing_loss(router_probs: jax.Array, expert_ids: jax.Array, n_experts: int) -> jax.Array:
    """Switch-Transformer aux loss: E * sum_e f_e * p_e (1.0 at uniform)."""
    t = router_probs.shape[0]
    counts = jnp.zeros(n_experts, jnp.float32).at[expert_ids.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = router_probs.mean(axis=0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)


def _dispatch_shard_map():
    """(shard_map fn, dp axes, tp axis, mesh) if a profile is active.

    GSPMD cannot see that the dispatch scatter is shard-local — each source
    row writes only the slab slice of its own data shard, but the compiler
    partial-sums the full slab across shards anyway (measured: 2 x 0.97 TB
    all-reduce per step on qwen3-moe train).  shard_map expresses the
    locality manually: per-shard scatter/gather with ZERO collectives, and
    (when E divides the 'model' axis) each device builds only ITS expert
    slice — activations are replicated across 'model', so this costs no
    communication either; the combine is one bf16 psum of token outputs
    (the minimal possible all-to-all volume).
    """
    from ..runtime.axes import get_activation_sharding

    prof = get_activation_sharding()
    if prof is None:
        return None
    dp = tuple(prof.logical.get("batch", ()))
    dp = tuple(a for a in dp if a in prof.mesh.shape)
    if not dp:
        return None
    tp = tuple(prof.logical.get("model", ()))
    tp = tuple(a for a in tp if a in prof.mesh.shape)
    return jax.shard_map, dp, (tp[0] if tp else None), prof.mesh


def _dispatch_scatter(ids_s, pos_c, x_rep, n_experts: int, cap_l: int):
    """(ns, Tl*k) indices + (ns, Tl*k, D) rows -> slab (E, ns, cap_l, D).

    Shard-local when a mesh profile is active (see _dispatch_shard_map);
    falls back to a plain batched scatter otherwise (identical semantics).
    """
    ns, tl, d = x_rep.shape
    sm = _dispatch_shard_map() if ns > 1 else None
    if sm is not None:
        shard_map, dp, tp, mesh = sm
        from jax.sharding import PartitionSpec as P

        tp_size = mesh.shape.get(tp, 1) if tp else 1
        if tp and n_experts % tp_size == 0 and tp_size > 1:
            e_per = n_experts // tp_size

            def local2d(ids_l, pos_l, x_l):
                # Expert-sharded: this device builds only its E-slice.
                e0 = jax.lax.axis_index(tp) * e_per
                rel = ids_l - e0
                ok = (rel >= 0) & (rel < e_per)
                x_m = jnp.where(ok[..., None], x_l, 0)
                rel_c = jnp.clip(rel, 0, e_per - 1)
                sidx = jnp.broadcast_to(
                    jnp.arange(ids_l.shape[0])[:, None], ids_l.shape
                )
                slab_l = jnp.zeros((e_per, ids_l.shape[0], cap_l, d), x_l.dtype)
                return slab_l.at[rel_c, sidx, pos_l].add(x_m, mode="drop")

            return shard_map(
                local2d, mesh=mesh,
                in_specs=(P(dp, None), P(dp, None), P(dp, None, None)),
                out_specs=P(tp, dp, None, None),
                check_vma=False,
            )(ids_s, pos_c, x_rep)

        def local(ids_l, pos_l, x_l):
            # ids_l/pos_l: (ns_local, tl); x_l: (ns_local, tl, d)
            slab_l = jnp.zeros((n_experts, ids_l.shape[0], cap_l, d), x_l.dtype)
            sidx = jnp.broadcast_to(
                jnp.arange(ids_l.shape[0])[:, None], ids_l.shape
            )
            return slab_l.at[ids_l, sidx, pos_l].add(x_l, mode="drop")

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, None), P(dp, None), P(dp, None, None)),
            out_specs=P(None, dp, None, None),
            check_vma=False,
        )(ids_s, pos_c, x_rep)
    sidx = jnp.broadcast_to(jnp.arange(ns)[:, None], ids_s.shape)
    slab = jnp.zeros((n_experts, ns, cap_l, d), x_rep.dtype)
    return slab.at[ids_s, sidx, pos_c].add(x_rep, mode="drop")


def _dispatch_gather(out_slab, ids_s, pos_c):
    """Inverse of _dispatch_scatter: (E, ns, cap_l, D) -> (ns, Tl*k, D)."""
    ns = ids_s.shape[0]
    n_experts = out_slab.shape[0]
    sm = _dispatch_shard_map() if ns > 1 else None
    if sm is not None:
        shard_map, dp, tp, mesh = sm
        from jax.sharding import PartitionSpec as P

        tp_size = mesh.shape.get(tp, 1) if tp else 1
        if tp and n_experts % tp_size == 0 and tp_size > 1:
            e_per = n_experts // tp_size

            def local2d(slab_l, ids_l, pos_l):
                # Each expert shard contributes its tokens' rows; the psum
                # over 'model' is the combine — one bf16 token-activation
                # volume, the minimal cross-shard traffic of MoE.
                e0 = jax.lax.axis_index(tp) * e_per
                rel = ids_l - e0
                ok = (rel >= 0) & (rel < e_per)
                rel_c = jnp.clip(rel, 0, e_per - 1)
                sidx = jnp.broadcast_to(
                    jnp.arange(ids_l.shape[0])[:, None], ids_l.shape
                )
                y = slab_l[rel_c, sidx, pos_l]
                y = jnp.where(ok[..., None], y, 0)
                return jax.lax.psum(y, tp)

            return shard_map(
                local2d, mesh=mesh,
                in_specs=(P(tp, dp, None, None), P(dp, None), P(dp, None)),
                out_specs=P(dp, None, None),
                check_vma=False,
            )(out_slab, ids_s, pos_c)

        def local(slab_l, ids_l, pos_l):
            sidx = jnp.broadcast_to(
                jnp.arange(ids_l.shape[0])[:, None], ids_l.shape
            )
            return slab_l[ids_l, sidx, pos_l]

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(None, dp, None, None), P(dp, None), P(dp, None)),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(out_slab, ids_s, pos_c)
    sidx = jnp.broadcast_to(jnp.arange(ns)[:, None], ids_s.shape)
    return out_slab[ids_s, sidx, pos_c]


def moe_ffn(
    x: jax.Array,  # (T, D) flattened tokens
    params: dict,
    n_experts: int,
    top_k: int,
    capacity: int,
    *,
    norm_topk: bool = True,
    expert_perm: Optional[jax.Array] = None,
    n_dispatch_shards: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (T, D), aux load-balancing loss).

    ``n_dispatch_shards`` (= the data-parallel degree) splits the capacity
    slab into PER-SHARD slices: slab (E, ns, cap/ns, D) where each data
    shard scatters only into its own slice.  Without this the scatter-add
    partial-sums across data shards and GSPMD emits a full-slab all-reduce
    per layer (measured: 2x 0.97 TB/step on qwen3-moe train — 16x the
    traffic of a true dispatch, since each token belongs to exactly one
    shard).  Per-shard slices make the scatter shard-local; only the small
    expert einsum boundary moves data.  This is the paper's hierarchical
    cache-domain structure applied to dispatch: capacity domains nested
    inside expert domains.  ns=1 reproduces the flat semantics (CPU tests).

    ``expert_perm`` (E,) — optional EP-schedule expert placement: logical
    expert e's weights live at slot expert_perm[e], so co-routed experts
    are physically adjacent (same 'model' shard).
    """
    t, d = x.shape
    logits = jnp.dot(x.astype(jnp.float32), params["router"])  # (T, E) f32
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)  # (T, k)
    if norm_topk:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    aux = router_load_balancing_loss(probs, ids, n_experts)

    if expert_perm is not None:
        ids = expert_perm[ids]  # logical -> physical slot

    ns = n_dispatch_shards
    if ns < 1 or t % ns or capacity % ns or capacity // ns < top_k:
        ns = 1  # decode-sized batches: slices would be thinner than top_k
    tl = (t // ns) * top_k   # routed pairs per shard
    cap_l = capacity // ns   # per-shard capacity slice

    # Position of each routed pair within its (expert, shard): one-hot
    # cumsum along the SHARD-LOCAL pair axis — no cross-shard dependency,
    # so the cumsum never all-gathers the one-hot across 'data'.
    ids_s = ids.reshape(ns, tl)                    # (ns, Tl*k)
    onehot = jax.nn.one_hot(ids_s, n_experts, dtype=jnp.int32)  # (ns, Tl*k, E)
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1     # (ns, Tl*k)
    keep = pos < cap_l
    pos_c = jnp.minimum(pos, cap_l - 1)

    x_rep = jnp.repeat(x, top_k, axis=0).reshape(ns, tl, d)
    x_rep = jnp.where(keep[..., None], x_rep, 0)
    slab = _dispatch_scatter(ids_s, pos_c, x_rep, n_experts, cap_l)

    # Grouped expert SwiGLU (E sharded over 'model' => expert parallelism).
    # The row-parallel w_down contraction reduces in the compute dtype —
    # the TPU MXU accumulates fp32 internally either way, and a bf16
    # all-reduce halves that collective.
    gate = jnp.einsum("escd,edf->escf", slab, params["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.einsum("escd,edf->escf", slab, params["w_up"], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    out_slab = jnp.einsum("escf,efd->escd", h, params["w_down"], preferred_element_type=x.dtype)

    # Gather each routed pair's output and combine with router weights.
    y_pairs = _dispatch_gather(out_slab, ids_s, pos_c)  # (ns, Tl*k, D)
    y_pairs = jnp.where(keep[..., None], y_pairs, 0.0)
    w_flat = weights.reshape(ns, tl, 1)
    y = (y_pairs.astype(jnp.float32) * w_flat).reshape(t, top_k, d).sum(axis=1)
    y = y.astype(x.dtype)

    if "shared" in params:
        sp = params["shared"]
        g = jnp.dot(x, sp["w_gate"], preferred_element_type=jnp.float32)
        u = jnp.dot(x, sp["w_up"], preferred_element_type=jnp.float32)
        hs = (jax.nn.silu(g) * u).astype(x.dtype)
        ys = jnp.dot(hs, sp["w_down"], preferred_element_type=x.dtype)
        sg = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), sp["gate"]))  # (T,1)
        y = y + (ys.astype(jnp.float32) * sg).astype(x.dtype)

    return y, aux

"""EP-scheduled SpMV Pallas TPU kernel (paper §5.2, TPU-native).

The host-side edge partitioner (core.edge_partition) assigns every non-zero
(task) to one of k clusters; ``core.reorder.build_pack_plan`` packs each
cluster's tasks and the *unique* x/y entries it touches into padded tiles
(the cpack layout transformation of paper §4.1 — the ``opt_arrayA``
rewrite).  Each cluster plays the role of one GPU thread block:

* **software-cache variant** (paper: shared memory / ``__shared__``):
  the cluster's packed x tile is staged into VMEM *once*; every task reads
  x through a cheap VMEM-local index.  Off-chip traffic per cluster = its
  unique x entries + unique y entries, so total HBM traffic =
  ``n_touched + C`` — the partition objective *is* the traffic count.

* **streaming variant** (paper: texture cache / ``tex1Dfetch``):
  no staging; every task loads its x entry straight from the full vector.
  That per-task load is an XLA gather ahead of the kernel (the un-staged
  fetch), and the kernel does only the in-tile scatter it shares with the
  software variant.  Same programmability/perf trade-off the paper studies
  in Fig. 12.

Both kernels emit per-cluster *partial* y tiles; the ops.py wrapper
scatter-adds them into the global y (cut output rows are combined there —
the analogue of the paper's per-block write-back; y is write-shared, which
is exactly why the paper cannot keep it in texture cache).

Lowering.  A grid cell holds ``SUBLANES`` (8) clusters as the rows of 2-D
tiles — ``(8, E)`` tasks, ``(8, X)`` staged x, ``(8, Y)`` partial y — and
walks its tasks in chunks of ``LANES`` (128).  The TPU has no 1-D gather or
scatter inside a kernel, so both are one-hot selections on the VPU: a
chunk's local x slots are compared against an iota over the x tile and the
selected entries summed (exactly one term is non-zero, so the gather is
exact); the products are scattered into the y tile the same way, summed
over the chunk.  Tiles wider than ``SLAB`` lanes are compared a slab at a
time, which bounds each ``(8, 128, SLAB)`` temporary to 4 MiB of VMEM.  The
wrapper pads k to a multiple of 8 with empty clusters (zero values, nothing
read back) and the tile widths to multiples of 128, so every pack ``pad`` —
the tile knob swept by benchmarks/table3_block_size.py — gives legal tiles.
The same kernel body runs interpreted on the CPU (``backend.pallas_interpret``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import backend as _backend

__all__ = ["spmv_software_cache", "spmv_streaming", "spmv_streaming_batched"]

SUBLANES = 8  # clusters per grid cell
LANES = 128  # tasks per chunk; tile widths are padded to this multiple
SLAB = 1024  # widest x/y span compared against one chunk at a time


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _pad_to(a: jax.Array, rows: int, cols: int) -> jax.Array:
    r, c = a.shape
    if (r, c) == (rows, cols):
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def _gather_chunk(xt_ref, xl: jax.Array) -> jax.Array:
    """``xt[p, xl[p, c]]`` for a (8, X) x tile and (8, C) local slots."""
    nb, c = xl.shape
    x_dim = xt_ref.shape[1]
    out = jnp.zeros((nb, c), xt_ref.dtype)
    for s in range(0, x_dim, SLAB):
        w = min(SLAB, x_dim - s)
        # Slice the ref, then add the chunk axis: indexing a value with a
        # slice and None together becomes a gather, which Mosaic refuses.
        xs = xt_ref[:, s:s + w][:, None, :]
        hit = xl[:, :, None] == jax.lax.broadcasted_iota(jnp.int32, (nb, c, w), 2) + s
        out = out + jnp.sum(jnp.where(hit, xs, 0), axis=2)
    return out


def _scatter_chunk(out_ref, yl: jax.Array, contrib: jax.Array) -> None:
    """``out[p, yl[p, c]] += contrib[p, c]`` over one (8, C) chunk."""
    nb, c = yl.shape
    y_dim = out_ref.shape[1]
    for s in range(0, y_dim, SLAB):
        w = min(SLAB, y_dim - s)
        hit = yl[:, :, None] == jax.lax.broadcasted_iota(jnp.int32, (nb, c, w), 2) + s
        out_ref[:, s:s + w] += jnp.sum(jnp.where(hit, contrib[:, :, None], 0), axis=1)


def _for_each_chunk(n_tasks: int, body) -> None:
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * LANES, LANES), LANES))
        return carry

    jax.lax.fori_loop(0, n_tasks // LANES, step, 0)


def _smem_kernel(vals_ref, xl_ref, yl_ref, xt_ref, out_ref):
    """Software cache: gather from the staged x tile, scatter into y."""
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def chunk(t):
        xv = _gather_chunk(xt_ref, xl_ref[:, t])
        _scatter_chunk(out_ref, yl_ref[:, t], vals_ref[:, t] * xv)

    _for_each_chunk(vals_ref.shape[1], chunk)


def _stream_kernel(vals_ref, xv_ref, yl_ref, out_ref):
    """Streaming: per-task x values arrive loaded; scatter into y."""
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def chunk(t):
        _scatter_chunk(out_ref, yl_ref[:, t], vals_ref[:, t] * xv_ref[:, t])

    _for_each_chunk(vals_ref.shape[1], chunk)


def _launch(kernel, task_ops, x_tile, y_max: int) -> jax.Array:
    """Run ``kernel`` over (k, E) task operands [+ a (k, X) x tile]."""
    k, e = task_ops[0].shape
    kp, ep, yp = _round_up(k, SUBLANES), _round_up(e, LANES), _round_up(y_max, LANES)
    ops = [_pad_to(a, kp, ep) for a in task_ops]
    specs = [pl.BlockSpec((SUBLANES, ep), lambda p: (p, 0))] * len(ops)
    if x_tile is not None:
        xp = _round_up(x_tile.shape[1], LANES)
        ops.append(_pad_to(x_tile, kp, xp))
        specs.append(pl.BlockSpec((SUBLANES, xp), lambda p: (p, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(kp // SUBLANES,),
        in_specs=specs,
        out_specs=pl.BlockSpec((SUBLANES, yp), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, yp), task_ops[0].dtype),
        interpret=_backend.pallas_interpret(),
    )(*ops)
    return out[:k, :y_max]


def spmv_software_cache(
    vals: jax.Array,      # (k, E_max) packed non-zeros (0 in padding slots)
    x_lidx: jax.Array,    # (k, E_max) int32 local x slot per task
    y_lidx: jax.Array,    # (k, E_max) int32 local y slot per task
    x_packed: jax.Array,  # (k, X_max) packed unique x entries per cluster
    y_max: int,
) -> jax.Array:
    """Per-cluster partial y tiles, shape (k, y_max)."""
    return _launch(_smem_kernel, (vals, x_lidx, y_lidx), x_packed, y_max)


def spmv_streaming(
    vals: jax.Array,         # (k, E_max)
    x_gidx_task: jax.Array,  # (k, E_max) int32 GLOBAL x index per task
    y_lidx: jax.Array,       # (k, E_max)
    x: jax.Array,            # (n_cols,) full input vector, NOT staged
    y_max: int,
) -> jax.Array:
    """Per-cluster partial y tiles, shape (k, y_max)."""
    xv = jnp.take(x, x_gidx_task, axis=0)  # one un-staged load per task
    return _launch(_stream_kernel, (vals, xv, y_lidx), None, y_max)


def spmv_streaming_batched(
    vals: jax.Array,         # (B, k, E_max) packed non-zeros, 0 in padding
    x_gidx_task: jax.Array,  # (B, k, E_max) int32 GLOBAL x index per task
    y_lidx: jax.Array,       # (B, k, E_max)
    x: jax.Array,            # (B, n_cols) one full input vector per request
    y_max: int,
) -> jax.Array:
    """Micro-batched streaming variant: B same-bucket requests, one launch.

    The B·k clusters of a whole micro-batch of same-shape requests share a
    single compiled kernel (the bucketed-compilation serve path).  Padding
    slots carry ``vals == 0`` so they contribute nothing; unused batch slots
    are all-zero rows.  Returns per-(request, cluster) partial y tiles,
    shape (B, k, y_max).
    """
    b, k, e_max = vals.shape
    n_cols = x.shape[1]
    # Request i's x occupies [i * n_cols, (i + 1) * n_cols) of the flat x.
    xg = x_gidx_task + (jnp.arange(b, dtype=x_gidx_task.dtype) * n_cols)[:, None, None]
    flat = (a.reshape(b * k, e_max) for a in (vals, xg, y_lidx))
    return spmv_streaming(*flat, x.reshape(-1), y_max).reshape(b, k, y_max)

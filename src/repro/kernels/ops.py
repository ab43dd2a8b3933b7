"""Jit'd public wrappers around the Pallas kernels.

``ep_spmv`` is the end-to-end EP-scheduled SpMV: it closes over a host-side
``PackPlan`` (static) and runs pack → kernel → combine:

  1. *pack*    — gather ``x`` into per-cluster contiguous tiles (the cpack
                 ``opt_arrayA`` rewrite; this gather's size is exactly
                 ``n_touched + C(x)``, the model's traffic count);
  2. *kernel*  — per-cluster partial products in VMEM;
  3. *combine* — scatter-add partial y tiles into the global y (cut rows
                 are summed here).

``mode="software"`` stages x tiles in VMEM (shared-memory analogue);
``mode="streaming"`` loads each task's x entry from the full x (texture
analogue, skips step 1's relayout).

Two compilation contracts coexist:

* **Per-plan** (``make_ep_spmv_fn``) — the plan's own tiles, uploaded once
  when bound; one compile per bound plan.  Right for a few long-lived
  matrices, fatal for thousands of small ones.
* **Bucketed** (``BucketSpec`` + ``pad_plan_operands`` +
  ``make_bucketed_spmv_fn``) — the plan arrays are *arguments* of a kernel
  compiled once per shape bucket, so every request whose plan fits the
  bucket's padded ceilings reuses the same executable, micro-batched
  ``spec.batch`` requests at a time.  Tail slots are zero-filled
  (``vals == 0`` contributes nothing) and out-of-range rows land on the
  bucket's sentinel row, de-padded by the caller.

This module takes only host-side ``PackPlan``s (+ the padding spec);
scheduler handles (ServicePlan / PlanTicket) are resolved by the request
layer (``repro.runtime.request``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from ..core import tracing
from ..core.reorder import PackPlan
from . import ep_spmv as _spmv
from . import moe_mlp as _moe

__all__ = [
    "BucketSpec",
    "ep_spmv",
    "make_bucketed_spmv_fn",
    "make_ep_spmv_fn",
    "moe_mlp",
    "pad_plan_operands",
    "spmv_hbm_traffic_model",
]


# ---------------------------------------------------------------------------
# Bucketed compilation: padded-shape contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Padded-shape contract of one compiled bucket kernel.

    Every request served through a bucket arrives widened to these
    rectangular ceilings: the plan tiles to ``(k, e_max/x_max/y_max)``, the
    input vector to ``n_cols`` slots, the output to ``n_rows`` rows, and
    the micro-batch to exactly ``batch`` requests (unused slots are
    all-zero and provably contribute nothing).  Two requests with the same
    spec share one compiled executable — the spec IS the compile-cache key.
    """

    k: int
    n_rows: int  # row ceiling: y is produced at this length, de-padded by the caller
    n_cols: int  # column ceiling: x must arrive zero-padded to this length
    e_max: int
    x_max: int
    y_max: int
    batch: int  # fixed micro-batch width; short batches are zero-padded
    mode: str = "software"

    def __post_init__(self) -> None:
        if self.mode not in ("software", "streaming"):
            raise ValueError(f"unknown mode {self.mode!r}")

    def fits(self, plan: PackPlan) -> bool:
        """True when ``plan``'s padded tiles fit inside this bucket."""
        return (
            plan.k == self.k
            and plan.n_rows <= self.n_rows
            and plan.n_cols <= self.n_cols
            and plan.e_max <= self.e_max
            and plan.x_max <= self.x_max
            and plan.y_max <= self.y_max
        )

    def operand_elems(self) -> int:
        """Total padded operand elements of one launch — the compile-cache
        size coordinate for (size, recency) eviction."""
        return self.batch * (
            self.k * (3 * self.e_max + self.x_max + self.y_max) + self.n_cols
        )


def pad_plan_operands(
    plan: PackPlan, vals: np.ndarray, spec: BucketSpec
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Widen one plan + its matrix values into ``spec``'s rectangular tiles.

    Returns host-side ``(vals_packed, x_lidx, y_lidx, x_gidx, y_gidx)`` of
    shapes ``(k, E)``/``(k, E)``/``(k, E)``/``(k, X)``/``(k, Y)``.  The tail
    contract that makes one compiled kernel safe for every plan in the
    bucket:

    * task tail slots carry ``vals == 0`` with local indices 0, so they add
      exactly ``0.0`` to slot 0 of their tiles;
    * ``x_gidx`` tail slots gather ``x[0]`` into x-tile slots no task reads;
    * ``y_gidx`` tail slots — and the plan's own ``n_rows`` sentinel —
      are remapped to the *bucket* sentinel ``spec.n_rows``, the row the
      caller slices off, so zero-sum padding scatters never touch a real
      row of a smaller matrix.
    """
    if not spec.fits(plan):
        raise ValueError(
            f"plan (k={plan.k}, rows={plan.n_rows}, cols={plan.n_cols}, "
            f"tiles=({plan.e_max},{plan.x_max},{plan.y_max})) does not fit "
            f"bucket {spec}"
        )
    vals = np.asarray(vals)
    vp = np.zeros((spec.k, spec.e_max), dtype=vals.dtype)
    vp[:, : plan.e_max] = plan.pack_values(vals)
    xl = np.zeros((spec.k, spec.e_max), dtype=np.int32)
    xl[:, : plan.e_max] = plan.x_lidx
    yl = np.zeros((spec.k, spec.e_max), dtype=np.int32)
    yl[:, : plan.e_max] = plan.y_lidx
    xg = np.zeros((spec.k, spec.x_max), dtype=np.int32)
    xg[:, : plan.x_max] = plan.x_gidx
    yg = np.full((spec.k, spec.y_max), spec.n_rows, dtype=np.int32)
    yg[:, : plan.y_max] = np.where(plan.y_gidx == plan.n_rows, spec.n_rows, plan.y_gidx)
    return vp, xl, yl, xg, yg


def _spmv_batch(spec: BucketSpec, vals, x_lidx, y_lidx, x_gidx, y_gidx, x):
    """pack -> kernel -> combine for ``spec.batch`` batch-leading requests."""
    b, k = spec.batch, spec.k
    e_max, x_max, y_max = spec.e_max, spec.x_max, spec.y_max
    n_rows = spec.n_rows
    if spec.mode == "software":
        # pack: each request gathers its unique x entries (n_touched + C loads)
        x_packed = jax.vmap(lambda xg, xv: jnp.take(xv, xg, axis=0))(x_gidx, x)
        partials = _spmv.spmv_software_cache(
            vals.reshape(b * k, e_max),
            x_lidx.reshape(b * k, e_max),
            y_lidx.reshape(b * k, e_max),
            x_packed.reshape(b * k, x_max),
            y_max,
        ).reshape(b, k, y_max)
    else:
        # Global x index per task = x_gidx[b, p, x_lidx[b, p, e]].
        xg_task = jnp.take_along_axis(x_gidx, x_lidx, axis=2)
        partials = _spmv.spmv_streaming_batched(vals, xg_task, y_lidx, x, y_max)
    # combine: one flat scatter-add over the whole batch; request b's rows
    # live at offset b * (n_rows + 1) and the sentinel row is sliced off.
    offs = (jnp.arange(b, dtype=y_gidx.dtype) * (n_rows + 1))[:, None, None]
    y = jnp.zeros(b * (n_rows + 1), dtype=partials.dtype)
    y = y.at[(y_gidx + offs).reshape(-1)].add(partials.reshape(-1))
    return y.reshape(b, n_rows + 1)[:, :n_rows]


def make_bucketed_spmv_fn(spec: BucketSpec):
    """Compile-once kernel for a shape bucket: ``(plan arrays, x) -> y``.

    Unlike :func:`make_ep_spmv_fn`, nothing about the matrix is baked into
    the trace — the packed values and indices are *arguments*, so one
    compiled executable serves every (plan, values, x) whose shapes were
    widened to ``spec`` by :func:`pad_plan_operands`.  The returned jit'd
    function maps batch-leading operands

        ``vals (B,k,E) · x_lidx (B,k,E) · y_lidx (B,k,E) ·
        x_gidx (B,k,X) · y_gidx (B,k,Y) · x (B, n_cols)``

    to ``y (B, n_rows)`` — ``B == spec.batch`` always; callers zero-pad
    short micro-batches and de-pad each row to its request's true
    ``n_rows`` on the way out.
    """
    def ep_spmv_batch(vals, x_lidx, y_lidx, x_gidx, y_gidx, x):
        return _spmv_batch(spec, vals, x_lidx, y_lidx, x_gidx, y_gidx, x)

    return jax.jit(ep_spmv_batch)


# ---------------------------------------------------------------------------
# Per-plan compilation
# ---------------------------------------------------------------------------


def make_ep_spmv_fn(
    plan: PackPlan,
    vals: np.ndarray,
    mode: Literal["software", "streaming"] = "software",
):
    """Bind a PackPlan + matrix values; return jit'd ``x -> y``.

    ``plan`` is a host-side ``PackPlan``; service-supplied handles
    (ServicePlan / PlanTicket) are resolved by the request layer
    (``repro.runtime.request.resolve_plan`` / ``GraphServer``), which owns
    tenants, timeouts, and the compile cache.

    The packed values and indices go to the device once, here (they change
    only when the matrix/partition changes — per paper §4 the relayout
    happens once, asynchronously); the returned function is the
    steady-state kernel the accelerator runs every iteration.  For many
    small matrices, prefer the bucketed contract
    (:func:`make_bucketed_spmv_fn`): this per-plan form compiles once per
    bound plan.
    """
    # The plan's own tiles as a batch-of-1 bucket: the same kernel and
    # combine as the bucketed path, with the packed operands uploaded once
    # and passed as arguments (never folded into the program as constants).
    # Each bound plan gets its own jit, so its executable lives and dies
    # with the returned function (and with a compile-cache entry holding it).
    spec = BucketSpec(k=plan.k, n_rows=plan.n_rows, n_cols=plan.n_cols,
                      e_max=plan.e_max, x_max=plan.x_max, y_max=plan.y_max,
                      batch=1, mode=mode)
    host = pad_plan_operands(plan, np.asarray(vals), spec)
    tracing.count("serve.h2d_bytes", sum(a.nbytes for a in host))
    operands = [jnp.asarray(a) for a in host]

    def ep_spmv_plan(vals, x_lidx, y_lidx, x_gidx, y_gidx, x):
        """One plan's ``(k, ·)`` operands and ``x (n_cols,)`` -> ``y (n_rows,)``."""
        ops = (a[None] for a in (vals, x_lidx, y_lidx, x_gidx, y_gidx, x))
        return _spmv_batch(spec, *ops)[0]

    return functools.partial(jax.jit(ep_spmv_plan), *operands)


def ep_spmv(
    x: jax.Array,
    plan: PackPlan,
    vals: np.ndarray,
    mode: Literal["software", "streaming"] = "software",
) -> jax.Array:
    """One-shot convenience wrapper (rebinds the plan every call)."""
    return make_ep_spmv_fn(plan, vals, mode)(x)


def spmv_hbm_traffic_model(plan: PackPlan, mode: str = "software") -> dict:
    """Modeled off-chip loads (paper Fig. 11's transaction count).

    software: unique x + unique y entries per cluster (C is the redundancy);
    streaming: every task load goes through the implicit cache — best case
    equals software, worst case one load per task (cache thrashing).
    """
    unique_loads = int(plan.x_count.sum() + plan.y_count.sum())
    task_loads = int(plan.e_count.sum() * 2)
    return {
        "mode": mode,
        "unique_loads": unique_loads,
        "worst_case_loads": unique_loads if mode == "software" else task_loads,
    }


moe_mlp = functools.partial(_moe.moe_mlp)

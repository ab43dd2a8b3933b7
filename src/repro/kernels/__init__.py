"""Pallas TPU kernels for the paper's compute hot-spots.

The paper's optimized kernel is SpMV with cluster-local caching (§5.2);
``ep_spmv`` is its TPU-native form.  ``moe_mlp`` is the grouped expert FFN
fed by EP-scheduled MoE dispatch (the technique's application to the
assigned MoE architectures).  Pure-jnp oracles live in ``ref.py``.  The
backend decides how a kernel runs (``backend.py``): interpreted on the CPU,
where the tests run, and lowered through Mosaic on a TPU.

The model zoo stays pure JAX: Mosaic custom calls contribute no FLOPs to
``cost_analysis()``, so kernels are an opt-in fast path, not a lowering
dependency.
"""
from .ep_spmv import spmv_software_cache, spmv_streaming, spmv_streaming_batched
from .flash_attention import flash_attention
from .ops import (
    BucketSpec,
    ep_spmv,
    make_bucketed_spmv_fn,
    make_ep_spmv_fn,
    moe_mlp,
    pad_plan_operands,
    spmv_hbm_traffic_model,
)

__all__ = [
    "BucketSpec",
    "ep_spmv",
    "flash_attention",
    "make_bucketed_spmv_fn",
    "make_ep_spmv_fn",
    "moe_mlp",
    "pad_plan_operands",
    "spmv_hbm_traffic_model",
    "spmv_software_cache",
    "spmv_streaming",
    "spmv_streaming_batched",
]

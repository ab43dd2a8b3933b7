"""Serving steps: batched prefill, one-token decode, and EP-SpMV requests.

``make_prefill_step`` / ``make_decode_step`` return the exact functions the
dry-run lowers for the prefill_32k / decode_32k / long_500k shapes — decode
is ONE new token against a cache of ``max_len`` (spec: ``decode_*`` lowers
``serve_step``, not ``train_step``).

The EP-SpMV request path moved to ``repro.runtime.request``: a typed
``GraphRequest`` -> ``ServeResult`` surface on a ``GraphServer`` that owns
the bucketed compile cache and the micro-batcher.  ``make_graph_serve_fn``
survives here only as a deprecated shim over it (same positional-tuple call
shape, same ``(y, info_dict)`` return).

Greedy sampling inline (argmax) keeps the served token path on-device; a
real frontend would swap in temperature sampling without touching the
lowered graph shape.
"""
from __future__ import annotations

import warnings
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["make_prefill_step", "make_decode_step", "make_graph_serve_fn"]


def make_prefill_step(model, max_len: int):
    def prefill_step(params: Any, batch: dict):
        logits, cache = model.prefill(params, batch, max_len)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, cache

    return prefill_step


def make_decode_step(model):
    def decode_step(params: Any, cache: dict, tokens: jax.Array, pos: jax.Array):
        """tokens: (B, 1) int32; pos: scalar int32 write position."""
        logits, cache = model.decode_step(params, cache, {"tokens": tokens}, pos)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_token, cache

    return decode_step


def make_graph_serve_fn(
    service,
    k: int,
    pad: int = 128,
    mode: str = "software",
    tenant: str = "default",
    priority: int = 0,
):
    """Deprecated shim: the positional-tuple serve handler, now a thin
    wrapper over :class:`repro.runtime.request.GraphServer`.

    Returns the old call shape — ``serve(n_rows, n_cols, rows, cols, vals,
    x, tenant=, priority=) -> (y, info_dict)`` — and still honors the
    legacy ``serve.tenant`` / ``serve.priority`` function attributes.  New
    code should construct a ``GraphServer`` and pass ``GraphRequest``s: it
    exposes the typed ``ServeResult``, the bucketed compile cache with
    ``stats()``, and the micro-batched ``submit`` lane, none of which this
    shim surfaces.  The returned handler's compile cache lives on an
    internal ``GraphServer`` (no batcher thread; every call is the
    synchronous lane).
    """
    warnings.warn(
        "make_graph_serve_fn is deprecated; use "
        "repro.runtime.request.GraphServer with GraphRequest",
        DeprecationWarning,
        stacklevel=2,
    )
    from .request import GraphRequest, GraphServer  # lazy: avoid import cycle

    server = GraphServer(
        service,
        k,
        pad=pad,
        mode=mode,
        tenant=tenant,
        priority=priority,
        start_batcher=False,
    )

    def serve(n_rows, n_cols, rows, cols, vals, x,
              tenant: str | None = None, priority: int | None = None):
        result = server.serve(
            GraphRequest(
                n_rows=n_rows, n_cols=n_cols, rows=rows, cols=cols,
                vals=vals, x=x,
                tenant=tenant if tenant is not None else serve.tenant,
                priority=priority if priority is not None else serve.priority,
            )
        )
        info = result.info.as_dict()
        return result.y, info

    serve.tenant = tenant
    serve.priority = priority
    serve.server = server  # escape hatch for stats()/close() on the shim
    return serve

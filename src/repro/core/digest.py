"""Content digests as sha256 trees over fixed leaves, hashed on the host's cores.

An array's bytes are cut into fixed 1 MiB leaves, each hashed on its own as
sha256 under a leaf prefix, and the digest is sha256 over a root prefix,
``repr(meta)`` and the leaf digests in order.  Digests of more than two
leaves hash them on one process-wide pool of a thread per usable core (at
most :data:`MAX_HASH_THREADS`; hashlib releases the GIL for buffers over
2 KiB); smaller ones hash on the calling thread.  The value depends on the
content alone, never on where or in what order the leaves hash.

Two keys are trees: the SpMV plan key (``partition_service.spmv_plan_key``)
and a request's value digest (``runtime.request.GraphRequest.vals_digest``).
Each has prefixes of its own, so equal bytes never give equal keys across
them.
"""
from __future__ import annotations

import hashlib
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

__all__ = ["INLINE_LEAVES", "LEAF_BYTES", "MAX_HASH_THREADS", "TREE_TAG", "sha256_tree"]

LEAF_BYTES = 1 << 20
TREE_TAG = "sha256-tree"
# At most this many leaves are hashed on the calling thread.
INLINE_LEAVES = 2
# Thread cap: over 45.8 MB on a 13-core TPU v5e host, 1 thread took 33.4 ms,
# 8 took 5.18, 13 took 4.07 and 16 took 3.87; 16 is the fewest within 5%.
MAX_HASH_THREADS = 16

_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _leaf_pool() -> ThreadPoolExecutor:
    """The process's leaf-hashing pool, made on first use: one thread per
    usable core, at most :data:`MAX_HASH_THREADS`."""
    global _pool
    with _pool_lock:
        if _pool is None:
            try:
                cores = len(os.sched_getaffinity(0))
            except AttributeError:
                cores = os.cpu_count() or 1
            _pool = ThreadPoolExecutor(
                max_workers=min(cores, MAX_HASH_THREADS),
                thread_name_prefix="sha256-tree")
        return _pool


def _forget_leaf_pool() -> None:
    # A forked child has none of the parent's threads: it makes its own.
    global _pool, _pool_lock
    _pool = None
    _pool_lock = threading.Lock()


os.register_at_fork(after_in_child=_forget_leaf_pool)


def sha256_tree(leaf_prefix: bytes, root_prefix: bytes, meta: tuple,
                *arrays: np.ndarray) -> tuple[str, bool]:
    """The tree's hex digest over each contiguous array's bytes, hashed in
    place, and whether its leaves hashed on the pool.  Never called from one
    of the pool's own threads, which would wait on the pool it occupies."""
    leaves = []
    for a in arrays:
        buf = memoryview(a).cast("B")
        leaves += [buf[i:i + LEAF_BYTES] for i in range(0, len(buf), LEAF_BYTES)]
    base = hashlib.sha256(leaf_prefix)

    def leaf_digest(leaf: memoryview) -> bytes:
        h = base.copy()
        h.update(leaf)
        return h.digest()

    pooled = len(leaves) > INLINE_LEAVES
    digests = _leaf_pool().map(leaf_digest, leaves) if pooled else map(leaf_digest, leaves)
    h = hashlib.sha256(root_prefix)
    h.update(repr(meta).encode())
    for d in digests:
        h.update(d)
    return h.hexdigest(), pooled

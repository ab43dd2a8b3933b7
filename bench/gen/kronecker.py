"""The Graph500 Kronecker graph and its personalized-PageRank matrix.

Graph500 (graph500.org, the specification's reference generator): each of
``edgefactor * 2**scale`` edges picks, one bit per level of ``scale``, a
quadrant of the adjacency matrix with the initiator's probabilities
``A, B, C`` (and ``D = 1 - A - B - C``); then the vertex labels are randomly
permuted.  The graph is then built as the GAP Benchmark Suite builds its
``kron`` input (Beamer, Asanović, Patterson, arXiv:1508.03619): made
symmetric, with duplicate edges and self-loops removed.  Vertices no edge
touches stay in the graph, isolated.

Personalized PageRank with damping ``α`` solves ``(I − α W D⁻¹) π = (1 − α) s``
for the adjacency ``W``, the degrees ``D`` and a personalization
distribution ``s``.  Its symmetric form (Gleich, "PageRank beyond the Web",
SIAM Review 2015) is

    (I − α D^{-1/2} W D^{-1/2}) y = (1 − α) D^{-1/2} s,    π = D^{1/2} y,

whose matrix has every eigenvalue in ``[1 − α, 1 + α]``: symmetric positive
definite, so conjugate gradients solve it.  An isolated vertex keeps the row
``a_ii = 1`` and is never a source, so its ``y`` and ``π`` are 0.
"""
from __future__ import annotations

import numpy as np


def edges(scale: int, edgefactor: int, initiator, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` of the ``edgefactor * 2**scale`` edges Graph500's
    generator draws, labels permuted, as int64; self-loops and duplicates
    are still in."""
    a, b, c = (float(v) for v in initiator)
    n, m = 1 << scale, edgefactor << scale
    rng = np.random.default_rng(seed)
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        src_bit = rng.random(m) > ab
        dst_bit = rng.random(m) > np.where(src_bit, c_norm, a_norm)
        src += src_bit.astype(np.int64) << level
        dst += dst_bit.astype(np.int64) << level
    perm = rng.permutation(n)
    return perm[src], perm[dst]


def adjacency(n: int, src: np.ndarray, dst: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(rows, cols)`` of the symmetric adjacency without self-loops or
    duplicates, sorted by row then column."""
    keep = src != dst
    u = np.concatenate([src[keep], dst[keep]])
    v = np.concatenate([dst[keep], src[keep]])
    key = np.unique(u * n + v)
    return key // n, key % n


def ppr_matrix(n: int, rows: np.ndarray, cols: np.ndarray, damping: float):
    """``(rows, cols, vals, degree)`` of ``I − α D^{-1/2} W D^{-1/2}`` for the
    adjacency ``(rows, cols)``: every diagonal entry is 1, an edge ``(i, j)``
    is ``−α / sqrt(d_i d_j)``; sorted by row then column, values float32."""
    degree = np.bincount(rows, minlength=n)
    off = -damping / np.sqrt(degree[rows].astype(np.float64) * degree[cols])
    diag = np.arange(n, dtype=np.int64)
    r = np.concatenate([rows, diag])
    c = np.concatenate([cols, diag])
    v = np.concatenate([off, np.ones(n)])
    order = np.lexsort((c, r))
    return r[order], c[order], v[order].astype(np.float32), degree


def graph500_ppr(cfg: dict):
    """``(n, rows, cols, vals, degree)`` of the configuration's PPR matrix:
    ``scale``, ``edgefactor``, ``initiator``, ``damping`` and ``graph_seed``."""
    n = 1 << int(cfg["scale"])
    src, dst = edges(int(cfg["scale"]), int(cfg["edgefactor"]), cfg["initiator"],
                     int(cfg["graph_seed"]))
    rows, cols = adjacency(n, src, dst)
    return (n, *ppr_matrix(n, rows, cols, float(cfg["damping"])))


def personalization(degree: np.ndarray, sources: int, rng: np.random.Generator) -> np.ndarray:
    """``s``: uniform over ``sources`` distinct non-isolated vertices drawn
    from ``rng`` (float64, sums to 1)."""
    pick = rng.choice(np.flatnonzero(degree > 0), size=sources, replace=False)
    s = np.zeros(degree.size)
    s[pick] = 1.0 / sources
    return s


def ppr_rhs(degree: np.ndarray, s: np.ndarray, damping: float) -> np.ndarray:
    """``b = (1 − α) D^{-1/2} s`` (float64; 0 where the degree is 0)."""
    inv_sqrt = np.where(degree > 0, 1.0 / np.sqrt(np.maximum(degree, 1)), 0.0)
    return (1.0 - damping) * inv_sqrt * s

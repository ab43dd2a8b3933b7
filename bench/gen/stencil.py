"""HPCG's 27-point stencil problem as COO (copy of ``core.graph.stencil_27pt_coo``,
widened to a box grid, with HPCG's matrix values).

HPCG (hpcg-benchmark.org; SAND2013-8752) discretises a 3-D heat diffusion
problem on an ``nx × ny × nz`` local grid: row ``(ix, iy, iz)`` couples to
every grid point within one step along each axis, itself included — 27
non-zeros per interior row — with 26 on the diagonal and -1 off it, so the
matrix is symmetric positive definite.
"""
from __future__ import annotations

import numpy as np

DIAGONAL = 26.0
OFF_DIAGONAL = -1.0


def coo(nx: int, ny: int, nz: int) -> tuple[int, np.ndarray, np.ndarray]:
    """``(n, rows, cols)`` of the stencil, sorted by row then column; row
    id ``ix + nx*(iy + ny*iz)``."""
    iz, iy, ix = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij")
    ix, iy, iz = ix.ravel(), iy.ravel(), iz.ravel()
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                jx, jy, jz = ix + dx, iy + dy, iz + dz
                ok = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
                      & (jz >= 0) & (jz < nz))
                rows.append(np.flatnonzero(ok))
                cols.append((jx + nx * (jy + ny * jz))[ok])
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    order = np.lexsort((cols, rows))
    return nx * ny * nz, rows[order], cols[order]


def hpcg_values(rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """HPCG's values: 26 on the diagonal, -1 off it (float32, exact)."""
    return np.where(rows == cols, DIAGONAL, OFF_DIAGONAL).astype(np.float32)


def nnz(nx: int, ny: int, nz: int) -> int:
    """Closed form of the stencil's non-zero count: ``Π (3·n_axis - 2)``."""
    return (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)

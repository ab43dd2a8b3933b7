"""The plain reference: a float64 COO SpMV in numpy, and the comparison that
decides a run's ``correct``.

It imports nothing of the program and takes nothing the program made: the
rows, columns, values and ``x`` are the request's own, as the benchmark
generated them.

The number compared is the worst componentwise error of a served ``y``:

    max_i |y_i - ref_i| / sum_j |a_ij| |x_j|

the error in units of the row's own magnitude, which float32 arithmetic
bounds by a small multiple of 2**-24 times the row length, whatever the
cancellation inside the row.  A row whose magnitude is 0 reads the absolute
error; a ``y`` of the wrong shape, or not finite, reads the largest float
(the result line is JSON, which has no infinity).

``control_spmv`` is the reference in the next precision below the one the
configurations state (float32): values and ``x`` rounded to bfloat16, the
products and sums in float32, as a bfloat16 matrix unit with a float32
accumulator would compute them.  It must read far above the limit.
"""
from __future__ import annotations

import sys

import ml_dtypes
import numpy as np

WRONG = sys.float_info.max  # the reading of an answer that is not a number


def spmv(n_rows: int, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
         x: np.ndarray) -> np.ndarray:
    """``y = A @ x`` in float64 for the COO matrix ``(rows, cols, vals)``."""
    terms = np.asarray(vals, np.float64) * np.asarray(x, np.float64)[cols]
    return np.bincount(rows, weights=terms, minlength=n_rows)


def magnitude(n_rows: int, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              x: np.ndarray) -> np.ndarray:
    """``sum_j |a_ij| |x_j|`` per row, in float64."""
    terms = np.abs(np.asarray(vals, np.float64)) * np.abs(np.asarray(x, np.float64))[cols]
    return np.bincount(rows, weights=terms, minlength=n_rows)


def rel_err(y, n_rows: int, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
            x: np.ndarray) -> float:
    """Worst componentwise error of ``y`` against the float64 reference."""
    y = np.asarray(y, np.float64)
    if y.shape != (n_rows,):
        return WRONG
    ref = spmv(n_rows, rows, cols, vals, x)
    scale = magnitude(n_rows, rows, cols, vals, x)
    err = np.abs(y - ref) / np.where(scale > 0, scale, 1.0)
    worst = float(err.max()) if err.size else 0.0
    return worst if np.isfinite(worst) else WRONG


def control_spmv(n_rows: int, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 x: np.ndarray) -> np.ndarray:
    """The reference one precision down: bfloat16 operands, float32 sums."""
    v = np.asarray(vals, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
    xb = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
    terms = v * xb[cols]
    y = np.zeros(n_rows, np.float32)
    np.add.at(y, rows, terms)
    return y

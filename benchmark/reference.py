"""The plain reference: MPI_SUM over ranks as a numpy fold in float64,
and the comparison that decides ``correct``.

It imports nothing of the library under test.  The number compared is
``max_err_eps``: the worst ``|result - fold|`` over every rank's result
and every element, in float32 epsilons of ``sum over ranks of |x|``.
A float32 sum over n ranks in any order lies within about ``(n - 1)``
such epsilons; a sum made in bfloat16 lies thousands away.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
#: elements per block, so the float64 temporaries stay small
BLOCK = 1 << 22
#: the reading of a result with the wrong shape or a non-finite value
WRONG = 1e300


def fold_sum(x: np.ndarray) -> np.ndarray:
    """Rank-ordered float64 sum of the rank-major ``x`` (n, count)."""
    acc = x[0].astype(np.float64)
    for r in range(1, x.shape[0]):
        acc += x[r]
    return acc


def _block_err(x: np.ndarray, out: np.ndarray) -> float:
    ref = fold_sum(x)
    scale = EPS32 * np.abs(x).sum(axis=0, dtype=np.float64)
    scale[scale == 0] = EPS32
    return float((np.abs(out - ref[None, :]).max(axis=0) / scale).max())


def max_err_eps(x: np.ndarray, out: np.ndarray) -> float:
    """Worst error of every rank's result ``out`` (n, count) against the
    fold of the input ``x`` (n, count), in float32 epsilons of the sum
    of magnitudes.  A result of the wrong shape or a non-finite value
    reads ``WRONG``.  Blocks of elements run on a few threads (numpy
    releases the interpreter lock)."""
    if out.shape != x.shape or not np.all(np.isfinite(out)):
        return WRONG
    starts = range(0, x.shape[1], BLOCK)
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        errs = pool.map(lambda lo: _block_err(x[:, lo:lo + BLOCK],
                                              out[:, lo:lo + BLOCK]), starts)
        return max(errs, default=0.0)

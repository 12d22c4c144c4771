"""The plain reference of ``C = A @ B``, on a seeded sample of rows.

Built from the COO arrays the benchmark generated, never from a packed
layout or anything else the program made.  Float64 throughout (a copy of
``chip_smoke.py``'s ``Reference``).  The number compared is the largest
elementwise ``|C - ref| / (eps32 * (|A| @ |B|))`` over the sample: the
error in float32 roundings, scaled by the magnitude of the terms summed.
An entry that should be exactly zero (``|A| @ |B| == 0``) and is not reads
as infinite.

``control`` is the reference computed one precision below the float32
that the configurations state: values and B rounded to bfloat16.  Put in
the program's place, it has to be judged not correct.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse

SAMPLE_ROWS = 2048
EPS32 = float(np.finfo(np.float32).eps)


def sample_rows(n: int, seed: int) -> np.ndarray:
    """The sorted rows of C that a run compares, drawn from ``seed``."""
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.choice(n, size=min(SAMPLE_ROWS, n), replace=False))


class Reference:
    """float64 ``A @ B`` on ``SAMPLE_ROWS`` rows drawn from ``seed``."""

    def __init__(self, n: int, rows: np.ndarray, cols: np.ndarray,
                 vals: np.ndarray, seed: int):
        self.rows = sample_rows(n, seed)
        # rows is sorted (row-major COO), so the sample's nonzeros are
        # contiguous runs found by binary search.
        lo = np.searchsorted(rows, self.rows, side="left")
        hi = np.searchsorted(rows, self.rows, side="right")
        idx = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)]) \
            if len(lo) else np.zeros(0, np.int64)
        idx = idx.astype(np.int64)
        self.slot = np.repeat(np.arange(len(self.rows)), hi - lo)
        sel_cols = np.asarray(cols[idx], dtype=np.int64)
        self.vals = np.asarray(vals[idx], dtype=np.float64)
        #: The rows of B the sample reads.
        self.needed = np.unique(sel_cols)
        self.col_of = np.searchsorted(self.needed, sel_cols)

    def _sum(self, vals: np.ndarray, g: np.ndarray):
        """``sum_j vals[i, j] * g[j]`` over the sample, in float64."""
        a = scipy.sparse.csr_matrix(
            (vals, (self.slot, self.col_of)),
            shape=(self.rows.shape[0], self.needed.shape[0]))
        return np.asarray(a @ g)

    def product(self, b_needed: np.ndarray):
        """``(ref, mag)``: ``A @ B`` and ``|A| @ |B|`` on the sample rows."""
        g = np.asarray(b_needed, dtype=np.float64)
        return self._sum(self.vals, g), self._sum(np.abs(self.vals),
                                                  np.abs(g))

    def scaled_error(self, c_rows: np.ndarray, b_needed: np.ndarray) -> float:
        """Largest ``|C - ref| / (eps32 * |A| @ |B|)`` on the sample."""
        c_rows = np.asarray(c_rows, dtype=np.float64)
        ref, mag = self.product(b_needed)
        if c_rows.shape != ref.shape:
            return float("inf")
        err = np.abs(c_rows - ref)
        if not np.all(np.isfinite(err)):
            return float("inf")
        if np.any(err[mag == 0] > 0):
            return float("inf")
        live = mag > 0
        return float((err[live] / (EPS32 * mag[live])).max()) \
            if live.any() else 0.0

    def control(self, b_needed: np.ndarray) -> np.ndarray:
        """The sample rows of ``A @ B`` with values and B in bfloat16."""
        bf = ml_dtypes.bfloat16
        vals = self.vals.astype(bf).astype(np.float64)
        g = np.asarray(b_needed, dtype=np.float32).astype(bf)
        return self._sum(vals, g.astype(np.float64)).astype(np.float32)

"""The compulsory work of ``C = A @ B`` and the least time it can take.

Counted from the operator and the width alone, the same for every format
and kernel that computes the product:

* flops: ``2 * nnz * d``;
* bytes: the nonzeros' values once (``nnz * 4``, float32; no index bytes,
  since some formats need none), every row of B that a nonzero reads
  once (``nonempty_cols * d * 4``), and C written once (``n * d * 4``).

The least time is the larger of flops over peak FLOP/s and bytes over
peak bytes/s.  A kernel's roofline share is the least time of the work it
did over the device time it took, so it can never pass 100% unless the
device beat its published peak.
"""
from __future__ import annotations

VALUE_BYTES = 4


def compulsory(nnz: int, nonempty_cols: int, n: int, d: int):
    """``(flops, bytes)`` that any implementation of ``A @ B`` must do."""
    flops = 2.0 * nnz * d
    nbytes = VALUE_BYTES * (nnz + nonempty_cols * d + n * d)
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict):
    """``(seconds, bound)``: the least time and which peak sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")

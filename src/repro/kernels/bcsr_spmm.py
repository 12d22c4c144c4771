"""Pallas TPU kernel: BCSR (block-compressed-sparse-row) SpMM.

TPU adaptation of the paper's CSB implementation.  A is stored as dense
t x t blocks; the kernel walks the nonzero blocks in block-row-major order on
the Pallas grid, DMAs each A block and the matching t x bd tile of B
HBM->VMEM, and accumulates C tiles in VMEM with MXU matmuls.

Grid layout: ``(d_tiles, num_blocks)`` with the block index innermost, so all
blocks of a block row are processed consecutively and the C tile stays
resident in VMEM until the block row changes (the paper's cache-reuse
argument made deterministic).  Block coordinates arrive via scalar prefetch,
which the TPU uses to program the DMA engine ahead of compute.

Lane-packed blocks: HBM arrays are tiled 128 lanes wide, so a ``[N, 64,
64]`` float32 array would take twice its bytes (and XLA would copy it into
that padded layout on every call).  A block of edge ``t < 128`` is stored
as ``[t / q, q * t]`` with ``q = lane_rows(t)``: row ``r`` of the stored
block holds block rows ``r, r + t/q, ...`` side by side, and the kernel
takes one matmul per group of ``t / q`` rows (``pack_blocks``).

Segments: the scalar-prefetched coordinates take 8 bytes a block in SMEM,
so a large operator (284k blocks at 943k rows) cannot prefetch them all.
``bcsr_segments`` cuts the sorted block list at block-row boundaries into
segments whose coordinates fit a budget; each segment is one
``pallas_call`` over its own block rows of C, and the calls write into one
C buffer (``input_output_aliases``), so no second C is made.

VMEM working set per grid step:
    A block  t*t*4           (e.g. 128x128 fp32 = 64 KiB)
    B tile   t*bd*4          (128x512     fp32 = 256 KiB)
    C tile   t*bd*4          (128x512     fp32 = 256 KiB)
double-buffered, well under the kernel's scoped VMEM limit; t and bd
default to MXU-aligned 128/512.  The matmul runs at ``Precision.HIGHEST``
so fp32 blocks are not rounded to bf16 on the MXU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmm import mxu_precision

#: SMEM bytes of one block's scalar-prefetched coordinates (row, column).
COORD_BYTES = 8
LANES = 128


def lane_rows(t: int) -> int:
    """How many rows of a ``t x t`` block share one stored row (``q``)."""
    return max(1, min(t, LANES // t))


def pack_blocks(blocks, t: int):
    """``[N, t, t]`` blocks -> the kernel's ``[N, t / q, q * t]`` layout.

    Works on NumPy and JAX arrays alike (a reshape and a transpose).
    """
    q = lane_rows(t)
    n_blocks = blocks.shape[0]
    return blocks.reshape(n_blocks, q, t // q, t).transpose(0, 2, 1, 3) \
        .reshape(n_blocks, t // q, q * t)


def pack_bcsr(rows, cols, vals, *, n: int, t: int, dtype):
    """COO -> the kernel's operands, on the host, without a ``[N, t, t]``
    intermediate.

    Returns ``(blocks [N, t/q, q*t], block_rows [N], block_cols [N],
    block_ptr [n/t + 1])`` as NumPy arrays, blocks sorted by (block row,
    block column), with a zero block on the diagonal of every block row
    that has none (the kernel writes only the C tiles it visits).
    """
    if n % t:
        raise ValueError(f"matrix dim {n} not divisible by block size {t}")
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    nb = n // t
    uniq, inverse = np.unique((rows // t) * nb + cols // t,
                              return_inverse=True)
    present = np.zeros(nb, dtype=bool)
    present[uniq // nb] = True
    missing = np.flatnonzero(~present)
    keys = np.concatenate([uniq, missing * nb + missing])
    order = np.argsort(keys, kind="stable")
    slot = np.empty_like(order)
    slot[order] = np.arange(order.size)
    keys = keys[order]
    q = lane_rows(t)
    h = t // q
    r, c = rows % t, cols % t
    flat = ((slot[inverse] * h + r % h) * q + r // h) * t + c
    blocks = np.zeros((keys.size, h, q * t), dtype=dtype)
    blocks.reshape(-1)[flat] = np.asarray(vals).astype(dtype)
    block_rows = (keys // nb).astype(np.int32)
    block_ptr = np.concatenate(
        [[0], np.cumsum(np.bincount(block_rows, minlength=nb))]
    ).astype(np.int32)
    return blocks, block_rows, (keys % nb).astype(np.int32), block_ptr


def bcsr_segments(block_ptr, max_blocks: int) -> Tuple[Tuple[int, int], ...]:
    """Cut blocks ``[0, block_ptr[-1])`` into ``(start, end)`` segments.

    Each segment is a run of whole block rows (``block_ptr`` is the CSR
    pointer over block rows) holding at most ``max_blocks`` blocks, cut
    greedily: a segment takes block rows until the next would not fit.

    Raises:
        ValueError: if one block row alone holds more than ``max_blocks``.
    """
    ptr = np.asarray(block_ptr, dtype=np.int64)
    total = int(ptr[-1])
    if max_blocks < 1:
        raise ValueError(f"max_blocks must be >= 1, got {max_blocks}")
    segments, start = [], 0
    while start < total:
        # The last block-row boundary at or below start + max_blocks.
        end = int(ptr[np.searchsorted(ptr, start + max_blocks,
                                      side="right") - 1])
        if end <= start:
            row = int(np.searchsorted(ptr, start, side="right")) - 1
            raise ValueError(
                f"block row {row} holds {int(ptr[row + 1] - ptr[row])} "
                f"blocks, more than a segment's {max_blocks}")
        segments.append((start, end))
        start = end
    return tuple(segments)


def _bcsr_kernel(rows_ref, cols_ref, a_ref, b_ref, *refs):
    """One grid step: o[rows[i]] += a[i] @ b[cols[i]] (accumulated in VMEM).

    ``refs`` is ``(o_ref,)``, or ``(c_hbm, o_ref)`` when the call writes
    into an earlier segment's C (aliased, never read here).
    """
    del cols_ref  # consumed by the B index map
    o_ref = refs[-1]
    i_n = pl.program_id(1)
    # First visit of this C tile in this d-pass: previous block was a
    # different block row (or this is the segment's first block).
    is_first = (i_n == 0) | (rows_ref[i_n] != rows_ref[jnp.maximum(i_n - 1,
                                                                   0)])

    @pl.when(is_first)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    h = a_ref.shape[1]                      # block rows per stored group
    t = o_ref.shape[0]
    b_tile = b_ref[...]                     # [t, bd]
    for p in range(t // h):                 # lane_rows(t) groups
        a_rows = a_ref[0, :, p * t:(p + 1) * t]           # [h, t]
        o_ref[p * h:(p + 1) * h, :] += jnp.dot(
            a_rows, b_tile, precision=mxu_precision(a_rows.dtype),
            preferred_element_type=jnp.float32)


def _segment(blocks, rows, cols, b, c, *, start: int, n: int, t: int,
             bd: int, vmem_limit: int, interpret: bool):
    """One ``pallas_call`` over blocks ``[start, start + rows.size)``,
    writing their block rows of C into ``c`` (or a new C when None)."""
    d = b.shape[1]
    h, w = blocks.shape[1:]
    in_specs = [
        pl.BlockSpec((1, h, w),
                     lambda i_d, i_n, rows, cols: (start + i_n, 0, 0)),
        pl.BlockSpec((t, bd), lambda i_d, i_n, rows, cols: (cols[i_n], i_d)),
    ]
    operands = [rows, cols, blocks, b]
    aliases = {}
    if c is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.ANY))
        aliases = {len(operands): 0}
        operands.append(c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // bd, rows.shape[0]),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t, bd),
                               lambda i_d, i_n, rows, cols: (rows[i_n], i_d)),
    )
    return pl.pallas_call(
        _bcsr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n // t * t, d), jnp.float32),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="bcsr_spmm",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("n", "t", "block_d",
                                             "vmem_limit", "interpret",
                                             "segments"))
def bcsr_spmm_pallas(blocks: jnp.ndarray, block_rows: jnp.ndarray,
                     block_cols: jnp.ndarray, b: jnp.ndarray, *, n: int,
                     t: int, block_d: int, vmem_limit: int,
                     interpret: bool,
                     segments: Optional[Tuple[Tuple[int, int], ...]] = None
                     ) -> jnp.ndarray:
    """C = A @ B with A given as sorted nonzero blocks.

    Args:
      blocks:     [N, t/q, q*t] lane-packed block values (``pack_blocks``),
                  sorted by (block_row, col).
      block_rows: [N] int32 block-row ids. Every block row in [0, n/t) must
                  appear at least once (``pack_bcsr`` pads empty rows with
                  a zero block).
      block_cols: [N] int32 block-col ids.
      b:          [n, d] dense operand.
      n, t:       matrix dim and block edge (static).
      block_d:    d-tile width (static, MXU-aligned).
      vmem_limit: scoped VMEM the kernel may use, in bytes (static).
      interpret:  run in Pallas interpret mode (the CPU test path).
      segments:   ``(start, end)`` block ranges cut at block-row boundaries
                  (``bcsr_segments``), one ``pallas_call`` each; None runs
                  all blocks in one call.
    """
    d = b.shape[1]
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} must be divisible by the d-tile {bd}")
    if blocks.shape[1:] != (t // lane_rows(t), lane_rows(t) * t):
        raise ValueError(f"blocks {blocks.shape} are not lane-packed for "
                         f"t={t} (see pack_blocks)")
    if segments is None:
        segments = ((0, blocks.shape[0]),)
    out = None
    for start, end in segments:
        out = _segment(blocks, block_rows[start:end], block_cols[start:end],
                       b, out, start=start, n=n, t=t, bd=bd,
                       vmem_limit=vmem_limit, interpret=interpret)
    return out[:n].astype(b.dtype)

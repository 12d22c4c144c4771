"""Pallas TPU kernel: BCSR (block-compressed-sparse-row) SpMM.

TPU adaptation of the paper's CSB implementation (DESIGN.md Section 3).  A is
stored as dense t x t blocks; the kernel walks the nonzero blocks in
block-row-major order on the Pallas grid, DMAs each A block and the matching
t x bd tile of B HBM->VMEM, and accumulates C tiles in VMEM with MXU matmuls.

Grid layout: ``(d_tiles, num_blocks)`` with the block index innermost, so all
blocks of a block row are processed consecutively and the C tile stays
resident in VMEM until the block row changes (the paper's cache-reuse
argument made deterministic).  Block coordinates arrive via scalar prefetch,
which the TPU uses to program the DMA engine ahead of compute.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmm import mxu_precision


def _bcsr_kernel(rows_ref, cols_ref, a_ref, b_ref, o_ref):
    """One grid step: o[rows[i]] += a[i] @ b[cols[i]] (accumulated in VMEM)."""
    del cols_ref  # consumed by the B index map
    i_n = pl.program_id(1)
    # First visit of this C tile in this d-pass: previous block was a
    # different block row (or this is the first block).
    is_first = (i_n == 0) | (rows_ref[i_n] != rows_ref[jnp.maximum(i_n - 1,
                                                                   0)])

    @pl.when(is_first)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    a_block = a_ref[0]                      # [t, t]
    b_tile = b_ref[...]                     # [t, bd]
    o_ref[...] += jnp.dot(a_block, b_tile,
                          precision=mxu_precision(a_block.dtype),
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "t", "block_d",
                                             "vmem_limit", "interpret"))
def bcsr_spmm_pallas(blocks: jnp.ndarray, block_rows: jnp.ndarray,
                     block_cols: jnp.ndarray, b: jnp.ndarray, *, n: int,
                     t: int, block_d: int, vmem_limit: int,
                     interpret: bool) -> jnp.ndarray:
    """C = A @ B with A given as sorted nonzero blocks.

    Args:
      blocks:     [N, t, t] dense block values, sorted by (block_row, col).
      block_rows: [N] int32 block-row ids. Every block row in [0, n/t) must
                  appear at least once (pad empty rows with a zero block —
                  see ops.pad_empty_block_rows).
      block_cols: [N] int32 block-col ids.
      b:          [n, d] dense operand.
      n, t:       matrix dim and block edge (static).
      block_d:    d-tile width (static, MXU-aligned).
      vmem_limit: scoped VMEM the kernel may use, in bytes (static).
      interpret:  run in Pallas interpret mode (the CPU test path).
    """
    d = b.shape[1]
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} must be divisible by the d-tile {bd}")
    num_blocks = blocks.shape[0]
    nb = n // t
    grid = (d // bd, num_blocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, t), lambda i_d, i_n, rows, cols: (i_n, 0, 0)),
            pl.BlockSpec((t, bd),
                         lambda i_d, i_n, rows, cols: (cols[i_n], i_d)),
        ],
        out_specs=pl.BlockSpec((t, bd),
                               lambda i_d, i_n, rows, cols: (rows[i_n], i_d)),
    )
    out = pl.pallas_call(
        _bcsr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="bcsr_spmm",
    )(block_rows, block_cols, blocks, b)
    return out[:n].astype(b.dtype)

"""Pallas TPU kernel: banded (diagonal-regime) SpMM.

Realizes the paper's diagonal-sparsity model (Eq. 3) on TPU: for a band of
half-width w (in t x t blocks), each block row multiplies at most 2w+1
diagonal-adjacent blocks.  Because consecutive block rows touch overlapping
B tiles, B is streamed HBM->VMEM essentially once — the TPU counterpart of
"B is loaded once into cache".

A is stored densely as ``band[nb, W, t, t]`` with W = 2w+1; edge blocks are
zero-padded so index maps never need masking (a zero block contributes
nothing while the clamped B tile it multiplies is already resident).
The matmul runs at ``Precision.HIGHEST`` so fp32 blocks are not rounded to
bf16 on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmm import mxu_precision


def _banded_kernel(a_ref, b_ref, o_ref, *, w: int):
    del w
    o = pl.program_id(2)

    @pl.when(o == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[0, 0], b_ref[...],
                          precision=mxu_precision(a_ref.dtype),
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("t", "w", "block_d", "vmem_limit",
                                    "interpret"))
def banded_spmm_pallas(band: jnp.ndarray, b: jnp.ndarray, *, t: int, w: int,
                       block_d: int, vmem_limit: int,
                       interpret: bool) -> jnp.ndarray:
    """C = A @ B for banded A.

    Args:
      band: [nb, 2w+1, t, t] block diagonals; band[i, o] is the block at
            block position (i, i + o - w), zero where out of range.
      b:    [n, d] dense operand; n = nb * t.
      t, w: block edge and half-width in blocks (static).
      block_d: d-tile width (static).
      vmem_limit: scoped VMEM the kernel may use, in bytes (static).
      interpret: run in Pallas interpret mode (the CPU test path).
    """
    nb, W, _, _ = band.shape
    assert W == 2 * w + 1, (W, w)
    n, d = b.shape
    assert n == nb * t, (n, nb, t)
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} not divisible by d-tile {bd}")
    grid = (d // bd, nb, W)

    def a_map(i_d, i, o):
        return (i, o, 0, 0)

    def b_map(i_d, i, o):
        col = jnp.clip(i + o - w, 0, nb - 1)
        return (col, i_d)

    def o_map(i_d, i, o):
        return (i, i_d)

    out = pl.pallas_call(
        functools.partial(_banded_kernel, w=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, t, t), a_map),
            pl.BlockSpec((t, bd), b_map),
        ],
        out_specs=pl.BlockSpec((t, bd), o_map),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="banded_spmm",
    )(band, b)
    return out.astype(b.dtype)

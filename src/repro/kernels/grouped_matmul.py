"""Pallas TPU kernel: grouped matmul (block-diagonal BCSR SpMM for MoE).

MegaBlocks insight, restated in the paper's terms: after sorting tokens by
routed expert, the MoE expert FFN is an SpMM whose A is *block-diagonal* —
the best case of the paper's blocked-sparsity regime (every t x t block is
fully dense, z = t, MXU utilization 1.0).  The kernel computes

    out[i*bm:(i+1)*bm] = x[i*bm:(i+1)*bm] @ w[group_ids[i]]

i.e. each row block of the sorted token buffer multiplies the weight matrix
of the expert that owns it.  ``group_ids`` arrives via scalar prefetch so the
weight DMA for block i+1 can be issued while block i is on the MXU.

Grid: (row_blocks, n_tiles, k_tiles), k innermost for VMEM accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmm import mxu_precision


def _gmm_kernel(gid_ref, x_ref, w_ref, o_ref):
    del gid_ref  # consumed by the W index map
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                          precision=mxu_precision(x_ref.dtype),
                          preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "vmem_limit", "interpret"))
def grouped_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray,
                          group_ids: jnp.ndarray, *, bm: int = 128,
                          bk: int = 128, bn: int = 128, vmem_limit: int,
                          interpret: bool) -> jnp.ndarray:
    """out[r] = x[r] @ w[group_of_row_block(r)].

    Args:
      x:         [T, K] sorted token buffer (T divisible by bm).
      w:         [E, K, N] expert weights.
      group_ids: [T // bm] int32 expert id per row block.  Rows within one
                 block must share an expert (guaranteed by the dispatcher's
                 block-aligned padding).
      bm/bk/bn:  tile sizes (MXU-aligned).
      vmem_limit: scoped VMEM the kernel may use, in bytes (static).
      interpret: run in Pallas interpret mode (the CPU test path).
    """
    T, K = x.shape
    E, K2, N = w.shape
    assert K == K2, (K, K2)
    if T % bm or K % bk or N % bn:
        raise ValueError(f"shapes ({T},{K},{N}) not divisible by tiles "
                         f"({bm},{bk},{bn})")
    grid = (T // bm, N // bn, K // bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, gid: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, gid: (gid[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, gid: (i, j)),
    )
    out = pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="grouped_matmul",
    )(group_ids, x, w)
    return out.astype(x.dtype)

"""SpMM implementations per sparse layout (pure JAX, jit-compiled).

These are the *system under test* for the paper's benchmarks on this host,
and the reference semantics for the Pallas TPU kernels in repro.kernels.

  csr_spmm   gather rows of B per nonzero, multiply, segment-sum by row
             (the paper's CSR implementation; worst-case traffic).
  ell_spmm   padded, fully vectorized column-slot loop (vendor-style).
  bcsr_spmm  batched dense t x t block matmuls + block-row segment sum
             (the paper's CSB, restructured for matrix units).
  dia_spmm   per-diagonal shifted axpy (the diagonal regime realized).

Scale-free-regime variants (PR 8) share the gather/segment-sum algebra
but traverse different host-prepared orders:

  binned_spmm    slab-major traversal (two-phase propagation blocking).
  rowsplit_spmm  equal-nnz chunk traversal (merge-path load balance).
  ell_coo_spmm   vectorized ELL body + COO-tail gather/segment-sum.

All return C = A @ B with C: [n, d] in the operand dtype.  Reduced
precisions (bf16 containers + bf16 B) round only the *products*:
every accumulation runs in fp32 (explicit upcast before the segment
sum / scan carry, ``preferred_element_type`` on the matmuls, which run
at ``Precision.HIGHEST`` so a TPU does not round fp32 blocks to bf16) and
the result is cast back once at the end — the same contract as the Pallas
kernels' fp32 VMEM accumulators.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.sparse.formats import (
    BCSRMatrix, BinnedMatrix, CSRMatrix, DIAMatrix, ELLCOOMatrix, ELLMatrix,
    RowSplitMatrix)


@jax.jit
def csr_spmm(a: CSRMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """C[r] += val * B[c] for every nonzero (r, c, val)."""
    gathered = b[a.indices]                       # [nnz, d] random gather
    scaled = gathered * a.data[:, None]           # [nnz, d]
    out = jax.ops.segment_sum(scaled.astype(jnp.float32), a.row_ids,
                              num_segments=a.n)
    return out.astype(b.dtype)


@jax.jit
def ell_spmm(a: ELLMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """Vectorized over the padded slot dimension; zero padding is harmless."""

    def _slot(carry, k):
        acc = carry
        cols = a.indices[:, k]                    # [n]
        vals = a.data[:, k]                       # [n]
        acc = acc + (b[cols] * vals[:, None]).astype(jnp.float32)
        return acc, None

    init = jnp.zeros((a.n, b.shape[1]), dtype=jnp.float32)
    out, _ = jax.lax.scan(_slot, init, jnp.arange(a.k))
    return out.astype(b.dtype)


@jax.jit
def bcsr_spmm(a: BCSRMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """Batched block matmul: the XLA-native form of the CSB traversal.

    B is viewed as nb tiles of shape [t, d]; each nonzero block multiplies
    its column tile and accumulates into its row tile.
    """
    d = b.shape[1]
    b_tiles = b.reshape(a.nb, a.t, d)
    gathered = b_tiles[a.block_cols]              # [N, t, d]
    prods = jnp.einsum("nij,njd->nid", a.blocks, gathered,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    out_tiles = jax.ops.segment_sum(prods, a.block_rows, num_segments=a.nb)
    return out_tiles.reshape(a.n, d).astype(b.dtype)


@jax.jit
def dia_spmm(a: DIAMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """C[r] += diag_k[r] * B[r + off_k]; offsets are static so this unrolls
    into num_offsets shifted multiplies — exactly one streaming pass over B
    per diagonal (the paper's 'B loaded once' regime when offsets are few).

    The shift is a static slice + zero pad rather than an index gather, so
    XLA emits pure streaming copies (no gather unit / scatter traffic) and
    the kernel runs at axpy speed — the behavior Eq. 3 charges for.
    """
    n, d = a.n, b.shape[1]
    out = None
    for i, off in enumerate(a.offsets):
        if off >= 0:
            # rows [0, n-off) read b[off:]; rows past n-off fall off the band.
            shifted = jnp.concatenate(
                [b[off:], jnp.zeros((off, d), b.dtype)]) if off else b
        else:
            shifted = jnp.concatenate(
                [jnp.zeros((-off, d), b.dtype), b[:n + off]])
        contrib = (a.data[i][:, None] * shifted).astype(jnp.float32)
        out = contrib if out is None else out + contrib
    if out is None:
        out = jnp.zeros((n, d), dtype=jnp.float32)
    return out.astype(b.dtype)


@partial(jax.jit, static_argnames=("block_rows_per_step",))
def bcsr_spmm_scan(a: BCSRMatrix, b: jnp.ndarray,
                   block_rows_per_step: int = 1) -> jnp.ndarray:
    """Memory-lean BCSR SpMM: scan over nonzero blocks without materializing
    the [N, t, d] product tensor.  Mirrors the Pallas kernel's grid walk and
    is used as its CPU wall-clock proxy for large N.
    """
    d = b.shape[1]
    b_tiles = b.reshape(a.nb, a.t, d)

    def _step(acc, blk):
        block, br, bc = blk
        prod = jnp.dot(block, b_tiles[bc],
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        acc = acc.at[br].add(prod)
        return acc, None

    init = jnp.zeros((a.nb, a.t, d), dtype=jnp.float32)
    out, _ = jax.lax.scan(_step, init,
                          (a.blocks, a.block_rows, a.block_cols))
    return out.reshape(a.n, d).astype(b.dtype)


@jax.jit
def binned_spmm(a: BinnedMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """Slab-major gather/segment-sum: same algebra as ``csr_spmm``, but the
    nonzero stream arrives grouped by B-row slab (ascending columns inside
    each slab), so consecutive gathers hit one cache/VMEM-resident slab of
    B — the traversal the binned AI model charges for.
    """
    gathered = b[a.cols]                          # [nnz, d] slab-local reuse
    scaled = gathered * a.data[:, None]           # [nnz, d]
    out = jax.ops.segment_sum(scaled.astype(jnp.float32), a.rows,
                              num_segments=a.n)
    return out.astype(b.dtype)


@jax.jit
def rowsplit_spmm(a: RowSplitMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """Equal-nnz chunk traversal: padding entries carry value 0 at row 0,
    so the segment sum absorbs them without masking."""
    if a.data.shape[0] == 0:
        return jnp.zeros((a.n, b.shape[1]), dtype=b.dtype)
    gathered = b[a.cols]                          # [P, d]
    scaled = gathered * a.data[:, None]           # [P, d]
    out = jax.ops.segment_sum(scaled.astype(jnp.float32), a.rows,
                              num_segments=a.n)
    return out.astype(b.dtype)


@jax.jit
def ell_coo_spmm(a: ELLCOOMatrix, b: jnp.ndarray) -> jnp.ndarray:
    """Vectorized body (the ELL slot loop up to ``k_cut``) plus a COO-tail
    gather/segment-sum for the overflow entries of hub rows."""

    def _slot(carry, k):
        acc = carry
        cols = a.body_indices[:, k]               # [n]
        vals = a.body_data[:, k]                  # [n]
        acc = acc + (b[cols] * vals[:, None]).astype(jnp.float32)
        return acc, None

    init = jnp.zeros((a.n, b.shape[1]), dtype=jnp.float32)
    out, _ = jax.lax.scan(_slot, init, jnp.arange(a.k_cut))
    if a.tail_data.shape[0]:
        tail = b[a.tail_cols] * a.tail_data[:, None]     # [tail_nnz, d]
        out = out + jax.ops.segment_sum(tail.astype(jnp.float32),
                                        a.tail_rows, num_segments=a.n)
    return out.astype(b.dtype)


def dense_spmm(a_dense: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Dense reference (XLA matmul) — the 'vendor peak' comparison point."""
    return a_dense @ b


IMPLEMENTATIONS = {
    "csr": csr_spmm,
    "ell": ell_spmm,
    "bcsr": bcsr_spmm,
    "dia": dia_spmm,
    "binned": binned_spmm,
    "rowsplit": rowsplit_spmm,
    "ell_coo": ell_coo_spmm,
}

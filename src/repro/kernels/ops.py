"""Container-level compat wrappers over the kernel registry.

The registry (``repro.kernels.registry``) is the system entry point: one
:class:`~repro.kernels.registry.KernelSpec` per ``(format, backend)``
pair, consumed by the dispatcher, the streaming layer, the calibration
sweep, and the benchmark suite.  This module keeps the original
container-level call signatures (``csr_spmm(CSRMatrix, b)`` etc.) for
direct kernel use and the kernel test sweeps; layout helpers and the
roofline-estimate types live in the registry and are re-exported here.

The wrappers are deprecated: they run fp32/int32 only and do not grow
the precision axis (value/index dtype selection lives in
:class:`~repro.kernels.registry.KernelContext`).  New callers should use
``registry.spmm(m, b, format=..., backend=...)`` or bind a
:class:`~repro.kernels.registry.KernelSpec`; each wrapper raises a
``DeprecationWarning`` on call.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax.numpy as jnp
import numpy as np

# Re-exported for backward compatibility: these moved to the registry.
from repro.kernels.registry import (          # noqa: F401
    KernelRoofline, ROW_TILE, band_to_blocks, bcsr_kernel_roofline,
    csr_kernel_roofline, default_interpret, dia_kernel_roofline,
    grouped_matmul_roofline, pad_empty_block_rows,
)
from repro.core.hardware import device_hardware, kernel_vmem_limit
from repro.kernels.bcsr_spmm import bcsr_spmm_pallas, pack_blocks
from repro.kernels.banded_spmm import banded_spmm_pallas
from repro.kernels.binned_spmm import (
    binned_spmm_pallas, csr_to_slab_bins, pack_rowsplit_chunks,
    rowsplit_spmm_pallas)
from repro.kernels.csr_spmm import csr_spmm_pallas, csr_to_row_tiles
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.sparse.formats import BCSRMatrix, CSRMatrix


def _interpret(flag: Optional[bool]) -> bool:
    return default_interpret() if flag is None else flag


def _vmem_limit() -> int:
    return kernel_vmem_limit(device_hardware())


def _warn_deprecated(name: str) -> None:
    # stacklevel=3: helper frame (1), wrapper frame (2), caller (3).
    warnings.warn(
        f"repro.kernels.{name} is a deprecated fp32/int32-only compat "
        f"wrapper; use repro.kernels.registry.spmm(m, b, format=..., "
        f"backend='pallas') with a KernelContext (which also carries the "
        f"value/index precision axis), or the dispatcher in "
        f"repro.sparse",
        DeprecationWarning, stacklevel=3)


def bcsr_spmm(a: BCSRMatrix, b: jnp.ndarray, *, block_d: int = 512,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """BCSR SpMM via the Pallas kernel (paper's CSB on TPU).

    Args:
        a: dense-block container, [n, n] with t x t blocks; empty block
            rows are zero-padded here so the kernel covers every C tile.
        b: dense right-hand side, [n, d]; when d > ``block_d``, d must be
            a multiple of ``block_d`` (the tile clamps to min(block_d, d)).
        block_d: d-tile width the kernel iterates over.
        interpret: force Pallas interpret mode; default: CPU backend only.

    Returns:
        ``C = A @ B`` as a dense [n, d] array.
    """
    _warn_deprecated("bcsr_spmm")
    a = pad_empty_block_rows(a)
    return bcsr_spmm_pallas(pack_blocks(a.blocks, a.t), a.block_ptr,
                            a.block_cols, b,
                            n=a.n, t=a.t, block_d=block_d,
                            vmem_limit=_vmem_limit(),
                            interpret=_interpret(interpret))


def csr_spmm(a: CSRMatrix, b: jnp.ndarray, *, row_tile: int = ROW_TILE,
             chunk: int = 128, block_d: int = 512,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """CSR SpMM via the Pallas row-gather/segment-sum kernel.

    Packs the CSR arrays into row-tiled chunks host-side (cached nowhere:
    callers that reuse a matrix should go through repro.sparse.dispatch,
    which caches prepared layouts per matrix).

    Args:
        a: CSR container, [n, n].
        b: dense right-hand side, [n, d]; when d > ``block_d``, d must be
            a multiple of ``block_d`` (the tile clamps to min(block_d, d)).
        row_tile: rows per C tile (at most 128).
        chunk: nonzeros packed per (tile, chunk) slot.
        block_d: d-tile width the kernel iterates over.
        interpret: force Pallas interpret mode; default: CPU backend only.

    Returns:
        ``C = A @ B`` as a dense [n, d] array.
    """
    _warn_deprecated("csr_spmm")
    arrays = csr_to_row_tiles(
        np.asarray(a.indptr), np.asarray(a.indices), np.asarray(a.data),
        n=a.n, row_tile=row_tile, chunk=chunk)
    return csr_spmm_pallas(*(jnp.asarray(x) for x in arrays), b, n=a.n,
                           row_tile=row_tile, block_d=block_d,
                           vmem_limit=_vmem_limit(),
                           interpret=_interpret(interpret))


def banded_spmm(band: jnp.ndarray, b: jnp.ndarray, *, t: int, w: int,
                block_d: int = 512,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Banded SpMM via the Pallas kernel (paper's diagonal regime).

    Args:
        band: block-band tensor [nb, 2w+1, t, t] from ``band_to_blocks``.
        b: dense right-hand side, [n, d] with n = nb * t.
        t: block edge; must divide n.
        w: block half-bandwidth (diagonal offsets within ±w*t).
        block_d: d-tile width the kernel iterates over.
        interpret: force Pallas interpret mode; default: CPU backend only.

    Returns:
        ``C = A @ B`` as a dense [n, d] array.
    """
    _warn_deprecated("banded_spmm")
    return banded_spmm_pallas(band, b, t=t, w=w, block_d=block_d,
                              vmem_limit=_vmem_limit(),
                              interpret=_interpret(interpret))


def binned_spmm(a: CSRMatrix, b: jnp.ndarray, *, row_tile: int = ROW_TILE,
                chunk: int = 128, block_d: int = 512,
                b_tile: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Two-phase binned SpMM via the Pallas slab-major kernel.

    Bins the CSR nonzeros by B-row slab host-side, so the kernel touches
    each VMEM-resident slab of B exactly once per d-pass and streams
    partial C blocks instead of streaming gathers (the scale-free
    regime's propagation-blocking traversal).

    Args:
        a: CSR container, [n, n] (the binning starts from CSR order).
        b: dense right-hand side, [n, d]; when d > ``block_d``, d must be
            a multiple of ``block_d`` (the tile clamps to min(block_d, d)).
        row_tile: rows per partial C block.
        chunk: nonzeros packed per kernel step.
        b_tile: B rows per VMEM-resident slab; None holds B whole (one
            slab — degenerates to CSR order).
        interpret: force Pallas interpret mode; default: CPU backend only.

    Returns:
        ``C = A @ B`` as a dense [n, d] array.
    """
    _warn_deprecated("binned_spmm")
    arrays = csr_to_slab_bins(
        np.asarray(a.indptr), np.asarray(a.indices), np.asarray(a.data),
        n=a.n, row_tile=row_tile, chunk=chunk, b_tile=b_tile)
    return binned_spmm_pallas(*(jnp.asarray(x) for x in arrays), b,
                              n=a.n, row_tile=row_tile, b_tile=b_tile,
                              block_d=block_d, vmem_limit=_vmem_limit(),
                              interpret=_interpret(interpret))


def rowsplit_spmm(a: CSRMatrix, b: jnp.ndarray, *, chunk: int = 128,
                  block_d: int = 512,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Row-split (merge-path) SpMM via the Pallas equal-nnz-chunk kernel.

    Cuts the nonzero stream into exact-``chunk`` work units so skewed
    degree distributions (hub rows) cannot starve kernel programs, then
    scatters the windowed partials back by row in a segment-sum epilogue.

    Args:
        a: CSR container, [n, n].
        b: dense right-hand side, [n, d]; held whole in VMEM (this kernel
            trades B residency for perfect load balance).
        chunk: nonzeros per work unit.
        block_d: d-tile width the kernel iterates over.
        interpret: force Pallas interpret mode; default: CPU backend only.

    Returns:
        ``C = A @ B`` as a dense [n, d] array.
    """
    _warn_deprecated("rowsplit_spmm")
    row_map, cols, slots, vals = pack_rowsplit_chunks(
        np.asarray(a.indptr), np.asarray(a.indices), np.asarray(a.data),
        n=a.n, chunk=chunk)
    return rowsplit_spmm_pallas(
        jnp.asarray(row_map), jnp.asarray(cols), jnp.asarray(slots),
        jnp.asarray(vals), b, n=a.n, window=int(row_map.shape[1]),
        block_d=block_d, vmem_limit=_vmem_limit(),
        interpret=_interpret(interpret))


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, group_ids: jnp.ndarray,
                   *, bm: int = 128, bk: int = 128, bn: int = 128,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Grouped (block-diagonal) matmul via the Pallas kernel (MoE FFN).

    Args:
        x: token rows sorted/padded into ``bm``-row group blocks, [T, K].
        w: per-group weights, [E, K, N].
        group_ids: group index per ``bm``-row block, [T / bm] int32.
        bm, bk, bn: MXU tile sizes (rows, contraction, columns).
        interpret: force Pallas interpret mode; default: CPU backend only.

    Returns:
        ``Y[i] = x[i] @ w[group_ids[i // bm]]`` as a dense [T, N] array.
    """
    _warn_deprecated("grouped_matmul")
    return grouped_matmul_pallas(x, w, group_ids, bm=bm, bk=bk, bn=bn,
                                 vmem_limit=_vmem_limit(),
                                 interpret=_interpret(interpret))

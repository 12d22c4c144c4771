"""Pallas TPU kernels for the scale-free regime: binned + row-split SpMM.

Two layouts over the same gather/one-hot-matmul machinery as the CSR
kernel (``repro.kernels.csr_spmm``).  Both keep B (or a slab of it)
resident in VMEM and gather rows with dynamic single-row loads, column
ids read as scalars from SMEM; the CSR kernel instead DMAs rows from HBM.

Two-phase binned SpMM (propagation blocking, Gu et al. 2020)
    Phase one (host, ``csr_to_slab_bins``) bins nonzeros by the B row
    slab they gather from.  A *visit* is one (slab, row tile) pair with
    nonzeros; visits are ordered slab-major.  Phase two visits slabs in
    order: while one ``[b_tile, bd]`` slab of B is VMEM resident, every
    row tile with nonzeros in that slab accumulates its contribution into
    a private partial-C block.  B is read once per touched slab per
    d-pass instead of once per nonzero; a segment-sum epilogue folds the
    per-visit partials into C.  Each visit is padded to whole chunks, so
    the layout stays small only when visits hold many nonzeros (banded
    and block structure); the dispatcher's packing gate skips it where
    the padding would inflate the layout.

Row-split SpMM (merge-path style load balancing)
    The row-major nonzero stream is cut into chunks of exactly ``chunk``
    entries regardless of row boundaries, so a hub row spans many grid
    steps instead of serializing one row tile.  Because the stream is
    row-major, the distinct rows inside one chunk form a contiguous run
    of nonempty-row ranks; the kernel reduces each chunk into a
    ``[window, bd]`` partial via the one-hot matmul, and a segment-sum
    epilogue scatters windows back to global rows through a host-built
    ``row_map``.  It holds all of B in VMEM, so the dispatcher skips it
    once B outgrows the kernel's VMEM budget.

Each grid step owns one output block (a visit's partial, a chunk's
window), so no block is revisited after another block was written.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmm import (
    MAX_ROW_TILE, chunk_product, chunk_scratch, chunk_spec, for_each_chunk,
    gather_from_vmem, index_extent_check, load_chunk, pack_chunks,
    scatter_chunks)


def csr_to_slab_bins(indptr: np.ndarray, indices: np.ndarray,
                     data: np.ndarray, *, n: int, row_tile: int = 32,
                     chunk: int = 128, b_tile: Optional[int] = None,
                     index_dtype=np.int32
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray, np.ndarray, np.ndarray]:
    """Bin CSR nonzeros by B row slab (phase one of the binned kernel).

    Returns ``(visit_tiles[V], visit_slabs[V], visit_starts[V + 1],
    cols, row_slots, vals)``, the last three chunk arrays as
    ``csr_spmm.scatter_chunks`` lays them out.  A
    *visit* is one (slab, row-tile) pair with nonzeros; it owns chunks
    ``[visit_starts[v], visit_starts[v + 1])`` and visits are ordered
    slab-major, so each B slab is resident for one contiguous run of grid
    steps per d-pass.  Within a visit, entries are sorted by column
    (CSC-like inside the slab), ``cols`` are slab-local, and
    ``row_slots`` (int8) are row indices within the tile.

    ``visit_tiles`` maps each visit to its row tile for the segment-sum
    epilogue.  With ``b_tile=None`` there is a single slab spanning all
    rows (the layout degenerates to one visit per nonempty row tile).
    An empty matrix still produces one visit (with no chunks) so the
    kernel has a well-formed grid.
    """
    if not 0 < row_tile <= MAX_ROW_TILE:
        raise ValueError(f"row_tile must be in [1, {MAX_ROW_TILE}], "
                         f"got {row_tile}")
    indptr = np.asarray(indptr, dtype=np.int64)
    nnz = int(indptr[-1])
    index_extent_check(n if b_tile is None else b_tile, index_dtype)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    cols = np.asarray(indices)[:nnz].astype(np.int64)
    vals = np.asarray(data)[:nnz]
    bt = n if b_tile is None else b_tile
    slabs = cols // bt
    tiles = rows // row_tile
    # The binning pass: slab-major, then row tile, then column (CSC-like
    # within each slab).  lexsort keys are last-key-major.
    order = np.lexsort((rows, cols, tiles, slabs))
    rows, cols, vals = rows[order], cols[order], vals[order]
    slabs, tiles = slabs[order], tiles[order]
    keys = slabs * ((n + row_tile - 1) // row_tile + 1) + tiles
    first = np.concatenate([[True], keys[1:] != keys[:-1]]) if nnz \
        else np.zeros(0, bool)
    visit_of = np.cumsum(first) - 1
    num_visits = max(1, int(first.sum()))
    starts, pos, num_chunks = pack_chunks(visit_of, num_visits, chunk)
    visit_tiles = tiles[first] if nnz else np.zeros(1, np.int64)
    visit_slabs = slabs[first] if nnz else np.zeros(1, np.int64)
    packed = scatter_chunks(
        pos, num_chunks, chunk,
        (cols - slabs * bt, index_dtype),
        (rows - tiles * row_tile, np.int8),
        (vals, np.asarray(data).dtype))
    return (visit_tiles.astype(np.int32), visit_slabs.astype(np.int32),
            starts, *packed)


def _binned_kernel(slabs_ref, starts_ref, cols_hbm, slots_hbm, vals_hbm,
                   b_ref, o_ref, cols_buf, slots_buf, vals_buf, gbuf, sems,
                   *, row_tile: int):
    """One grid step: one visit's partial C block, gathered from the
    resident B slab chunk by chunk."""
    del slabs_ref  # consumed by the B index map
    o_ref[...] = jnp.zeros_like(o_ref)

    def chunk(c):
        load_chunk(c, (cols_hbm, slots_hbm, vals_hbm),
                   (cols_buf, slots_buf, vals_buf), sems)
        gather_from_vmem(c, cols_buf, b_ref, gbuf)
        o_ref[...] += chunk_product(c, slots_buf, vals_buf, gbuf, row_tile)

    for_each_chunk(pl.program_id(1), starts_ref, chunk)


@functools.partial(jax.jit,
                   static_argnames=("n", "row_tile", "b_tile", "block_d",
                                    "vmem_limit", "interpret"))
def binned_spmm_pallas(visit_tiles: jnp.ndarray, visit_slabs: jnp.ndarray,
                       visit_starts: jnp.ndarray, cols: jnp.ndarray,
                       row_slots: jnp.ndarray, vals: jnp.ndarray,
                       b: jnp.ndarray, *, n: int, row_tile: int,
                       b_tile: Optional[int], block_d: int,
                       vmem_limit: int, interpret: bool) -> jnp.ndarray:
    """C = A @ B with A given as slab-binned chunks (csr_to_slab_bins).

    The grid walks visits slab-major; each step writes the visit's
    private partial C block.  The epilogue segment-sums partials by
    ``visit_tiles`` into the row tiles — that reduction
    (2 * V * row_tile * d extra C traffic) is the price the binned AI
    model charges for reading B once per touched slab.

    Args:
      visit_tiles:  [V] int32 row-tile id per visit.
      visit_slabs:  [V] int32 B row-slab id per visit (non-decreasing).
      visit_starts: [V + 1] int32 first chunk of each visit.
      cols:         [C / p, p, chunk] slab-local columns (int32 or int16).
      row_slots:    [C / p, p, chunk] int8 row index within the tile.
      vals:         [C / p, p, chunk] values, zero-padded (see
                    ``csr_spmm.scatter_chunks``).
      b:            [n, d] dense operand.
      n:            matrix dimension (static).
      row_tile:     rows per C tile (static).
      b_tile:       B rows per VMEM-resident slab (static); must match
                    the layout's ``b_tile``.  None holds B whole.
      block_d:      d-tile width (static).
      vmem_limit:   scoped VMEM the kernel may use, in bytes (static).
      interpret:    run in Pallas interpret mode (the CPU test path).
    """
    out_dtype = b.dtype
    b = b.astype(jnp.float32)
    d = b.shape[1]
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} must be divisible by the d-tile {bd}")
    bt = -(-b.shape[0] // 8) * 8 if b_tile is None else b_tile
    if b.shape[0] % bt != 0:
        pad = bt - b.shape[0] % bt
        b = jnp.concatenate([b, jnp.zeros((pad, d), b.dtype)])
    chunk = cols.shape[2]
    num_visits = visit_tiles.shape[0]
    num_tiles = (n + row_tile - 1) // row_tile
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // bd, num_visits),
        in_specs=[
            hbm, hbm, hbm,
            pl.BlockSpec((bt, bd),
                         lambda i_d, v, slabs, starts: (slabs[v], i_d)),
        ],
        out_specs=pl.BlockSpec(
            (row_tile, bd), lambda i_d, v, slabs, starts: (v, i_d)),
        scratch_shapes=chunk_scratch(cols, row_slots, vals) + [
            pltpu.VMEM((chunk, bd), jnp.float32),
            pltpu.SemaphoreType.DMA((3,))],
    )
    partials = pl.pallas_call(
        functools.partial(_binned_kernel, row_tile=row_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_visits * row_tile, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="binned_spmm",
    )(visit_slabs, visit_starts, cols, row_slots, vals, b)
    # Epilogue: fold visit partials into their row tiles.
    tiled = jax.ops.segment_sum(
        partials.reshape(num_visits, row_tile, d), visit_tiles,
        num_segments=num_tiles)
    return tiled.reshape(num_tiles * row_tile, d)[:n].astype(out_dtype)


def pack_rowsplit_chunks(indptr: np.ndarray, indices: np.ndarray,
                         data: np.ndarray, *, n: int, chunk: int = 128,
                         index_dtype=np.int32
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray]:
    """Cut the row-major nonzero stream into equal-``chunk`` work units.

    Returns ``(row_map[C, W], cols, row_slots, vals)``, the last three
    chunk arrays as ``csr_spmm.scatter_chunks`` lays them out.
    ``row_slots`` (int8) index into a per-chunk
    window of ``W`` rows: because the stream is row-major, the distinct
    rows of a chunk are consecutive nonempty-row ranks, so slot ``w`` of
    chunk ``c`` is global row ``row_map[c, w]`` (or the sentinel ``n``
    past the window's last real row).  ``W`` is the widest chunk's row
    span, rounded up to a multiple of 8 for the output tile.

    Unlike the CSR packing there is no per-tile padding: total padding is
    under one chunk regardless of degree skew.

    ``cols`` are stored at ``index_dtype``.  Row-split columns are
    *global* (the kernel holds all of B resident), so int16 is only legal
    when ``n`` itself fits — checked here; ``row_map`` stays int32 (it is
    epilogue metadata, not per-nonzero traffic).
    """
    if chunk > MAX_ROW_TILE:
        raise ValueError(f"chunk must be <= {MAX_ROW_TILE} (int8 window "
                         f"slots), got {chunk}")
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    data = np.asarray(data)
    index_extent_check(n, index_dtype)
    nnz = int(indptr[-1])
    rows = np.repeat(np.arange(n, dtype=np.int64),
                     np.diff(indptr).astype(np.int64))
    num_chunks = max(1, -(-nnz // chunk))
    padded = num_chunks * chunk
    # Rank each nonzero's row among the nonempty rows (ascending).
    nonempty = np.flatnonzero(np.diff(indptr) > 0).astype(np.int64)
    ranks = np.searchsorted(nonempty, rows)
    ranks_p = np.zeros(padded, dtype=np.int64)
    ranks_p[:nnz] = ranks
    ranks_p[nnz:] = ranks_p[nnz - 1] if nnz else 0
    ranks_c = ranks_p.reshape(num_chunks, chunk)
    rank_lo = ranks_c[:, 0]
    slots = ranks_c - rank_lo[:, None]
    span = int((slots.max() + 1)) if nnz else 1
    window = max(8, -(-span // 8) * 8)
    # Global row per (chunk, window slot); sentinel n past the last rank.
    flat = rank_lo[:, None] + np.arange(window)[None, :]
    row_map = np.where(flat < nonempty.shape[0],
                       nonempty[np.minimum(flat, nonempty.shape[0] - 1)]
                       if nonempty.shape[0] else 0,
                       n).astype(np.int32)
    if nonempty.shape[0] == 0:
        row_map[:] = n
    pos = np.arange(nnz)
    return (row_map, *scatter_chunks(
        pos, num_chunks, chunk, (indices[:nnz], index_dtype),
        (slots.reshape(-1)[:nnz], np.int8), (data[:nnz], data.dtype)))


def _rowsplit_kernel(cols_ref, slots_ref, vals_ref, b_ref, o_ref, gbuf, *,
                     window: int):
    """One grid step: reduce one equal-nnz chunk into its row window."""
    c = pl.program_id(1)
    gather_from_vmem(c, cols_ref, b_ref, gbuf)
    # Each chunk owns its window block exclusively: one write, no
    # accumulation, no zeroing predicate.
    o_ref[...] = chunk_product(c, slots_ref, vals_ref, gbuf, window)


@functools.partial(jax.jit,
                   static_argnames=("n", "window", "block_d", "vmem_limit",
                                    "interpret"))
def rowsplit_spmm_pallas(row_map: jnp.ndarray, cols: jnp.ndarray,
                         row_slots: jnp.ndarray, vals: jnp.ndarray,
                         b: jnp.ndarray, *, n: int, window: int,
                         block_d: int, vmem_limit: int,
                         interpret: bool) -> jnp.ndarray:
    """C = A @ B with A as equal-nnz chunks (pack_rowsplit_chunks).

    Args:
      row_map:    [C, W] int32 global row per window slot (n = sentinel).
      cols:       [C / p, p, chunk] global columns (int32 or int16).
      row_slots:  [C / p, p, chunk] int8 window slot per nonzero.
      vals:       [C / p, p, chunk] values, zero-padded (see
                  ``csr_spmm.scatter_chunks``).
      b:          [n, d] dense operand (held whole; the row-split kernel
                  trades B residency for perfect load balance).
      n:          matrix dimension (static).
      window:     W, the widest chunk's row span (static, multiple of 8).
      block_d:    d-tile width (static).
      vmem_limit: scoped VMEM the kernel may use, in bytes (static).
      interpret:  run in Pallas interpret mode (the CPU test path).
    """
    out_dtype = b.dtype
    b = b.astype(jnp.float32)
    d = b.shape[1]
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} must be divisible by the d-tile {bd}")
    if b.shape[0] % 8 != 0:
        pad = 8 - b.shape[0] % 8
        b = jnp.concatenate([b, jnp.zeros((pad, d), b.dtype)])
    num_chunks, chunk = row_map.shape[0], cols.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(d // bd, num_chunks),
        in_specs=[
            chunk_spec(cols, lambda i_d, i_c: i_c // cols.shape[1],
                       smem=True),
            chunk_spec(row_slots, lambda i_d, i_c: i_c // row_slots.shape[1]),
            chunk_spec(vals, lambda i_d, i_c: i_c // vals.shape[1]),
            pl.BlockSpec((b.shape[0], bd), lambda i_d, i_c: (0, i_d)),
        ],
        out_specs=pl.BlockSpec((window, bd), lambda i_d, i_c: (i_c, i_d)),
        scratch_shapes=[pltpu.VMEM((chunk, bd), jnp.float32)],
    )
    partials = pl.pallas_call(
        functools.partial(_rowsplit_kernel, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_chunks * window, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="rowsplit_spmm",
    )(cols, row_slots, vals, b)
    # Epilogue: scatter windows to global rows; sentinel n is dropped.
    out = jax.ops.segment_sum(partials, row_map.reshape(-1),
                              num_segments=n + 1)
    return out[:n].astype(out_dtype)

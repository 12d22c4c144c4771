"""Pallas TPU kernel: CSR row-gather / segment-sum SpMM with B left in HBM.

TPU realization of the paper's CSR baseline (the random-regime
implementation): every nonzero gathers its row of B and the products are
segment-summed by destination row.  The kernel tiles that traversal so the
segment sum becomes an MXU matmul:

  * rows are grouped into tiles of ``row_tile`` rows; each tile's nonzeros
    are padded to whole chunks of ``chunk`` entries (sliced-ELL style
    packing of the CSR arrays, built host-side by ``csr_to_row_tiles``);
  * one grid step owns one row tile's C block and reduces the tile's
    chunks (their range arrives via scalar prefetch, one int per tile:
    SMEM holds 1 MiB, too little for one int per chunk at n = 2**20);
  * per chunk, the ``chunk`` rows of B its column ids name are DMA'd
    straight from HBM into a VMEM gather slot, and one
    ``[row_tile, chunk] @ [chunk, bd]`` matmul — its left operand the
    value-weighted one-hot of the nonzeros' row slots — reduces them into
    the tile.

B never has to fit VMEM: only the two ``[chunk, bd]`` gather slots are
resident, so the layout needs no B slabs and column ids stay global.

The chunk loop is a software pipeline over the global chunk sequence
(every tile's chunks in tile order, ``tile_starts[T]`` in all), because a
chunk run in series pays its metadata round trip, the latency of its
slowest row DMA and its matmul back to back:

  * two gather slots, each with its own DMA semaphore: while chunk ``c``
    is waited on and reduced from slot ``c % 2``, the row DMAs of chunk
    ``c + 1`` are already issued into the other;
  * metadata further ahead: chunk ``c + 2``'s column ids are fetched into
    SMEM (two entries) while chunk ``c + 1``'s rows are issued, and
    ``c + 1``'s row slots and values (two VMEM entries each) follow its
    rows;
  * one wait per chunk: DMA semaphores count bytes, so a wait on a
    descriptor the size of the whole slot covers its ``chunk`` row copies;
  * across tiles: the pipeline's state lives in scratch and both grid
    axes run in order (``arbitrary``), so the last chunk of a tile
    requests the first chunk of the next nonempty tile, however many
    empty tiles lie between; only the first chunk of each d-pass is
    requested cold (``pipeline_counts``);
  * every DMA started is waited on: a chunk requests the next only if
    ``c + 1 < tile_starts[T]``, so the last chunk of a d-pass drains the
    pipeline, and the all-empty matrix's one padding chunk, which no tile
    owns, is never fetched.

B is passed as ``[d / bd, n, bd]`` so that a row DMA takes whole rows of
one d-pass's slice (a free reshape at one pass, a copy of B at several).

The gathered rows are fp32 (B is upcast before the call): Mosaic cannot
address single rows of a packed bf16 tile.  bf16 precisions still store
values at bf16; accumulation is fp32 throughout, and the matmuls run at
``Precision.HIGHEST`` so fp32 operands are not rounded to bf16 on the MXU.

Index and value chunks are ``[C / p, p, chunk]`` arrays with ``p`` chunks
per 32-bit sublane row (``chunks_per_row``), so each chunk is fetched by
one tile-aligned DMA at its storage width; row slots are int8
(``row_tile <= 128``).  Padding slots carry value 0 and slot 0, so they
contribute nothing; every tile's C block is zeroed before its chunks, so
empty rows come out zero.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Row slots are stored int8, so an output tile holds at most 128 rows.
MAX_ROW_TILE = 128


def index_extent_check(extent: int, index_dtype) -> None:
    """Refuse an index dtype that cannot address ``extent`` positions.

    The packers reserve sentinel values equal to the extent itself, so
    the extent — not ``extent - 1`` — must be representable (an extent of
    exactly ``2**15`` is illegal for int16).
    """
    if np.dtype(index_dtype) == np.int16 and extent > 2 ** 15 - 1:
        raise ValueError(
            f"int16 indices cannot address extent {extent} "
            f"(max {2 ** 15 - 1} including the sentinel slot)")


def pack_chunks(group_of_nz: np.ndarray, num_groups: int, chunk: int
                ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Place a group-sorted nonzero stream into whole chunks per group.

    ``group_of_nz`` is non-decreasing; group ``g`` gets
    ``ceil(count / chunk)`` chunks.  Returns ``(starts[G + 1], pos[nnz],
    num_chunks)``: group ``g`` owns chunks ``[starts[g], starts[g + 1])``
    and each nonzero's slot in the flattened ``[C * chunk]`` layout.  An
    empty stream still gets one (all-padding) chunk so every array has a
    well-formed shape.
    """
    counts = np.bincount(group_of_nz, minlength=num_groups)
    starts = np.concatenate([[0], np.cumsum(-(-counts // chunk))])
    first_nz = np.concatenate([[0], np.cumsum(counts)])
    rank = np.arange(group_of_nz.shape[0]) - first_nz[group_of_nz]
    pos = starts[group_of_nz] * chunk + rank
    return starts.astype(np.int32), pos, max(1, int(starts[-1]))


def chunks_per_row(dtype) -> int:
    """Chunks stored per sublane row of a packed chunk array.

    A 32-bit sublane holds one element of a 32-bit dtype, two of a 16-bit
    and four of an 8-bit one.  Storing ``[C / p, p, chunk]`` makes one
    chunk-row a whole HBM tile, so a chunk is fetched with one aligned DMA
    and a narrow dtype is not padded out to 32 bits.
    """
    return max(1, 4 // np.dtype(dtype).itemsize)


def scatter_chunks(pos: np.ndarray, num_chunks: int, chunk: int,
                   *arrays: Tuple[np.ndarray, object]):
    """Zero-filled ``[C / p, p, chunk]`` arrays with ``values`` at ``pos``.

    Chunk ``c`` of each array is row ``c % p`` of its entry ``c // p``
    (``p = chunks_per_row(dtype)``).
    """
    out = []
    for values, dtype in arrays:
        p = chunks_per_row(dtype)
        rows = -(-num_chunks // p)
        flat = np.zeros(rows * p * chunk, dtype=dtype)
        flat[pos] = values
        out.append(flat.reshape(rows, p, chunk))
    return out


def csr_to_row_tiles(indptr: np.ndarray, indices: np.ndarray,
                     data: np.ndarray, *, n: int, row_tile: int = 32,
                     chunk: int = 128, index_dtype=np.int32
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Pack CSR arrays into fixed-size chunks grouped by row tile.

    Returns ``(tile_starts[T + 1], cols, row_slots, vals)``, the last
    three chunk arrays as ``scatter_chunks`` lays them out: row tile ``t``
    owns chunks
    ``[tile_starts[t], tile_starts[t + 1])`` (none for an empty tile),
    ``cols`` are global column ids of B at ``index_dtype`` and
    ``row_slots`` (int8) are row indices *within* the tile.  Padding is
    under one chunk per row tile.
    """
    if not 0 < row_tile <= MAX_ROW_TILE:
        raise ValueError(f"row_tile must be in [1, {MAX_ROW_TILE}], "
                         f"got {row_tile}")
    indptr = np.asarray(indptr, dtype=np.int64)
    nnz = int(indptr[-1])
    index_extent_check(n, index_dtype)
    num_tiles = (n + row_tile - 1) // row_tile
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    tiles = rows // row_tile
    starts, pos, num_chunks = pack_chunks(tiles, num_tiles, chunk)
    cols, slots, vals = scatter_chunks(
        pos, num_chunks, chunk,
        (np.asarray(indices)[:nnz], index_dtype),
        (rows - tiles * row_tile, np.int8),
        (np.asarray(data)[:nnz], np.asarray(data).dtype))
    return starts, cols, slots, vals


def mxu_precision(dtype):
    """``HIGHEST`` for fp32 operands, else the default: an fp32 matmul at
    default precision rounds its operands to bf16 on the MXU, and Mosaic
    refuses ``HIGHEST`` on operands that already are bf16."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 \
        else jax.lax.Precision.DEFAULT


def chunk_row(ref, c) -> jnp.ndarray:
    """Chunk ``c``'s ``[1, chunk]`` row of a loaded ``[1, p, chunk]`` entry,
    widened to 32 bits (static slices of the widened tile, then a select:
    Mosaic cannot address one row of a packed tile dynamically)."""
    p = ref.shape[1]
    wide = jnp.float32 if jnp.issubdtype(ref.dtype, jnp.floating) \
        else jnp.int32
    full = ref[0].astype(wide)                                 # [p, chunk]
    row = full[0:1]
    for i in range(1, p):
        row = jnp.where(c % p == i, full[i:i + 1], row)
    return row


def chunk_product(c, slots_ref, vals_ref, gbuf, rows: int) -> jnp.ndarray:
    """``[rows, bd]`` partial of chunk ``c``: value-weighted one-hot @ rows.

    ``w[r, j] = vals[j]`` where ``slots[j] == r``: the segment sum by row
    slot expressed as one MXU matmul over the gathered rows in ``gbuf``.
    """
    slots = chunk_row(slots_ref, c)                          # [1, chunk]
    vals = chunk_row(vals_ref, c)                            # [1, chunk]
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, slots.shape[1]), 0)
    w = jnp.where(slot_ids == slots, vals, 0.0)
    return jnp.dot(w, gbuf[...], precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def chunk_col(cols_ref, c, j) -> jnp.ndarray:
    """Column id ``j`` of chunk ``c`` from its SMEM ``[1, p, chunk]`` entry."""
    return cols_ref[0, c % cols_ref.shape[1], j].astype(jnp.int32)


def gather_from_vmem(c, cols_ref, b_ref, gbuf) -> None:
    """``gbuf[j] = b_ref[cols[j]]`` from a VMEM-resident block of B."""
    def body(j, carry):
        gbuf[pl.ds(j, 1), :] = b_ref[pl.ds(chunk_col(cols_ref, c, j), 1), :]
        return carry
    jax.lax.fori_loop(0, gbuf.shape[0], body, 0)


def chunk_spec(array, index_map, smem: bool = False) -> pl.BlockSpec:
    """BlockSpec of one ``[1, p, chunk]`` entry of a packed chunk array;
    ``index_map`` gives the entry index."""
    shape = (1,) + tuple(array.shape[1:])
    full = lambda *idx: (index_map(*idx), 0, 0)   # noqa: E731
    if smem:
        return pl.BlockSpec(shape, full, memory_space=pltpu.SMEM)
    return pl.BlockSpec(shape, full)


def chunk_scratch(cols, slots, vals, entries: int = 1) -> list:
    """Scratch for ``entries`` entries each of the columns (SMEM), slots
    and values."""
    return [pltpu.SMEM((entries,) + tuple(cols.shape[1:]), cols.dtype),
            pltpu.VMEM((entries,) + tuple(slots.shape[1:]), slots.dtype),
            pltpu.VMEM((entries,) + tuple(vals.shape[1:]), vals.dtype)]


def load_chunk(c, hbm_refs, bufs, sems) -> None:
    """DMA the entries holding chunk ``c`` of the (cols, slots, vals)."""
    copies = [pltpu.make_async_copy(
        src.at[pl.ds(c // src.shape[1], 1)], dst, sems.at[i])
        for i, (src, dst) in enumerate(zip(hbm_refs, bufs))]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()


def for_each_chunk(owner: int, starts_ref, body) -> None:
    """Run ``body(c)`` for the chunks ``[starts[owner], starts[owner+1])``."""
    def step(c, carry):
        body(c)
        return carry
    jax.lax.fori_loop(starts_ref[owner], starts_ref[owner + 1], step, 0)


#: DMA starts per iteration of the row-issue loop (Mosaic unrolls a
#: ``fori_loop`` only fully, so the loop body issues this many itself).
#: On a v5e at 128-slot chunks the pipelined kernel took 3.39 / 2.58 /
#: 2.25 / 2.15 / 2.09 / 2.06 / 2.05 / 2.43 us a chunk at 1 / 2 / 4 / 8 /
#: 16 / 32 / 64 / 128 starts per iteration.
ISSUE_UNROLL: int = 32

# Semaphore kinds, one of each per pipeline slot.
_ROWS, _COLS, _META = 0, 1, 2


def pipeline_counts(chunks: int, d: int, block_d: int) -> dict:
    """Static counts of one ``csr_spmm_pallas`` launch.

    ``chunks`` is ``tile_starts[-1]``.  Every d-pass reduces all of them;
    ``cold_chunks`` are those whose B rows were not requested while an
    earlier chunk reduced: the first of each d-pass.
    """
    passes = d // min(block_d, d)
    return {"chunks": passes * chunks,
            "cold_chunks": passes * min(chunks, 1)}


def _csr_kernel(starts_ref, cols_hbm, slots_hbm, vals_hbm, b_hbm, o_ref,
                cols_buf, slots_buf, vals_buf, gbuf, sems, *,
                row_tile: int):
    """One grid step: one row tile's C block, its chunks in a loop.

    ``reduce(c)`` finds chunk ``c``'s rows in flight in slot ``c % 2`` and
    the column ids of ``c + 1`` in flight into SMEM; it requests ``c + 1``
    (which fetches the ids of ``c + 2``) before it waits on and reduces
    ``c`` (see the module docstring).
    """
    tile = pl.program_id(1)
    b_pass = b_hbm.at[pl.program_id(0)]          # [n, bd]: this d-pass
    total = starts_ref[pl.num_programs(1)]
    chunk = gbuf.shape[1]
    unroll = math.gcd(ISSUE_UNROLL, chunk)
    o_ref[...] = jnp.zeros_like(o_ref)

    def entry(c, hbm, buf, kind):
        return pltpu.make_async_copy(hbm.at[pl.ds(c // hbm.shape[1], 1)],
                                     buf.at[pl.ds(c % 2, 1)],
                                     sems.at[c % 2, kind])

    def meta_copies(c):
        return [entry(c, slots_hbm, slots_buf, _META),
                entry(c, vals_hbm, vals_buf, _META)]

    def rows_copy(c, col, j):
        return pltpu.make_async_copy(
            b_pass.at[pl.ds(col, 1)],
            gbuf.at[c % 2, pl.ds(j, 1), :], sems.at[c % 2, _ROWS])

    def request(c):
        """Issue chunk ``c``'s B rows; its column ids are in flight."""
        entry(c, cols_hbm, cols_buf, _COLS).wait()

        @pl.when(c + 1 < total)
        def _():
            entry(c + 1, cols_hbm, cols_buf, _COLS).start()

        def issue(i, carry):
            for k in range(unroll):
                j = i * unroll + k
                col = cols_buf[c % 2, c % cols_buf.shape[1], j]
                rows_copy(c, col.astype(jnp.int32), j).start()
            return carry

        jax.lax.fori_loop(0, chunk // unroll, issue, 0)
        for cp in meta_copies(c):
            cp.start()

    @pl.when((tile == 0) & (total > 0))
    def _():                       # a d-pass starts: chunk 0 goes cold
        first = starts_ref[0]      # 0, traced: chunk 1 may not exist
        entry(first, cols_hbm, cols_buf, _COLS).start()
        request(first)

    def reduce(c):
        @pl.when(c + 1 < total)
        def _():
            request(c + 1)

        # DMA semaphores count bytes: one wait the size of the whole slot
        # covers all of its row copies.
        slot = gbuf.at[c % 2]
        pltpu.make_async_copy(slot, slot, sems.at[c % 2, _ROWS]).wait()
        for cp in meta_copies(c):
            cp.wait()
        o_ref[...] += chunk_product(c, slots_buf.at[pl.ds(c % 2, 1)],
                                    vals_buf.at[pl.ds(c % 2, 1)], slot,
                                    row_tile)

    for_each_chunk(tile, starts_ref, reduce)


@functools.partial(jax.jit,
                   static_argnames=("n", "row_tile", "block_d",
                                    "vmem_limit", "interpret"))
def csr_spmm_pallas(tile_starts: jnp.ndarray, cols: jnp.ndarray,
                    row_slots: jnp.ndarray, vals: jnp.ndarray,
                    b: jnp.ndarray, *, n: int, row_tile: int, block_d: int,
                    vmem_limit: int, interpret: bool) -> jnp.ndarray:
    """C = A @ B with A given as row-tiled CSR chunks (csr_to_row_tiles).

    Args:
      tile_starts: [T + 1] int32 first chunk of each row tile.
      cols:        [C / p, p, chunk] global column ids (int32 or int16).
      row_slots:   [C / p, p, chunk] int8 row index within the tile.
      vals:        [C / p, p, chunk] values, zero-padded (see
                   ``scatter_chunks`` for the ``p`` chunks per row).
      b:           [n, d] dense operand; stays in HBM.
      n:           matrix dimension (static).
      row_tile:    rows per C tile (static); must match the packing.
      block_d:     d-tile width (static).
      vmem_limit:  scoped VMEM the kernel may use, in bytes (static).
      interpret:   run in Pallas interpret mode (the CPU test path).
    """
    out_dtype = b.dtype
    b = b.astype(jnp.float32)
    d = b.shape[1]
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} must be divisible by the d-tile {bd}")
    # [d / bd, n, bd]: a row DMA takes whole rows of one d-pass's slice
    # (Mosaic refuses a one-row slice of a tiled HBM array that also
    # slices its columns).  A copy of B only when there are several passes.
    b = b.reshape(n, d // bd, bd).transpose(1, 0, 2)
    chunk = cols.shape[2]
    num_tiles = tile_starts.shape[0] - 1
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bd, num_tiles),
        in_specs=[hbm, hbm, hbm, hbm],
        out_specs=pl.BlockSpec(
            (row_tile, bd), lambda i_d, t, starts: (t, i_d)),
        scratch_shapes=chunk_scratch(cols, row_slots, vals, entries=2) + [
            pltpu.VMEM((2, chunk, bd), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 3))],
    )
    out = pl.pallas_call(
        functools.partial(_csr_kernel, row_tile=row_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles * row_tile, d),
                                       jnp.float32),
        # The pipeline carries DMAs from one grid step to the next, so
        # the steps run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="csr_spmm",
    )(tile_starts, cols, row_slots, vals, b)
    return out[:n].astype(out_dtype)

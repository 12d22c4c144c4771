"""Pallas TPU kernel: BCSR (block-compressed-sparse-row) SpMM.

TPU adaptation of the paper's CSB implementation.  A is stored as dense
t x t blocks sorted by (block row, block column); the kernel walks them
block row by block row, DMAs each A block and the matching t x bd tile of
B HBM->VMEM, and accumulates the row's C tile in VMEM with MXU matmuls.

Grid layout: ``(d_tiles, block_rows)``, one grid step per block row: the
step zeroes its C tile, multiplies the row's blocks into it in a
``fori_loop`` and the tile is written back once, when the step ends.  The
block-row pointer and the block columns arrive via scalar prefetch.

The block loop is a software pipeline over the blocks of a segment, in
order, because a block's own work (a ``[t, t] @ [t, bd]`` matmul) is too
short to hide the latency of the DMAs that bring it in:

  * a ring of ``ring_slots(t)`` slots, each holding one A block and one
    B tile with a DMA semaphore each: block ``k`` lands in slot
    ``k % slots`` and, before it is waited on, requests the block
    ``slots - g`` places on, into the slot of a block already multiplied
    (``g = blocks_per_matmul(t)``);
  * ``g`` blocks a matmul: the row's blocks are multiplied ``g`` at a
    time, then one at a time, each row group of their lane-packed blocks
    side by side against their B tiles stacked, so a matmul's
    contraction is ``g * t`` wide, up to ``MATMUL_K``;
  * across block rows: the ring's state lives in scratch and both grid
    axes run in order (``arbitrary``), so the last blocks of row ``r``
    request the first blocks of row ``r + 1``; only the first matmul of
    each segment's d-pass waits on DMAs with nothing to hide them
    (``ring_counts``);
  * every DMA started is waited on: a block is requested only if it is
    in the segment, so the last blocks of a d-pass drain the ring.

B is passed as ``[d / bd, n, bd]`` so that a B-tile DMA takes whole rows
of one d-pass's slice (a free reshape at one pass, a copy of B at
several).

Lane-packed blocks: HBM arrays are tiled 128 lanes wide, so a ``[N, 64,
64]`` float32 array would take twice its bytes (and XLA would copy it into
that padded layout on every call).  A block of edge ``t < 128`` is stored
as ``[t / q, q * t]`` with ``q = lane_rows(t)``: row ``r`` of the stored
block holds block rows ``r, r + t/q, ...`` side by side, and the kernel
takes one matmul per group of ``t / q`` rows (``pack_blocks``).

Segments: the scalar-prefetched coordinates take at most 8 bytes a block
in SMEM (a column, and a row pointer entry: every block row owns a
block), so a large operator (284k blocks at 943k rows) cannot prefetch
them all.  ``bcsr_segments`` cuts the sorted block list at block-row
boundaries into segments whose coordinates fit a budget; each segment is
one ``pallas_call`` over its own block rows of C (``segment_rows``), and
the calls write into one C buffer (``input_output_aliases``), so no second
C is made.

VMEM working set: the ring's A blocks (t*t at the value width) and B
tiles (t*bd), plus the fp32 t x bd C tile, which the output pipeline
double-buffers.  The matmul runs at ``Precision.HIGHEST`` so fp32 blocks are not
rounded to bf16 on the MXU.
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


def segment_rows(block_ptr, segments) -> Tuple[Tuple[int, int], ...]:
    """The block rows ``(first, end)`` of each ``(start, end)`` block range
    of ``segments`` (``bcsr_segments``): together they cover every block
    row once, a block row without blocks going to the segment after it
    (or to the last)."""
    ptr = np.asarray(block_ptr, dtype=np.int64)
    cuts = [0] + [int(np.searchsorted(ptr, s)) for s, _ in segments[1:]] \
        + [ptr.shape[0] - 1]
    return tuple(zip(cuts[:-1], cuts[1:]))


#: Slots of the block ring (at least): each holds one A block and one B
#: tile.  On a v5e, rings of 8 to 32 slots take the same time.
RING: int = 32
#: The contraction width of the kernel's matmuls: ``MATMUL_K // t``
#: consecutive blocks of a row are multiplied at a time.  On a v5e at
#: t = 64, a block costs 0.257 us one to a matmul, 0.167 two, 0.128 four.
MATMUL_K: int = 256


def blocks_per_matmul(t: int) -> int:
    """Blocks of edge ``t`` that one matmul takes side by side: enough to
    fill ``MATMUL_K``, at most 8 (the loop body unrolls a DMA wait and a
    slice per block and row group, which grow as ``t`` shrinks)."""
    return max(1, min(8, MATMUL_K // t))


def ring_slots(t: int) -> int:
    """Slots of the ring at block edge ``t``: room for the blocks being
    multiplied and as many in flight."""
    return max(RING, 2 * blocks_per_matmul(t))


def d_passes(d: int, block_d: int) -> Tuple[int, int]:
    """``(width, bd)``: the kernel runs B at ``width`` columns in d-passes
    of ``bd``.  A B-tile DMA must take whole 128-lane tiles (Mosaic
    refuses a narrower one, even B's full width), so a d-tile narrower
    than that runs as B padded with zero columns to whole tiles; HBM
    stores such a B padded to 128 lanes anyway."""
    bd = min(block_d, d)
    if d % bd != 0:
        raise ValueError(f"d={d} must be divisible by the d-tile {bd}")
    if bd % LANES:
        return -(-d // LANES) * LANES, LANES
    return d, bd


def ring_counts(block_ptr, rows, t: int) -> dict:
    """Static counts of one d-pass of ``bcsr_spmm_pallas`` over the
    segments' block ``rows`` (``segment_rows``).

    The pass visits every stored block (``blocks``) in one grid step per
    block row (``steps``); ``cold_blocks`` are those waited on with no
    earlier block's matmul to hide their DMAs: each segment's first
    matmul, ``blocks_per_matmul(t)`` blocks, or one where the first row
    that holds blocks has fewer (they are then multiplied one at a time).
    """
    ptr = np.asarray(block_ptr, dtype=np.int64)
    held = np.diff(ptr)
    group = blocks_per_matmul(t)
    cold = 0
    for first, end in rows:
        counts = held[first:end][held[first:end] > 0]
        if counts.size:
            cold += group if counts[0] >= group else 1
    return {"blocks": int(ptr[-1] - ptr[0]), "steps": ptr.shape[0] - 1,
            "cold_blocks": cold}


def _bcsr_kernel(ptr_ref, cols_ref, a_hbm, b_hbm, *refs, start: int,
                 group: int):
    """One grid step: one block row's C tile, its blocks in a loop.

    ``refs`` is ``(o_ref, a_buf, b_buf, sems)``, after ``c_hbm`` when the
    call writes into an earlier segment's C (aliased, never read here).
    Block ``k`` (a global index; ``cols_ref`` holds the segment's columns
    from ``start``) lands in ring slot ``k % slots``; before it is
    waited on, it requests block ``k + slots - group``.  A row's blocks are
    multiplied ``group`` at a time, then one at a time: each row group of
    the lane-packed blocks takes one matmul, the blocks' groups side by
    side against their B tiles stacked.
    """
    o_ref, a_buf, b_buf, sems = refs[-4:]
    row = pl.program_id(1)
    end = ptr_ref[pl.num_programs(1)]
    b_pass = b_hbm.at[pl.program_id(0)]          # [n, bd]: this d-pass
    slots, h, w = a_buf.shape
    t = b_buf.shape[1]
    lead = slots - group

    def request(k):
        slot = k % slots
        col = cols_ref[k - start]
        pltpu.make_async_copy(a_hbm.at[k], a_buf.at[slot],
                              sems.at[slot, 0]).start()
        pltpu.make_async_copy(b_pass.at[pl.ds(col * t, t)], b_buf.at[slot],
                              sems.at[slot, 1]).start()

    def multiply(ks):
        """Multiply blocks ``ks`` into the C tile, once their DMAs land;
        first request each one's successor ``lead`` blocks on, into a
        slot whose block is done."""
        for k in ks:
            @pl.when(k + lead < end)
            def _():
                request(k + lead)
        held = [k % slots for k in ks]
        for slot in held:
            for i, buf in enumerate((a_buf.at[slot], b_buf.at[slot])):
                pltpu.make_async_copy(buf, buf, sems.at[slot, i]).wait()
        a = [a_buf[slot] for slot in held]               # [t / q, q * t]
        b = jnp.concatenate([b_buf[slot] for slot in held], axis=0)
        for p in range(w // t):
            rows = jnp.concatenate([x[:, p * t:(p + 1) * t] for x in a],
                                   axis=1)
            o_ref[p * h:(p + 1) * h, :] += jnp.dot(
                rows, b, precision=mxu_precision(rows.dtype),
                preferred_element_type=jnp.float32)

    @pl.when(row == 0)
    def _():                       # a d-pass starts: its first blocks go cold
        first = ptr_ref[0]

        def prime(k, carry):
            request(k)
            return carry

        jax.lax.fori_loop(first, jnp.minimum(first + lead, end), prime, 0)

    o_ref[...] = jnp.zeros_like(o_ref)
    lo, hi = ptr_ref[row], ptr_ref[row + 1]
    whole = (hi - lo) // group

    def groups(j, carry):
        multiply([lo + j * group + i for i in range(group)])
        return carry

    def singles(k, carry):
        multiply([k])
        return carry

    jax.lax.fori_loop(0, whole, groups, 0)
    if group > 1:
        jax.lax.fori_loop(lo + whole * group, hi, singles, 0)


def _segment(blocks, ptr, cols, b, c, *, start: int, first_row: int,
             t: int, vmem_limit: int, interpret: bool):
    """One ``pallas_call`` over the block rows ``[first_row, first_row +
    ptr.size - 1)``, whose blocks start at ``start``, writing their C
    tiles into ``c`` (or a new C when None)."""
    passes, n, bd = b.shape
    slots = ring_slots(t)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [ptr, cols, blocks, b]
    in_specs = [hbm, hbm]
    aliases = {}
    if c is not None:
        in_specs.append(hbm)
        aliases = {len(operands): 0}
        operands.append(c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(passes, ptr.shape[0] - 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (t, bd), lambda i_d, r, ptr, cols: (first_row + r, i_d)),
        scratch_shapes=[pltpu.VMEM((slots,) + blocks.shape[1:], blocks.dtype),
                        pltpu.VMEM((slots, t, bd), b.dtype),
                        pltpu.SemaphoreType.DMA((slots, 2))],
    )
    return pl.pallas_call(
        functools.partial(_bcsr_kernel, start=start,
                          group=blocks_per_matmul(t)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, passes * bd), jnp.float32),
        input_output_aliases=aliases,
        # The ring carries DMAs from one grid step to the next, so the
        # steps run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="bcsr_spmm",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("n", "t", "block_d",
                                             "vmem_limit", "interpret",
                                             "segments", "rows"))
def bcsr_spmm_pallas(blocks: jnp.ndarray, block_ptr: jnp.ndarray,
                     block_cols: jnp.ndarray, b: jnp.ndarray, *, n: int,
                     t: int, block_d: int, vmem_limit: int,
                     interpret: bool,
                     segments: Optional[Tuple[Tuple[int, int], ...]] = None,
                     rows: Optional[Tuple[Tuple[int, int], ...]] = None
                     ) -> jnp.ndarray:
    """C = A @ B with A given as sorted nonzero blocks.

    Args:
      blocks:     [N, t/q, q*t] lane-packed block values (``pack_blocks``),
                  sorted by (block_row, col).
      block_ptr:  [n/t + 1] int32 first block of each block row (a block
                  row without blocks comes out zero).
      block_cols: [N] int32 block-col ids.
      b:          [n, d] dense operand.
      n, t:       matrix dim and block edge (static).
      block_d:    d-tile width (static, MXU-aligned).
      vmem_limit: scoped VMEM the kernel may use, in bytes (static).
      interpret:  run in Pallas interpret mode (the CPU test path).
      segments:   ``(start, end)`` block ranges cut at block-row boundaries
                  (``bcsr_segments``), one ``pallas_call`` each; None runs
                  all blocks in one call.
      rows:       the block rows of each segment (``segment_rows``); given
                  with ``segments``.
    """
    d = b.shape[1]
    width, bd = d_passes(d, block_d)
    if blocks.shape[1:] != (t // lane_rows(t), lane_rows(t) * t):
        raise ValueError(f"blocks {blocks.shape} are not lane-packed for "
                         f"t={t} (see pack_blocks)")
    if (segments is None) != (rows is None):
        raise ValueError("segments and rows are given together")
    if segments is None:
        segments = ((0, blocks.shape[0]),)
        rows = ((0, block_ptr.shape[0] - 1),)
    # [width / bd, n, bd]: a B-tile DMA takes whole rows of one d-pass's
    # slice (a free reshape at one pass, a copy of B at several).
    out_dtype = b.dtype
    b = jnp.pad(b, ((0, 0), (0, width - d)))
    b = b.reshape(n, width // bd, bd).transpose(1, 0, 2)
    out = None
    for (start, end), (r0, r1) in zip(segments, rows):
        out = _segment(blocks, block_ptr[r0:r1 + 1], block_cols[start:end],
                       b, out, start=start, first_row=r0, t=t,
                       vmem_limit=vmem_limit, interpret=interpret)
    return out[:, :d].astype(out_dtype)

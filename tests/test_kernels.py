"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels, sparse
from repro.core import banded as gen_banded
from repro.core import blocked as gen_blocked
from repro.core import erdos_renyi
from repro.kernels import ref, registry
from repro.kernels.banded_spmm import banded_spmm_pallas
from repro.kernels.bcsr_spmm import bcsr_spmm_pallas, pack_blocks
from repro.kernels.binned_spmm import (binned_spmm_pallas, csr_to_slab_bins,
                                       pack_rowsplit_chunks,
                                       rowsplit_spmm_pallas)
from repro.kernels.csr_spmm import csr_spmm_pallas, csr_to_row_tiles
from repro.kernels.grouped_matmul import grouped_matmul_pallas

# The sweeps below pack each layout themselves and call the ``*_pallas``
# kernel, so they reach packing knobs (row_tile, chunk, b_tile, block_d)
# the registry derives itself; the registry path is covered by
# test_registry / test_differential.

RNG = np.random.default_rng(0)


def _b(n, d, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=(n, d))).astype(dtype)


def _launch():
    """What every kernel takes from its caller: this device's scoped
    VMEM limit, and interpret mode on the CPU backend."""
    ctx = registry.KernelContext()
    return {"vmem_limit": ctx.vmem_limit,
            "interpret": ctx.resolve_interpret()}


def _device(arrays):
    return [jnp.asarray(x) for x in arrays]


@pytest.mark.parametrize("t", [16, 32])
@pytest.mark.parametrize("d,block_d", [(16, 16), (64, 32), (128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bcsr_kernel_sweep(t, d, block_d, dtype):
    n = 8 * t
    m = gen_blocked(n, t=t, num_blocks=20, nnz_per_block=3 * t, seed=t + d)
    a = sparse.coo_to_bcsr(m, t, dtype=jnp.float32)
    b = _b(n, d, dtype)
    p = kernels.pad_empty_block_rows(a)
    out = bcsr_spmm_pallas(pack_blocks(p.blocks, t), p.block_ptr,
                           p.block_cols, b, n=n, t=t, block_d=block_d,
                           **_launch())
    expect = ref.bcsr_ref(np.asarray(a.blocks), a.block_rows, a.block_cols,
                          b, n=n, t=t)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_bcsr_kernel_empty_rows_padded():
    """Block rows with no nonzero blocks must still produce zero C tiles."""
    t, n = 16, 128
    m = gen_blocked(n, t=t, num_blocks=2, nnz_per_block=20, seed=3)
    a = sparse.coo_to_bcsr(m, t)
    b = _b(n, 8)
    p = kernels.pad_empty_block_rows(a)
    out = bcsr_spmm_pallas(pack_blocks(p.blocks, t), p.block_ptr,
                           p.block_cols, b, n=n, t=t, block_d=8, **_launch())
    expect = ref.bcsr_ref(np.asarray(a.blocks), a.block_rows, a.block_cols,
                          b, n=n, t=t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


# --------------------------------------------------------------------- #
# The block-row BCSR kernel: one grid step per block row, the row's blocks
# streamed through a ring of RING slots that runs across block rows.
# --------------------------------------------------------------------- #

#: Stored blocks in each block row (a 0 is padded with a zero diagonal
#: block by ``pack_bcsr``) and the blocks a segment may hold (None: one).
#: At t = 16 the kernel multiplies 8 blocks at a time, then one at a
#: time, through a ring of 32: rows of fewer, exactly 8, groups of 8 and
#: a remainder, a full ring and more than it holds.
RAGGED = [1, 7, 3, 35, 8, 2, 17, 0, 32, 4, 33] + [1] * 25
BLOCK_ROWS = {
    "ragged": (RAGGED, None),
    "rows-of-one-block": ([1] * 8, None),
    "padded-empty-rows": ([0, 3, 0, 6, 0, 0, 2, 0], None),
    "segments": (RAGGED, 60),
    "segments-of-one-row": ([2, 5, 0, 6, 1, 0], 6),
}


def _block_row_matrix(per_row, t, seed):
    """COO arrays with ``per_row[r]`` distinct t x t blocks in block row
    ``r``, each holding ``2 t`` random nonzeros."""
    rng = np.random.default_rng(seed)
    nb = len(per_row)
    rows, cols = [], []
    for r, k in enumerate(per_row):
        for c in rng.choice(nb, k, replace=False):
            rows.append(r * t + rng.integers(0, t, 2 * t))
            cols.append(c * t + rng.integers(0, t, 2 * t))
    rows = np.concatenate(rows or [np.zeros(0, np.int64)])
    cols = np.concatenate(cols or [np.zeros(0, np.int64)])
    lin = np.unique(rows * nb * t + cols)
    return lin // (nb * t), lin % (nb * t), rng.uniform(0.5, 1.5, lin.size)


def _ring_bcsr(structure, precision, d, block_d, interpret=True, t=16):
    """The BCSR kernel on ``structure`` against the float64 product of the
    stored (precision-rounded) values and B; returns the packed
    ``(block_ptr, segments, rows)``."""
    from repro.core.precision import as_precision
    from repro.kernels.bcsr_spmm import (bcsr_segments, pack_bcsr,
                                         segment_rows)
    per_row, cap = BLOCK_ROWS[structure]
    n = len(per_row) * t
    prec = as_precision(precision)
    rows, cols, vals = _block_row_matrix(per_row, t, seed=len(structure))
    stored = np.asarray(jnp.asarray(vals, prec.value_jnp)).astype(np.float64)
    blocks, _, bcols, ptr = pack_bcsr(rows, cols, vals, n=n, t=t,
                                      dtype=prec.value_jnp)
    segments = bcsr_segments(ptr, cap or blocks.shape[0])
    seg_rows = segment_rows(ptr, segments)
    b = _b(n, d, prec.value_jnp)
    out = bcsr_spmm_pallas(jnp.asarray(blocks), jnp.asarray(ptr),
                           jnp.asarray(bcols), b, n=n, t=t, block_d=block_d,
                           vmem_limit=16 << 20, interpret=interpret,
                           segments=segments, rows=seg_rows)
    b64 = np.asarray(b, np.float64)
    a64 = np.zeros((n, n))
    np.add.at(a64, (rows, cols), stored)
    bound = np.abs(a64) @ np.abs(b64)
    err = np.abs(np.asarray(out, np.float64) - a64 @ b64)
    # fp32 accumulation of exactly stored operands; an output in bf16
    # (the reduced precisions return B's dtype) rounds once more.
    eps = np.finfo(np.float32).eps * 64 if prec.value_jnp == jnp.float32 \
        else 2.0 ** -8
    assert out.shape == (n, d) and out.dtype == b.dtype
    assert np.all(err <= eps * bound), float(np.max(err - eps * bound))
    assert np.all(np.asarray(out)[bound == 0] == 0)
    return ptr, segments, seg_rows


#: ``(d, block_d)``: one d-pass, and two (B padded to 128-lane passes).
D_PASSES = {"one-d-pass": (8, 8), "two-d-passes": (256, 128)}
RING_CASES = [(s, p, "f32i32") for p in D_PASSES for s in BLOCK_ROWS] + [
    ("ragged", "two-d-passes", "bf16i32"),
    ("segments", "one-d-pass", "bf16i32")]


@pytest.mark.parametrize("structure,passes,precision", RING_CASES,
                         ids=["-".join(c) for c in RING_CASES])
def test_bcsr_kernel_ring_matches_f64_ref(structure, passes, precision):
    """Block rows of 1 block to more than the ring holds, padded empty
    rows and several segments, against the float64 reference; the
    segments cover every block row once."""
    from repro.kernels.bcsr_spmm import blocks_per_matmul, ring_slots
    assert blocks_per_matmul(16) in RAGGED
    assert max(max(p) for p, _ in BLOCK_ROWS.values()) > ring_slots(16)
    d, block_d = D_PASSES[passes]
    per_row, cap = BLOCK_ROWS[structure]
    ptr, segments, rows = _ring_bcsr(structure, precision, d, block_d)
    assert len(segments) > 1 if cap else len(segments) == 1
    assert rows[0][0] == 0 and rows[-1][1] == len(per_row)
    assert all(a[1] == b[0] for a, b in zip(rows, rows[1:]))
    assert [(int(ptr[a]), int(ptr[b])) for a, b in rows] == list(segments)


@pytest.mark.parametrize("mode,structure", [
    ("on_wait", "ragged"), ("on_wait", "segments"),
    ("eager", "padded-empty-rows"), ("eager", "segments-of-one-row")])
def test_bcsr_kernel_ring_waits_on_every_dma(mode, structure, capfd):
    """Under Pallas's TPU interpreter, as for the CSR kernel: ``on_wait``
    lands a DMA only when it is waited on, into scratch that starts as
    NaN, so a block read before its wait or a slot reused while its DMAs
    are in flight shows in the result; ``eager`` reports a DMA never
    waited on."""
    from jax.experimental.pallas import tpu as pltpu
    _ring_bcsr(structure, "f32i32", 256, 128,
               interpret=pltpu.InterpretParams(
                   dma_execution_mode=mode, uninitialized_memory="nan"))
    assert "non-zero count" not in capfd.readouterr().out


def test_bcsr_kernel_zeroes_block_rows_it_holds_no_block_for():
    """A block-row pointer with empty rows (no padding): their C tiles
    come out zero, at either end of a segment and inside one."""
    from repro.kernels.bcsr_spmm import (bcsr_segments, pack_bcsr,
                                         segment_rows)
    t, per_row = 16, [0, 3, 0, 0, 5, 0, 2, 0]
    n = len(per_row) * t
    rows, cols, vals = _block_row_matrix(per_row, t, seed=4)
    blocks, brows, bcols, _ = pack_bcsr(rows, cols, vals, n=n, t=t,
                                        dtype=jnp.float32)
    keep = np.asarray(per_row)[brows] > 0          # drop the padding
    ptr = np.concatenate([[0], np.cumsum(np.bincount(
        brows[keep], minlength=len(per_row)))]).astype(np.int32)
    segments = bcsr_segments(ptr, 8)
    assert segments == ((0, 8), (8, 10))
    assert segment_rows(ptr, segments) == ((0, 5), (5, 8))
    b = _b(n, 8)
    out = bcsr_spmm_pallas(jnp.asarray(blocks[keep]), jnp.asarray(ptr),
                           jnp.asarray(bcols[keep]), b, n=n, t=t,
                           block_d=8, vmem_limit=16 << 20, interpret=True,
                           segments=segments,
                           rows=segment_rows(ptr, segments))
    a = np.zeros((n, n))
    np.add.at(a, (rows, cols), vals.astype(np.float32))
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d,block_d,passes", [(8, 8, 1), (128, 128, 1),
                                              (1024, 512, 2),
                                              (192, 64, 2)])
def test_bcsr_ring_counts(d, block_d, passes):
    """Blocks, grid steps (block rows) and cold blocks of one d-pass: a
    segment's first matmul goes cold, ``blocks_per_matmul(t)`` blocks, or
    one where the first row that holds blocks has fewer (the rest are
    hidden behind it).  A d-tile narrower than 128 lanes runs as whole
    128-lane passes."""
    from repro.kernels.bcsr_spmm import d_passes, ring_counts
    ptr = np.cumsum([0, 5, 0, 2, 6])
    assert ring_counts(ptr, ((0, 2), (2, 4)), t=64) == {
        "blocks": 13, "steps": 4, "cold_blocks": 4 + 1}
    assert ring_counts(ptr, ((0, 1), (1, 3), (3, 4)),
                       t=64)["cold_blocks"] == 4 + 1 + 4
    assert ring_counts(ptr, ((0, 4),), t=128)["cold_blocks"] == 2
    width, bd = d_passes(d, block_d)
    assert width // bd == passes


@pytest.mark.parametrize("t", [16, 32, 64, 128])
def test_bcsr_vmem_footprint_is_the_ring(t):
    """The VMEM gate counts the ring's slots of an A block and a B tile
    (room for the blocks one matmul takes and as many in flight) and the
    double-buffered fp32 C tile; at bd = 512 it fits the v5e's kernel
    budget at every block edge."""
    from repro.core.hardware import TPU_V5E
    from repro.kernels.bcsr_spmm import blocks_per_matmul, ring_slots
    ctx = registry.KernelContext(hardware=TPU_V5E, bcsr_block=t)
    slots, bd = ring_slots(t), 512
    assert slots >= 2 * blocks_per_matmul(t)
    footprint = registry.get("bcsr", "pallas").vmem_footprint(1 << 20, 1024,
                                                              ctx)
    assert footprint == slots * 4 * (t * t + t * bd) + 2 * 4 * t * bd
    assert footprint <= ctx.vmem_limit


@pytest.mark.parametrize("pattern", ["er", "banded", "blocked", "powerlaw"])
@pytest.mark.parametrize("d,block_d", [(8, 8), (64, 32)])
def test_csr_kernel_sweep(pattern, d, block_d):
    from repro.core import scale_free
    n = 256
    gen = {
        "er": lambda: erdos_renyi(n, 6, seed=1),
        "banded": lambda: gen_banded(n, 3, seed=2),
        "blocked": lambda: gen_blocked(n, t=16, num_blocks=32,
                                       nnz_per_block=12, seed=3),
        "powerlaw": lambda: scale_free(n, 8, seed=4),
    }[pattern]
    m = gen()
    a = sparse.coo_to_csr(m)
    b = _b(n, d)
    tiles = csr_to_row_tiles(a.indptr, a.indices, a.data, n=n, row_tile=8,
                             chunk=32)
    out = csr_spmm_pallas(*_device(tiles), b, n=n, row_tile=8,
                          block_d=block_d, **_launch())
    expect = sparse.coo_to_dense(m) @ b
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("row_tile", [32, 64, 100])
def test_csr_kernel_streamed_b_matches_ref(row_tile):
    """B rows DMA'd from HBM into row tiles of any height (incl.
    n % row_tile != 0) match the oracle."""
    n = 256
    m = erdos_renyi(n, 6, seed=7)
    a = sparse.coo_to_csr(m)
    b = _b(n, 64)
    tiles = csr_to_row_tiles(a.indptr, a.indices, a.data, n=n,
                             row_tile=row_tile, chunk=32)
    out = csr_spmm_pallas(*_device(tiles), b, n=n, row_tile=row_tile,
                          block_d=32, **_launch())
    expect = ref.csr_ref(a.indptr, a.indices, a.data, b, n=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


def test_csr_kernel_streams_past_vmem():
    """n * bd * 4 exceeds the (shrunk) VMEM budget, so whole-B residency
    is impossible; the CSR kernel gathers B rows from HBM, so its layout
    carries no slab, its footprint does not grow with n, and the
    dispatcher's pallas path still matches the oracle."""
    import dataclasses
    from repro.core.hardware import TPU_V5E

    n, d = 1024, 64
    vmem = 192 * 1024
    assert n * d * 4 > vmem                  # whole B cannot be resident
    hw = dataclasses.replace(TPU_V5E, vmem_bytes=vmem)
    m = erdos_renyi(n, 8, seed=9)
    disp = sparse.Dispatcher(hardware=hw, backend="pallas",
                             calibration=False)
    plan = disp.plan(m, d, strategy="csr")
    run = disp.executor(m, plan)
    layout = next(v for k, v in disp._converted.items() if k[1] == "layout")
    assert "b_tile" not in layout
    spec = registry.get("csr", "pallas")
    ctx = registry.KernelContext(hardware=hw)
    assert spec.vmem_footprint(n, d, ctx) <= ctx.vmem_limit
    assert spec.vmem_footprint(1 << 22, d, ctx) == \
        spec.vmem_footprint(n, d, ctx)
    a = sparse.coo_to_csr(m)
    b = _b(n, d)
    expect = ref.csr_ref(a.indptr, a.indices, a.data, b, n=n)
    np.testing.assert_allclose(np.asarray(run(b)), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("pattern", ["er", "powerlaw"])
@pytest.mark.parametrize("b_tile", [None, 8, 100])
def test_binned_kernel_sweep(pattern, b_tile):
    """Slab-binned kernel across slab sizings, including b_tile=8 (the
    degenerate one-row-tile slab: maximum binning overhead) and a
    non-multiple-of-8 slab edge."""
    from repro.core import scale_free
    n = 256
    m = (erdos_renyi(n, 6, seed=11) if pattern == "er"
         else scale_free(n, 8, alpha=2.05, seed=12))
    a = sparse.coo_to_csr(m)
    b = _b(n, 64)
    bins = csr_to_slab_bins(a.indptr, a.indices, a.data, n=n,
                            row_tile=registry.ROW_TILE, chunk=32,
                            b_tile=b_tile)
    out = binned_spmm_pallas(*_device(bins), b, n=n,
                             row_tile=registry.ROW_TILE, b_tile=b_tile,
                             block_d=32, **_launch())
    expect = ref.csr_ref(a.indptr, a.indices, a.data, b, n=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


def test_binned_kernel_streams_past_vmem():
    """Mirror of the CSR acceptance case for the binned tier: with VMEM
    shrunk below whole-B residency the dispatcher's pallas path must bin
    into multiple B slabs and still match the oracle."""
    import dataclasses
    from repro.core.hardware import TPU_V5E

    n, d = 512, 64
    vmem = 256 * 1024
    hw = dataclasses.replace(TPU_V5E, vmem_bytes=vmem)
    # Dense enough that every (slab, row tile) visit fills its chunks:
    # the dispatcher's packing gate admits the layout.
    m = erdos_renyi(n, 32, seed=13)
    disp = sparse.Dispatcher(hardware=hw, backend="pallas",
                             calibration=False)
    plan = disp.plan(m, d, strategy="binned")
    run = disp.executor(m, plan)
    layout = next(v for k, v in disp._converted.items()
                  if k[1] == "layout")
    assert layout["b_tile"] is not None and layout["b_tile"] < n
    # visit_slabs is arrays[1]: >0 means the binning touched >1 B slab.
    assert int(np.asarray(layout["arrays"][1]).max()) > 0
    spec = registry.get("binned", "pallas")
    ctx = registry.KernelContext(hardware=hw)
    assert spec.vmem_footprint(n, d, ctx) <= ctx.vmem_limit
    a = sparse.coo_to_csr(m)
    b = _b(n, d)
    expect = ref.csr_ref(a.indptr, a.indices, a.data, b, n=n)
    np.testing.assert_allclose(np.asarray(run(b)), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


def test_binned_kernel_degenerate_bins():
    """Degenerate slab occupancies: all nonzeros in one slab (every
    other bin empty) and the all-zero matrix (one synthetic zero visit)."""
    from repro.core.patterns import COOMatrix
    n = 64
    # Hub column block: every nonzero lands in B rows [0, 8) — with
    # b_tile=8 exactly one of eight slabs is ever visited.
    rng = np.random.default_rng(5)
    rows = np.arange(n, dtype=np.int32)
    cols = rng.integers(0, 8, size=n).astype(np.int32)
    m = COOMatrix(n=n, rows=rows, cols=cols,
                  vals=np.ones(n, np.float32), pattern="hub_cols")
    a = sparse.coo_to_csr(m)
    b = _b(n, 16)

    def binned(a):
        bins = csr_to_slab_bins(a.indptr, a.indices, a.data, n=n,
                                row_tile=registry.ROW_TILE, chunk=32,
                                b_tile=8)
        return binned_spmm_pallas(*_device(bins), b, n=n,
                                  row_tile=registry.ROW_TILE, b_tile=8,
                                  block_d=16, **_launch())

    expect = ref.csr_ref(a.indptr, a.indices, a.data, b, n=n)
    np.testing.assert_allclose(np.asarray(binned(a)), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)

    empty = COOMatrix(n=n, rows=np.zeros(0, np.int32),
                      cols=np.zeros(0, np.int32),
                      vals=np.zeros(0, np.float32), pattern="empty")
    ae = sparse.coo_to_csr(empty)
    assert not np.any(np.asarray(binned(ae)))
    chunks = pack_rowsplit_chunks(ae.indptr, ae.indices, ae.data, n=n,
                                  chunk=32)
    outr = rowsplit_spmm_pallas(*_device(chunks), b, n=n,
                                window=int(chunks[0].shape[1]), block_d=16,
                                **_launch())
    assert not np.any(np.asarray(outr))


@pytest.mark.parametrize("chunk", [32, 128])
def test_rowsplit_kernel_skewed_rows(chunk):
    """Load-balance stress: one hub row with n nonzeros next to
    singleton rows — chunks must cross row boundaries correctly, and the
    epilogue must scatter windowed partials to the right rows."""
    from repro.core.patterns import COOMatrix
    n = 128
    rows = np.concatenate([np.full(n, 3), np.arange(n)]).astype(np.int32)
    cols = np.concatenate([np.arange(n), np.arange(n)]).astype(np.int32)
    vals = (1.0 + np.arange(2 * n)).astype(np.float32) / n
    m = COOMatrix(n=n, rows=rows, cols=cols, vals=vals, pattern="skew")
    a = sparse.coo_to_csr(m)
    b = _b(n, 32)
    chunks = pack_rowsplit_chunks(a.indptr, a.indices, a.data, n=n,
                                  chunk=chunk)
    out = rowsplit_spmm_pallas(*_device(chunks), b, n=n,
                               window=int(chunks[0].shape[1]), block_d=32,
                               **_launch())
    expect = ref.csr_ref(a.indptr, a.indices, a.data, b, n=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


def test_csr_kernel_empty_and_ragged_rows():
    """Empty rows still get zeroed C tiles; rows crossing chunk boundaries
    accumulate across grid steps."""
    n = 64
    rows = np.array([0] * 50 + [63] * 3)       # row 0 spans >1 chunk of 32
    cols = np.arange(53) % n
    from repro.core import COOMatrix
    m_coo = COOMatrix(
        n=n, rows=rows.astype(np.int32), cols=cols.astype(np.int32),
        vals=np.ones(53), pattern="custom")
    a = sparse.coo_to_csr(m_coo)
    b = _b(n, 8)
    tiles = csr_to_row_tiles(a.indptr, a.indices, a.data, n=n, row_tile=8,
                             chunk=32)
    out = csr_spmm_pallas(*_device(tiles), b, n=n, row_tile=8, block_d=8,
                          **_launch())
    expect = sparse.coo_to_dense(m_coo) @ b
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


# Row-tile structures for the pipelined CSR kernel (row_tile 8, chunk 32,
# n 128: 16 tiles).  Nonzeros per tile; a tile of k nonzeros owns
# ceil(k / 32) chunks.
PIPELINE_TILES = {
    "tile-chunk-counts": [0, 10, 40, 150, 32, 33, 0, 64, 1, 0, 0, 97, 5,
                          0, 2, 31],
    "empty-tiles-at-start": [0] * 6 + [12, 70, 3, 0, 40, 8, 1, 9, 33, 4],
    "empty-tiles-in-middle": [20, 45, 7] + [0] * 9 + [3, 66, 1, 30],
    "empty-tiles-at-end": [9, 100, 33, 2, 17] + [0] * 11,
    "single-chunk": [0] * 7 + [19] + [0] * 8,
    "all-empty": [0] * 16,
}


def _tiles_matrix(per_tile, seed, n=128, row_tile=8):
    """COO arrays with ``per_tile[t]`` nonzeros in row tile ``t`` (rows,
    columns and duplicates drawn at random)."""
    rng = np.random.default_rng(seed)
    rows = np.concatenate([
        t * row_tile + rng.integers(0, row_tile, k)
        for t, k in enumerate(per_tile)]).astype(np.int64)
    cols = rng.integers(0, n, rows.shape[0])
    vals = rng.uniform(0.5, 1.5, rows.shape[0])
    return rows, cols, vals


def _pipelined_csr(structure, precision, d, block_d, interpret=True):
    """The CSR kernel on ``structure`` against the float64 product of the
    stored (precision-rounded) values and B; returns the chunk count."""
    from repro.core.precision import as_precision
    n, row_tile = 128, 8
    prec = as_precision(precision)
    rows, cols, vals = _tiles_matrix(PIPELINE_TILES[structure],
                                     seed=len(structure), n=n)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    packed = np.asarray(jnp.asarray(vals, prec.value_jnp))
    stored = packed.astype(np.float64)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows,
                                                        minlength=n))])
    arrays = csr_to_row_tiles(indptr, cols, packed, n=n, row_tile=row_tile,
                              chunk=32, index_dtype=prec.index_np)
    b = _b(n, d, prec.value_jnp)
    out = csr_spmm_pallas(*(jnp.asarray(x) for x in arrays), b, n=n,
                          row_tile=row_tile, block_d=block_d,
                          vmem_limit=16 << 20, interpret=interpret)
    b64 = np.asarray(b, np.float64)
    a64 = np.zeros((n, n))
    np.add.at(a64, (rows, cols), stored)
    bound = np.abs(a64) @ np.abs(b64)
    err = np.abs(np.asarray(out, np.float64) - a64 @ b64)
    # fp32 accumulation of exactly stored operands; an output in bf16
    # (the reduced precisions return B's dtype) rounds once more.
    eps = np.finfo(np.float32).eps * 64 if prec.value_jnp == jnp.float32 \
        else 2.0 ** -8
    assert out.shape == (n, d) and out.dtype == b.dtype
    assert np.all(err <= eps * bound), float(np.max(err - eps * bound))
    assert np.all(np.asarray(out)[bound == 0] == 0)
    return int(arrays[0][-1])


@pytest.mark.parametrize("precision", ["f32i32", "bf16i32", "bf16i16"])
@pytest.mark.parametrize("d,block_d", [(8, 8), (16, 8)],
                         ids=["one-d-pass", "two-d-passes"])
@pytest.mark.parametrize("structure", list(PIPELINE_TILES))
def test_csr_kernel_pipeline_matches_f64_ref(structure, d, block_d,
                                             precision):
    """The software-pipelined chunk loop, whose next chunk may sit tiles
    ahead (or nowhere), against the float64 reference."""
    chunks = _pipelined_csr(structure, precision, d, block_d)
    want = sum(-(-k // 32) for k in PIPELINE_TILES[structure])
    assert chunks == want


@pytest.mark.parametrize("mode,structure", [
    ("on_wait", "tile-chunk-counts"), ("on_wait", "empty-tiles-in-middle"),
    ("on_wait", "all-empty"), ("eager", "empty-tiles-at-start"),
    ("eager", "all-empty")])
def test_csr_kernel_pipeline_waits_on_every_dma(mode, structure, capfd):
    """Under Pallas's TPU interpreter.  ``on_wait`` lands a DMA only when
    it is waited on, into scratch that starts as NaN, so a read before its
    wait, or a gather slot reused while its rows are in flight, shows in
    the result.  ``eager`` lands each DMA at its start, so one never
    waited on leaves its semaphore nonzero at kernel exit, which the
    interpreter reports."""
    from jax.experimental.pallas import tpu as pltpu
    _pipelined_csr(structure, "f32i32", 16, 8,
                   interpret=pltpu.InterpretParams(
                       dma_execution_mode=mode, uninitialized_memory="nan"))
    assert "non-zero count" not in capfd.readouterr().out


@pytest.mark.parametrize("d", [16, 1024])
def test_csr_execute_span_counts_chunks(d):
    """``repro.execute`` carries the CSR kernel's static counts: every
    d-pass reduces every chunk, and only its first goes cold."""
    from repro import obs
    n = 64
    m = erdos_renyi(n, 6, seed=5)
    disp = sparse.Dispatcher(backend="pallas", calibration=False)
    sp = sparse.StreamPlan(disp, m, sparse.BSpec(d=d, reuse=4),
                           strategy="csr")
    chunks = disp.layout(m, sp.dispatch)["chunks"]
    b = _b(n, d)
    obs.reset()
    c = sp.execute(b)
    sp.execute_wide(_b(n, 3 * d), block_d=d)
    ex = [s for s in obs.spans() if s.name == "repro.execute"]
    passes = d // registry.pallas_block_d(d)
    assert chunks > 0
    assert ex[0].attrs == {"format": "csr", "chunks": passes * chunks,
                           "cold_chunks": passes}
    assert ex[1].attrs == {"format": "csr", "chunks": 3 * passes * chunks,
                           "cold_chunks": 3 * passes}
    a = sparse.coo_to_csr(m)
    np.testing.assert_allclose(
        np.asarray(c), np.asarray(ref.csr_ref(a.indptr, a.indices, a.data,
                                              b, n=n)),
        rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("bandwidth", [1, 5, 17])
@pytest.mark.parametrize("d", [16, 64])
def test_banded_kernel_sweep(bandwidth, d):
    n, t = 256, 32
    m = gen_banded(n, bandwidth, fill=0.9, seed=bandwidth)
    dia = sparse.coo_to_dia(m)
    band, w = kernels.band_to_blocks(np.asarray(dia.data), dia.offsets,
                                     n=n, t=t)
    b = _b(n, d)
    out = banded_spmm_pallas(band, b, t=t, w=w, block_d=d, **_launch())
    expect = ref.banded_ref(np.asarray(band), b, t=t, w=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("E,bm", [(4, 64), (8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(E, bm, dtype):
    T, K, N = 4 * bm, 128, 256
    x = _b(T, K, dtype)
    w = jnp.asarray(RNG.normal(size=(E, K, N))).astype(dtype)
    gids = jnp.asarray(RNG.integers(0, E, size=T // bm).astype(np.int32))
    out = grouped_matmul_pallas(x, w, gids, bm=bm, bk=64, bn=128,
                                **_launch())
    expect = ref.grouped_matmul_ref(x, w, gids, bm=bm)
    tol = 1e-1 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_grouped_matmul_matches_moe_semantics():
    """grouped_matmul on expert-sorted tokens == per-expert dense matmul."""
    E, bm, K, N = 4, 32, 64, 64
    gids = jnp.asarray([0, 1, 1, 3], jnp.int32)
    x = _b(4 * bm, K)
    w = jnp.asarray(RNG.normal(size=(E, K, N)).astype(np.float32))
    out = grouped_matmul_pallas(x, w, gids, bm=bm, bk=64, bn=64,
                                **_launch())
    for blk in range(4):
        seg = slice(blk * bm, (blk + 1) * bm)
        np.testing.assert_allclose(
            np.asarray(out[seg]),
            np.asarray(x[seg] @ w[int(gids[blk])]), rtol=2e-3, atol=2e-3)


def test_kernel_rooflines():
    m = gen_blocked(256, t=32, num_blocks=30, nnz_per_block=64, seed=1)
    a = sparse.coo_to_bcsr(m, 32)
    r = kernels.bcsr_kernel_roofline(a, 64)
    assert 0 < r.mxu_utilization <= 1
    assert r.useful_flops <= r.mxu_flops
    assert r.attainable_flops_per_s > 0
    c = kernels.csr_kernel_roofline(sparse.coo_to_csr(m), 64)
    assert c.mxu_utilization == 1.0   # CSR issues only useful FLOPs
    assert c.useful_flops == pytest.approx(r.useful_flops)
    assert c.ai < r.ai                # random-gather traffic dominates CSR
    g = kernels.grouped_matmul_roofline(4096, 4096, 1536, 128)
    assert g.mxu_utilization == 1.0   # block-diagonal: every block dense
    assert g.ai > r.ai                # MoE blocks beat generic sparse blocks


def test_kernels_take_interpret_and_vmem_limit_from_the_caller():
    """No kernel entry point defaults to interpret mode, and every
    pallas_call states its scoped VMEM limit."""
    import inspect
    import pathlib
    import re
    fns = (banded_spmm_pallas, bcsr_spmm_pallas, binned_spmm_pallas,
           rowsplit_spmm_pallas, csr_spmm_pallas, grouped_matmul_pallas)
    for fn in fns:
        params = inspect.signature(fn).parameters
        for name in ("interpret", "vmem_limit"):
            assert params[name].default is inspect.Parameter.empty, \
                (fn.__name__, name)
    calls = 0
    for path in pathlib.Path(registry.__file__).parent.glob("*.py"):
        for call in re.split(r"pl\.pallas_call\(", path.read_text())[1:]:
            calls += 1
            assert "vmem_limit_bytes=vmem_limit" in call.split(")(")[0], \
                path.name
    assert calls == len(fns)
    # Interpret mode is the CPU backend's, never an accelerator's.
    assert registry.KernelContext().resolve_interpret() is True


# --------------------------------------------------------------------- #
# Segmented BCSR: block coordinates cut into runs of block rows that fit
# the SMEM budget, one pallas_call each, all writing one C.
# --------------------------------------------------------------------- #

def _small_smem_v5e():
    """The v5e with SMEM for 48 blocks' coordinates a segment (the
    kernels may take three quarters of SMEM, 8 bytes a block)."""
    import dataclasses
    from repro.core.hardware import TPU_V5E
    return dataclasses.replace(TPU_V5E, smem_bytes=512)


def _segmented_mesh_plan(precision="f32i32", d=16):
    """A 1,536-row hexahedral mesh forced onto the BCSR kernel (154
    blocks of 64) with the small SMEM budget."""
    from repro.core import hex_mesh
    m = hex_mesh(8, 8, 8, seed=3)
    disp = sparse.Dispatcher(hardware=_small_smem_v5e(), backend="pallas",
                             calibration=False, tree=False)
    sp = sparse.StreamPlan(disp, m, sparse.BSpec(d=d, reuse=4,
                                                 precision=precision),
                           strategy="bcsr")
    return m, sp, disp.layout(m, sp.dispatch)


@pytest.mark.parametrize("precision,tol", [
    # float32 values (rounded once from float64, 2^-24) and B, products
    # summed in float32 over at most 81 nonzeros a row: each entry within
    # about (81 + 2) * 2^-24 = 4.9e-6 of |A| @ |B|.
    ("f32i32", 1e-5),
    # values, B and the output each rounded to bfloat16 (at most 2^-9
    # relative), the sums in float32: about 3 * 2^-9 = 5.9e-3 of |A| @ |B|.
    ("bf16i32", 8e-3)])
def test_segmented_bcsr_matches_float64(precision, tol):
    m, sp, layout = _segmented_mesh_plan(precision)
    assert len(layout["segments"]) >= 3
    b = _b(m.n, 16)
    c = np.asarray(sp.execute(b), np.float64)
    a = np.zeros((m.n, m.n))
    a[m.rows, m.cols] = m.vals
    bb = np.asarray(b.astype(jnp.float32), np.float64)
    scaled = np.abs(c - a @ bb) / (np.abs(a) @ np.abs(bb))
    assert scaled.max() <= tol


def test_bcsr_execute_span_counts_blocks():
    """``repro.execute`` carries the BCSR kernel's stored blocks, grid
    steps (one a block row), cold blocks (a segment's first matmul),
    block edge and segments; an ``execute_wide`` sums the counts, not
    the edge."""
    from repro import obs
    from repro.kernels.bcsr_spmm import blocks_per_matmul
    m, sp, layout = _segmented_mesh_plan(d=8)
    blocks, segments = layout["arrays"][0].shape[0], len(layout["segments"])
    steps = m.n // 64
    ptr = np.asarray(layout["arrays"][1])
    assert blocks >= 154 and segments >= 3
    # Each segment's first row fills a matmul: it goes cold, no more.
    assert all(ptr[r + 1] - ptr[r] >= 4 for r, _ in layout["rows"])
    cold = blocks_per_matmul(64) * segments
    obs.reset()
    sp.execute(_b(m.n, 8))
    sp.execute_wide(_b(m.n, 16), block_d=8)
    ex = [s for s in obs.spans() if s.name == "repro.execute"]
    assert ex[0].attrs == {"format": "bcsr", "blocks": blocks,
                           "steps": steps, "cold_blocks": cold,
                           "block_t": 64, "segments": segments}
    assert ex[1].attrs == {"format": "bcsr", "blocks": 2 * blocks,
                           "steps": 2 * steps, "cold_blocks": 2 * cold,
                           "block_t": 64, "segments": 2 * segments}


def test_bcsr_segments_of_an_audikw_sized_operator_fit_v5e_smem():
    """284,193 blocks over 14,739 block rows (the ``fem_audikw`` operator
    at t = 64): every segment's coordinates fit the v5e SMEM budget, and
    the segments cut the block list at block-row boundaries."""
    from repro.core.hardware import TPU_V5E, kernel_smem_limit
    from repro.kernels.bcsr_spmm import COORD_BYTES, bcsr_segments
    from repro.kernels.registry import bcsr_segment_blocks
    nb, total = 943_296 // 64, 284_193
    counts = np.full(nb, total // nb)
    extra = np.random.default_rng(15).choice(nb, total - counts.sum(),
                                             replace=False)
    counts[extra] += 1
    ptr = np.concatenate([[0], np.cumsum(counts)])
    segments = bcsr_segments(ptr, bcsr_segment_blocks(TPU_V5E))
    assert len(segments) >= 3
    assert segments[0][0] == 0 and segments[-1][1] == total
    assert all(a[1] == b[0] for a, b in zip(segments, segments[1:]))
    assert {s for seg in segments for s in seg} <= set(ptr.tolist())
    assert max(COORD_BYTES * (e - s) for s, e in segments) \
        <= kernel_smem_limit(TPU_V5E)


def test_bcsr_segments_refuse_a_block_row_wider_than_a_segment():
    from repro.kernels.bcsr_spmm import bcsr_segments
    assert bcsr_segments([0, 2, 5, 6], 3) == ((0, 2), (2, 5), (5, 6))
    with pytest.raises(ValueError, match="block row 1 holds 3 blocks"):
        bcsr_segments([0, 2, 5, 6], 2)

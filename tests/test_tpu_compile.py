"""Every registered Pallas kernel compiles for a TPU v5e at n = 2**20.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so these tests
catch what interpret mode cannot: illegal block shapes, in-kernel
operations Mosaic cannot lower, and scoped-VMEM overruns.  Nothing runs,
so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these tests (a
compile for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hardware import TPU_V5E, kernel_vmem_limit
from repro.kernels import registry
from repro.kernels.banded_spmm import banded_spmm_pallas
from repro.kernels.bcsr_spmm import (bcsr_segments, bcsr_spmm_pallas,
                                     lane_rows, segment_rows)
from repro.kernels.binned_spmm import binned_spmm_pallas, rowsplit_spmm_pallas
from repro.kernels.csr_spmm import chunks_per_row, csr_spmm_pallas

N = 2 ** 20
D = 128
CHUNK = 128
#: ~1.3x the chunks of a degree-16 operator at N rows (packing padding).
NUM_CHUNKS = 170_000
VMEM_LIMIT = kernel_vmem_limit(TPU_V5E)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding, **static):
    """Lower + compile ``fn`` for the described chip; return the program."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    lowered = fn.lower(*args, **static)
    assert "tpu_custom_call" in lowered.as_text()     # a Mosaic kernel
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < TPU_V5E.hbm_bytes
    return compiled


def _chunks(index_dtype, value_dtype, num_chunks=NUM_CHUNKS):
    """Shapes of the packed (cols, slots, vals) chunk arrays."""
    def packed(dtype):
        p = chunks_per_row(dtype)
        return ((-(-num_chunks // p), p, CHUNK), dtype)
    return [packed(index_dtype), packed(jnp.int8), packed(value_dtype)]


@pytest.mark.parametrize("value_dtype,index_dtype,d", [
    (jnp.float32, jnp.int32, D), (jnp.bfloat16, jnp.int32, D),
    (jnp.bfloat16, jnp.int16, D), (jnp.float32, jnp.int32, 2 * D)],
    ids=["f32i32", "bf16i32", "bf16i16", "f32i32-two-d-passes"])
def test_csr_kernel_compiles(one_chip, value_dtype, index_dtype, d):
    """The pipelined gather kernel, also with the pipeline restarting at a
    second d-pass (``block_d < d``)."""
    num_tiles = N // registry.ROW_TILE
    shapes = ([((num_tiles + 1,), jnp.int32)]
              + _chunks(index_dtype, value_dtype) + [((N, d), value_dtype)])
    _compile(csr_spmm_pallas, shapes, one_chip, n=N,
             row_tile=registry.ROW_TILE, block_d=D, vmem_limit=VMEM_LIMIT,
             interpret=False)


@pytest.mark.parametrize("value_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_binned_kernel_compiles(one_chip, value_dtype):
    b_tile = registry.choose_b_tile(N, VMEM_LIMIT, bd=D)
    assert b_tile is not None and b_tile < N
    visits = 40_000
    shapes = ([((visits,), jnp.int32), ((visits,), jnp.int32),
               ((visits + 1,), jnp.int32)]
              + _chunks(jnp.int16, value_dtype) + [((N, D), value_dtype)])
    _compile(binned_spmm_pallas, shapes, one_chip, n=N,
             row_tile=registry.ROW_TILE, b_tile=b_tile, block_d=D,
             vmem_limit=VMEM_LIMIT, interpret=False)


def test_rowsplit_kernel_compiles(one_chip):
    """Row-split holds all of B; the largest n its footprint admits."""
    ctx = registry.KernelContext(hardware=TPU_V5E)
    spec = registry.get("rowsplit", "pallas")
    n = 2 ** 14
    assert spec.vmem_footprint(n, D, ctx) <= VMEM_LIMIT
    assert spec.vmem_footprint(N, D, ctx) > VMEM_LIMIT
    chunks = n * 16 // CHUNK
    window = 24
    shapes = ([((chunks, window), jnp.int32)]
              + _chunks(jnp.int16, jnp.float32, chunks)
              + [((n, D), jnp.float32)])
    _compile(rowsplit_spmm_pallas, shapes, one_chip, n=n, window=window,
             block_d=D, vmem_limit=VMEM_LIMIT, interpret=False)


def _bcsr_shapes(blocks, t, n, d):
    """Lane-packed blocks (``[N, t/q, q*t]``), the block-row pointer, the
    block columns and B."""
    q = lane_rows(t)
    return [((blocks, t // q, q * t), jnp.float32), ((n // t + 1,), jnp.int32),
            ((blocks,), jnp.int32), ((n, d), jnp.float32)]


@pytest.mark.parametrize("d", [16, 128, 512])
def test_bcsr_kernel_compiles(one_chip, d):
    t, blocks = 64, 50_000
    _compile(bcsr_spmm_pallas, _bcsr_shapes(blocks, t, N, d), one_chip,
             n=N, t=t, block_d=registry.pallas_block_d(d),
             vmem_limit=VMEM_LIMIT, interpret=False)


def test_segmented_bcsr_kernel_compiles_at_audikw_size(one_chip):
    """The ``fem_audikw`` operator: 284,193 blocks of 64 at n = 943,296,
    cut into segments whose coordinates fit SMEM, one ``pallas_call``
    each into one aliased C: no copy of the 4.66 GB of blocks, no second
    C."""
    n, t, total = 943_296, 64, 284_193
    nb = n // t
    counts = np.full(nb, total // nb)
    counts[:total - counts.sum()] += 1
    ptr = np.concatenate([[0], np.cumsum(counts)])
    segments = bcsr_segments(ptr, registry.bcsr_segment_blocks(TPU_V5E))
    assert len(segments) >= 3
    compiled = _compile(bcsr_spmm_pallas, _bcsr_shapes(total, t, n, D),
                        one_chip, n=n, t=t, block_d=D,
                        vmem_limit=VMEM_LIMIT, interpret=False,
                        segments=segments, rows=segment_rows(ptr, segments))
    assert compiled.as_text().count("tpu_custom_call") >= len(segments)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n * D


def test_banded_kernel_compiles(one_chip):
    t = registry.pallas_band_tile(N)
    assert t == 128
    w = 1
    shapes = [((N // t, 2 * w + 1, t, t), jnp.float32), ((N, D), jnp.float32)]
    _compile(banded_spmm_pallas, shapes, one_chip, t=t, w=w, block_d=D,
             vmem_limit=VMEM_LIMIT, interpret=False)


def test_grouped_matmul_compiles(one_chip):
    from repro.kernels.grouped_matmul import grouped_matmul_pallas
    tokens, k, n_out, experts, bm = 8192, 2048, 1024, 8, 128
    shapes = [((tokens, k), jnp.bfloat16), ((experts, k, n_out), jnp.bfloat16),
              ((tokens // bm,), jnp.int32)]
    _compile(grouped_matmul_pallas, shapes, one_chip, bm=bm, bk=128, bn=128,
             vmem_limit=VMEM_LIMIT, interpret=False)


def test_choose_b_tile_fits_the_compiled_budget():
    """The binned slab is sized from the limit every kernel requests."""
    b_tile = registry.choose_b_tile(N, VMEM_LIMIT, bd=D)
    ctx = registry.KernelContext(hardware=TPU_V5E, plan_d=D)
    assert ctx.resolve_b_tile(N) == b_tile
    assert registry.get("binned", "pallas").vmem_footprint(N, D, ctx) \
        <= VMEM_LIMIT
    assert np.int16(b_tile - 1) == b_tile - 1       # int16 slab-local cols

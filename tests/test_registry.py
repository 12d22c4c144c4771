"""Kernel registry: completeness, numerics per spec, VMEM models."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels, sparse
from repro.core import banded, blocked, erdos_renyi
from repro.core.hardware import HOST_CPU, TPU_V5E
from repro.kernels import registry

N = 256


def _mats():
    return {
        "csr": erdos_renyi(N, 6, seed=1),
        "ell": erdos_renyi(N, 6, seed=2),
        "bcsr": blocked(N, t=32, num_blocks=24, nnz_per_block=300, seed=3),
        "dia": banded(N, 3, fill=0.9, seed=4),
    }


def _b(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))


# --------------------------------------------------------------------- #
# Completeness: the README feature matrix must resolve end to end.
# --------------------------------------------------------------------- #

def test_every_dispatch_pair_registered():
    """Every (format, backend) pair the dispatcher can choose resolves."""
    for fmt in sparse.FORMATS:
        for backend in registry.BACKENDS:
            spec = registry.get(fmt, backend)
            assert spec.key == (fmt, backend)
            assert spec.description
    assert registry.get("grouped", "pallas").format == "grouped"
    matrix = registry.feature_matrix()
    assert set(matrix) >= {(f, b) for f in sparse.FORMATS
                           for b in registry.BACKENDS}
    assert set(registry.formats_for("jax")) == set(sparse.FORMATS)
    assert set(registry.formats_for("pallas")) == \
        set(sparse.FORMATS) | {"grouped"}


def test_get_unknown_pair_lists_available():
    with pytest.raises(KeyError, match="available"):
        registry.get("csr", "cuda")
    with pytest.raises(ValueError, match="already registered"):
        registry.register(registry.get("csr", "jax"))


def test_every_spmm_spec_matches_dense():
    """bind -> run agrees with the dense reference for every pair."""
    ctx = registry.KernelContext(bcsr_block=32)
    b = _b(N, 16)
    for fmt, m in _mats().items():
        dense = np.asarray(sparse.coo_to_dense(m)) @ np.asarray(b)
        for backend in registry.BACKENDS:
            run = registry.get(fmt, backend).bind(m, ctx)
            np.testing.assert_allclose(
                np.asarray(run(b)), dense, rtol=5e-4, atol=5e-4,
                err_msg=f"{fmt}/{backend}")


def test_registry_spmm_one_call():
    m = _mats()["csr"]
    b = _b(N, 8)
    out = registry.spmm(m, b, format="csr", backend="pallas")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(sparse.coo_to_dense(m)) @ np.asarray(b),
        rtol=5e-4, atol=5e-4)


def test_grouped_spec_roundtrip():
    """The MoE grouped-matmul spec: bind carries (w, gids, tiles)."""
    from repro.kernels import ref
    E, bm, K, Nn = 4, 32, 64, 64
    gids = jnp.asarray([0, 1, 1, 3], jnp.int32)
    x = _b(4 * bm, K, seed=5)
    w = jnp.asarray(np.random.default_rng(6).normal(
        size=(E, K, Nn)).astype(np.float32))
    spec = registry.get("grouped", "pallas")
    run = spec.bind((w, gids, bm, 64, 64), registry.KernelContext())
    np.testing.assert_allclose(
        np.asarray(run(x)), np.asarray(ref.grouped_matmul_ref(x, w, gids,
                                                              bm=bm)),
        rtol=2e-3, atol=2e-3)
    roof = spec.estimate((w, gids, bm, 64, 64), 0, registry.KernelContext())
    assert roof.mxu_utilization == 1.0 and roof.ai > 0


# --------------------------------------------------------------------- #
# Estimates and VMEM footprints.
# --------------------------------------------------------------------- #

def test_estimates_have_roofline_fields():
    ctx = registry.KernelContext(hardware=TPU_V5E, bcsr_block=32)
    for fmt, m in _mats().items():
        for backend in registry.BACKENDS:
            r = kernels.KernelRoofline, registry.get(fmt, backend)
            est = r[1].estimate(m, 64, ctx)
            assert est.ai > 0 and est.useful_flops > 0
            assert 0 < est.mxu_utilization <= 1
            assert est.useful_flops <= est.mxu_flops + 1e-6
            assert est.attainable_flops_per_s > 0


def test_vmem_footprints():
    ctx = registry.KernelContext(hardware=TPU_V5E, bcsr_block=32)
    for fmt in sparse.FORMATS:
        assert registry.get(fmt, "jax").vmem_footprint(N, 64, ctx) == 0
        fp = registry.get(fmt, "pallas").vmem_footprint(N, 64, ctx)
        assert 0 < fp <= TPU_V5E.vmem_bytes
    # The CSR footprint must respect a small VMEM budget even for an n
    # where whole-B residency would blow it by orders of magnitude: B
    # rows are DMA'd from HBM.  (The floor is the [chunk, bd] gather
    # scratch, 256 KiB at bd=512.)
    tiny = dataclasses.replace(TPU_V5E, vmem_bytes=2 * 2 ** 20)
    tctx = registry.KernelContext(hardware=tiny)
    n_big = 1_000_000
    assert n_big * 512 * 4 > tiny.vmem_bytes        # whole B would not fit
    fp = registry.get("csr", "pallas").vmem_footprint(n_big, 512, tctx)
    assert fp <= tctx.vmem_limit


def test_choose_b_tile_policy():
    # Plenty of VMEM: hold B whole (None = unstreamed layout).
    assert registry.choose_b_tile(512, 128 * 2 ** 20) is None
    # Tight VMEM: slab shrinks, stays a multiple of 8, floors at 8.
    bt = registry.choose_b_tile(10_000, 2 ** 20, bd=512)
    assert bt is not None and bt % 8 == 0 and bt < 10_000
    assert registry.choose_b_tile(10_000, 1024, bd=512) == 8
    # No budget information: behave as before (whole B).
    assert registry.choose_b_tile(512, 0) is None


def test_context_resolves_b_tile_override():
    ctx = registry.KernelContext(b_tile=64)
    assert ctx.resolve_b_tile(256) == 64
    assert ctx.resolve_b_tile(32) is None        # override >= n: whole B
    auto = registry.KernelContext(
        hardware=dataclasses.replace(HOST_CPU, vmem_bytes=2 ** 16))
    assert auto.vmem_limit == 2 ** 15
    assert auto.resolve_b_tile(100_000) == \
        registry.choose_b_tile(100_000, 2 ** 15)


def test_plan_d_repacks_b_slab():
    """Per-d slab re-packing: small planned widths get taller B slabs.

    The default bd=512 charges the VMEM budget for the widest d-tile;
    a plan that knows d=8 hosts a 64x-narrower slab and so fits 64x the
    rows (capped by n / whole-B residency).
    """
    tight = dataclasses.replace(HOST_CPU, vmem_bytes=2 ** 21)
    budget = registry.KernelContext(hardware=tight).vmem_limit
    assert budget == 2 ** 20
    n = 100_000
    wide = registry.KernelContext(hardware=tight)              # bd=512
    narrow = registry.KernelContext(hardware=tight, plan_d=8)  # bd=8
    t_wide, t_narrow = wide.resolve_b_tile(n), narrow.resolve_b_tile(n)
    assert t_wide is not None and t_narrow is not None
    assert t_narrow == registry.choose_b_tile(n, 2 ** 20, bd=8)
    assert t_narrow > t_wide
    # plan_d=None preserves the legacy conservative sizing exactly.
    assert t_wide == registry.choose_b_tile(n, 2 ** 20, bd=512)
    # Non-power-of-two widths route through the kernel's actual d-tile.
    d24 = registry.KernelContext(hardware=tight, plan_d=24)
    assert d24.resolve_b_tile(n) == registry.choose_b_tile(
        n, 2 ** 20, bd=registry.pallas_block_d(24))
    # An explicit override still wins over the planned width.
    forced = registry.KernelContext(hardware=tight, plan_d=8, b_tile=64)
    assert forced.resolve_b_tile(n) == 64
    # With a taller slab the whole-B threshold moves: a matrix that
    # streams at bd=512 can be fully resident at bd=8.
    n_small = registry.choose_b_tile(4096, 2 ** 20, bd=512)
    assert n_small is not None                   # streams under wide tile
    assert registry.KernelContext(hardware=tight,
                                  plan_d=8).resolve_b_tile(4096) is None


def test_registry_version_current():
    """REGISTRY_VERSION gates calibration staleness; must be an int >= 2
    (v2 introduced per-d slab re-packing)."""
    assert isinstance(registry.REGISTRY_VERSION, int)
    assert registry.REGISTRY_VERSION >= 2

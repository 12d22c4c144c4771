"""Structure-aware dispatch: planning, policy, caching, and execution."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import sparse
from repro.core import banded, blocked, erdos_renyi, scale_free

N = 512


def _mats():
    return {
        "random": erdos_renyi(N, 8, seed=1),
        "banded": banded(N, 3, fill=0.9, seed=2),
        "fem": blocked(N, t=32, num_blocks=N // 16, nnz_per_block=320,
                       seed=3),
        "powerlaw": scale_free(N, 8, alpha=2.2, seed=4),
    }


def _b(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))


# --------------------------------------------------------------------- #
# Numerics: every strategy x pattern must agree with the dense reference.
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("pattern", sorted(_mats()))
@pytest.mark.parametrize("strategy", ["auto", "csr"])
def test_spmm_matches_dense(pattern, strategy):
    m = _mats()[pattern]
    b = _b(N, 8)
    ref = sparse.coo_to_dense(m) @ b
    out = sparse.spmm(m, b, strategy=strategy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_forced_strategies_match_dense():
    m = _mats()["banded"]
    b = _b(N, 4)
    ref = sparse.coo_to_dense(m) @ b
    for strategy in ("ell", "bcsr", "dia"):
        out = sparse.spmm(m, b, strategy=strategy)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4, err_msg=strategy)


def test_pallas_backend_matches_dense():
    disp = sparse.Dispatcher(backend="pallas", bcsr_block=32)
    b = _b(N, 16)
    for pattern, m in _mats().items():
        ref = sparse.coo_to_dense(m) @ b
        out = disp.spmm(m, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4, err_msg=pattern)


# --------------------------------------------------------------------- #
# Policy: the paper's structure -> format mapping, with skip reasons.
# --------------------------------------------------------------------- #

#: Formats sharing the CSR gather/segment-sum algebra: the acceptable
#: picks for hub/scale-free structure (plain ELL must still policy-skip).
GATHER_FAMILY = {"csr", "binned", "rowsplit", "ell_coo"}


def test_expected_formats_per_structure():
    """The acceptance mapping: banded->dia, dense blocks->bcsr,
    hub/scale-free->the CSR gather family (ELL policy-skipped there)."""
    mats = _mats()
    d = 64
    assert sparse.plan_spmm(mats["banded"], d).chosen == "dia"
    assert sparse.plan_spmm(mats["fem"], d).chosen == "bcsr"
    plan = sparse.plan_spmm(mats["powerlaw"], d)
    assert plan.chosen in GATHER_FAMILY
    assert "ell" in plan.skips
    assert "padding" in plan.skips["ell"]


def test_binned_model_wins_high_skew_on_bandwidth_bound_hw():
    """The model-level form of PR 8's scale-free claim, deterministic:
    on a bandwidth-bound part (TPU v5e) the slab-binned traversal's
    collapsed B-traffic term must rank binned above plain CSR for
    high-skew scale-free structure once B outgrows on-chip residency."""
    from repro.core.hardware import TPU_V5E
    m = scale_free(8192, 16, alpha=2.05, seed=10)
    disp = sparse.Dispatcher(hardware=TPU_V5E, backend="pallas",
                             calibration=False)
    plan = disp.plan(m, 64)
    assert plan.regime == "scale_free"
    binned = plan.candidate("binned")
    csr = plan.candidate("csr")
    assert binned.eligible and csr.eligible
    assert binned.predicted_gflops > csr.predicted_gflops
    assert plan.chosen in GATHER_FAMILY


def test_v5e_kernel_costs_pick_bcsr_for_a_mesh_and_csr_for_a_power_law():
    """On the v5e, whose kernel costs were measured on the chip, the
    issue floors decide: a 3D elasticity mesh goes to the BCSR kernel
    (the roofline alone would take the CSR kernel's ``ell`` pick), a
    power-law graph to ``csr`` (its ``ell_coo`` twin runs the same kernel
    on the same layout, so it ties and ``csr`` comes first)."""
    import dataclasses
    from repro.core import hex_mesh
    from repro.core.hardware import TPU_V5E, kernel_costs

    def plan(m, hw):
        disp = sparse.Dispatcher(hardware=hw, backend="pallas",
                                 calibration=False, tree=False)
        return disp.plan(m, 128, reuse=1000)

    assert kernel_costs(TPU_V5E) is not None
    roofline_only = dataclasses.replace(TPU_V5E, name="tpu-v5e-roofline")
    mesh = hex_mesh(16, 16, 16)
    picked = plan(mesh, TPU_V5E)
    assert picked.regime == "blocked" and picked.chosen == "bcsr"
    assert plan(mesh, roofline_only).chosen != "bcsr"
    graph = plan(scale_free(2 ** 18, 16, alpha=2.05, seed=10), TPU_V5E)
    assert graph.regime == "scale_free" and graph.chosen == "csr"
    assert graph.candidate("ell_coo").amortized_gflops == \
        graph.candidate("csr").amortized_gflops


#: The v5e's BCSR cost: seconds of the block-row kernel a stored block,
#: after the block's bytes (``tools/kernel_costs.py`` on ``fem_audikw``).
BCSR_BLOCK_S = 0.1135e-6


def test_v5e_bcsr_prediction_is_stored_blocks_times_the_block_cost():
    """The v5e plan predicts the BCSR kernel on a mesh from its measured
    cost: stored blocks times (``block_step_s``, a stored block's share
    of the block-row kernel's time, plus the block's bytes at HBM
    bandwidth)."""
    from repro.core import hex_mesh
    from repro.core.hardware import KERNEL_COSTS, TPU_V5E
    from repro.kernels.bcsr_spmm import pack_bcsr
    costs = KERNEL_COSTS["TPU v5 lite"]
    assert costs.block_step_s == pytest.approx(BCSR_BLOCK_S)
    mesh = hex_mesh(16, 16, 16)
    d, t = 128, 64
    disp = sparse.Dispatcher(hardware=TPU_V5E, backend="pallas",
                             calibration=False, tree=False)
    c = disp.plan(mesh, d, reuse=1000).candidate("bcsr")
    blocks = pack_bcsr(mesh.rows, mesh.cols, mesh.vals, n=mesh.n, t=t,
                       dtype=np.float32)[0].shape[0]
    predicted_s = 2.0 * mesh.nnz * d / (c.predicted_gflops * 1e9)
    assert predicted_s == pytest.approx(
        blocks * (costs.block_step_s + t * t * 4 / TPU_V5E.hbm_bandwidth),
        rel=1e-9)


@pytest.mark.parametrize("structure,d", [("uniform", 8), ("uniform", 64),
                                         ("scale_free", 8),
                                         ("scale_free", 64)])
def test_reduced_precision_roofline_gain_on_bandwidth_bound_hw(structure, d):
    """The tentpole's model-level claim, deterministic: on a
    bandwidth-bound part (TPU v5e) the roofline must predict >= 1.5x
    attainable GFLOP/s for bf16 values + int16 indices over fp32 + int32
    on the CSR-family kernels for bandwidth-bound structures (uniform /
    scale-free at d >= 8) — halving the bytes-per-nonzero on a
    memory-bound kernel halves its time bound.

    The claim is the bytes model's, so the v5e here is the roofline
    alone: the chip's measured kernel costs (``hardware.KERNEL_COSTS``)
    floor every CSR-family row at its DMA issue time, the same at every
    precision, and apply only to the spec of a device kind."""
    import dataclasses
    from repro.core.hardware import TPU_V5E, kernel_costs
    roofline_only = dataclasses.replace(TPU_V5E, name="tpu-v5e-roofline")
    assert kernel_costs(roofline_only) is None
    if structure == "uniform":
        m = erdos_renyi(8192, 16, seed=11)
    else:
        m = scale_free(8192, 16, alpha=2.05, seed=11)
    disp = sparse.Dispatcher(hardware=roofline_only, backend="pallas",
                             calibration=False)
    # tolerance admits bf16 (eps 2^-7) so the reduced rows rank eligibly.
    plan = disp.plan(m, d, tolerance=1e-2)
    gained = []
    for name in ("csr", "binned", "rowsplit", "ell_coo"):
        lo = plan.candidate(name, "bf16i16")
        hi = plan.candidate(name, "f32i32")
        if not (lo.eligible and hi.eligible):
            continue                  # structure-gated format: not at issue
        # Halved bytes-per-nonzero must exactly double the modeled AI.
        assert lo.ai == pytest.approx(2.0 * hi.ai, rel=1e-6)
        # The >= 1.5x attainable claim holds wherever the bf16 row is
        # still under the memory roof; rows the compact layout promotes
        # all the way into the compute-bound regime are the win itself,
        # not an exception (their gain is capped by the ceiling).
        ceiling_capped = (lo.predicted_gflops
                          < TPU_V5E.attainable(lo.ai) / 1e9 * 0.999)
        if not ceiling_capped:
            assert lo.predicted_gflops >= 1.5 * hi.predicted_gflops, (
                f"{name} @ d={d} ({structure}): bf16i16 predicts "
                f"{lo.predicted_gflops:.1f} GF/s vs f32i32 "
                f"{hi.predicted_gflops:.1f} GF/s")
            gained.append(name)
    # Non-vacuity: every swept config keeps >= 1 CSR-family format under
    # the memory roof at bf16i16 with the full >= 1.5x predicted gain.
    assert gained, f"no bandwidth-bound CSR-family row at d={d}"
    # The winning plan itself runs reduced under this tolerance.
    assert plan.precision in ("bf16i16", "bf16i32")


def test_skip_reasons_recorded():
    plan = sparse.plan_spmm(_mats()["random"], 16)
    # Random sparsity at avg degree 8: DIA is hopeless and says why.
    assert "dia" in plan.skips
    assert "diagonals" in plan.skips["dia"]
    for cand in plan.candidates:
        assert cand.eligible == (cand.skip_reason is None)


def test_bcsr_inflation_gate():
    """Sparse blocks (D << t^2) must skip BCSR, mirroring mxu_util -> 0."""
    m = blocked(N, t=64, num_blocks=N // 32, nnz_per_block=40, seed=6)
    plan = sparse.plan_spmm(m, 16)
    assert "bcsr" in plan.skips
    assert "inflation" in plan.skips["bcsr"]


def test_plan_summary_and_audit_fields():
    plan = sparse.plan_spmm(_mats()["fem"], 16)
    text = plan.summary()
    assert plan.chosen in text and plan.regime in text
    for cand in plan.candidates:
        if cand.eligible:
            assert cand.ai > 0
            assert cand.predicted_gflops > 0
            # Conversion amortization can only cost, never gain.
            assert cand.amortized_gflops <= cand.predicted_gflops + 1e-9


def test_amortization_improves_with_reuse():
    m = _mats()["fem"]
    lo = sparse.plan_spmm(m, 16, reuse=1).candidate("bcsr")
    hi = sparse.plan_spmm(m, 16, reuse=10_000).candidate("bcsr")
    assert hi.amortized_gflops > lo.amortized_gflops
    assert hi.amortized_gflops == pytest.approx(hi.predicted_gflops,
                                                rel=0.05)


def test_bad_inputs_raise():
    m = _mats()["random"]
    with pytest.raises(ValueError):
        sparse.plan_spmm(m, 16, strategy="dense")
    with pytest.raises(ValueError):
        sparse.Dispatcher(backend="tpu")
    with pytest.raises(ValueError):
        # Forcing DIA on random sparsity: structurally impossible.
        sparse.spmm(m, _b(N, 4), strategy="dia")


# --------------------------------------------------------------------- #
# Caching: plans and conversions are computed once per matrix.
# --------------------------------------------------------------------- #

def test_plan_and_conversion_cached():
    disp = sparse.Dispatcher()
    m = _mats()["fem"]
    p1 = disp.plan(m, 16)
    assert disp.plan(m, 16) is p1                     # plan cache hit
    assert disp.plan(m, 32) is not p1                 # keyed on d
    c1 = disp.convert(m, "csr")
    assert disp.convert(m, "csr") is c1               # conversion cache hit
    b = _b(N, 16)
    out1 = disp.spmm(m, b)
    out2 = disp.spmm(m, b)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_pallas_layouts_shared_between_csr_and_ell():
    """ELL's pallas pick lowers to the CSR kernel; the row-tile packing
    must be prepared once (shared layout_key) and prepare must reuse the
    dispatcher's conversion cache rather than re-converting."""
    disp = sparse.Dispatcher(backend="pallas", bcsr_block=32)
    m = _mats()["random"]
    b = _b(N, 8)
    csr_container = disp.convert(m, "csr")
    out_csr = disp.spmm(m, b, strategy="csr")
    out_ell = disp.spmm(m, b, strategy="ell")
    np.testing.assert_allclose(np.asarray(out_csr), np.asarray(out_ell),
                               rtol=1e-6, atol=1e-6)
    layouts = [k for k in disp._converted if len(k) > 2 and k[1] == "layout"]
    assert len(layouts) == 1                     # one shared packing
    assert disp.convert(m, "csr") is csr_container   # cache, not rebuilt


def test_cache_evicts_on_gc():
    disp = sparse.Dispatcher()
    m = erdos_renyi(N, 4, seed=9)
    disp.plan(m, 16)
    disp.convert(m, "csr")
    assert disp._plans and disp._converted
    del m
    import gc
    gc.collect()
    assert not disp._plans
    assert not disp._converted


# --------------------------------------------------------------------- #
# Learned fallback: the dispatch tree breaks analytic near-ties only.
# --------------------------------------------------------------------- #

def _constant_tree(label):
    """A depth-0 tree that always predicts ``label``."""
    from repro.data.dtree import FEATURES, DecisionTree
    x = np.array([[0.0] * len(FEATURES), [1.0] * len(FEATURES)])
    return DecisionTree(max_depth=0, min_leaf=1).fit(x, [label, label])


def test_analytic_only_without_tree():
    disp = sparse.Dispatcher(backend="jax", tree=False)
    plan = disp.plan(_mats()["random"], 16)
    assert plan.decision_source == "analytic"
    assert plan.decision_path == ()
    assert "decision=analytic" in plan.summary()


def test_tree_breaks_near_tie_with_provenance():
    # A huge margin makes every eligible candidate a near-tie, so the
    # tree's pick must win and stamp its provenance + path.
    disp = sparse.Dispatcher(backend="jax", tree=_constant_tree("csr"),
                             tree_margin=0.99)
    m = _mats()["random"]
    plan = disp.plan(m, 16)
    assert plan.chosen == "csr"
    assert plan.decision_source == "tree"
    assert plan.decision_path and plan.decision_path[-1].startswith(
        "leaf:csr")
    text = plan.summary()
    assert "decision=tree" in text and "~ tree:" in text
    # Numerics are unaffected by who chose the format.
    b = _b(N, 16)
    ref = np.asarray(sparse.formats.coo_to_dense(m)) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(disp.spmm(m, b)), ref,
                               rtol=5e-4, atol=5e-4)


def test_tree_cannot_overrule_confident_ranking():
    # DIA is policy-ineligible on random sparsity, and with margin=0 no
    # gap qualifies: the analytic winner stands in both cases.
    m = _mats()["random"]
    analytic = sparse.Dispatcher(backend="jax", tree=False).plan(m, 16)
    ineligible = sparse.Dispatcher(backend="jax",
                                   tree=_constant_tree("dia"),
                                   tree_margin=0.99).plan(m, 16)
    assert ineligible.chosen == analytic.chosen
    assert ineligible.decision_source == "analytic"
    zero_margin = sparse.Dispatcher(backend="jax",
                                    tree=_constant_tree("csr"),
                                    tree_margin=0.0).plan(m, 16)
    assert zero_margin.decision_source == "analytic"


def test_tree_ignored_for_forced_strategy():
    disp = sparse.Dispatcher(backend="jax", tree=_constant_tree("csr"),
                             tree_margin=0.99)
    plan = disp.plan(_mats()["random"], 16, strategy="ell")
    assert plan.chosen == "ell"
    assert plan.decision_source == "analytic"


def test_tree_margin_validated():
    with pytest.raises(ValueError, match="tree_margin"):
        sparse.Dispatcher(tree_margin=1.5)


def test_persisted_tree_resolved_lazily(tmp_path, monkeypatch):
    """tree=None loads the store's tree; refits invalidate cached plans
    through refresh_calibration + the fingerprint in the plan key."""
    from repro.data.dtree import DispatchTreeStore
    monkeypatch.setenv("REPRO_CALIBRATION_DIR", str(tmp_path))
    m = _mats()["random"]
    disp = sparse.Dispatcher(backend="jax", tree_margin=0.99)
    before = disp.plan(m, 16)
    assert before.decision_source == "analytic"   # no tree persisted yet
    DispatchTreeStore().save(_constant_tree("csr"), "jax")
    disp.refresh_calibration()
    after = disp.plan(m, 16)
    assert after is not before                    # new plan, not cache hit
    assert after.decision_source == "tree" and after.chosen == "csr"


# --------------------------------------------------------------------- #
# Acceptance on the model: auto keeps up with the best forced format.
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("target", ["host-jax", "v5e-pallas"])
@pytest.mark.parametrize("pattern", sorted(_mats()))
def test_auto_no_worse_than_best_forced_format(pattern, target):
    """Auto's pick predicts at least ``1 - tree_margin`` of the best
    amortized GFLOP/s of any format a forced plan accepts (a forced plan
    that raises ``ValueError`` is a format the policy refuses here).  The
    margin is the most the dispatch tree may give up; with no tree the
    pick is the best."""
    from repro.core.hardware import TPU_V5E
    disp = sparse.Dispatcher(backend="jax") if target == "host-jax" \
        else sparse.Dispatcher(hardware=TPU_V5E, backend="pallas")
    m, d = _mats()[pattern], 64
    auto = disp.plan(m, d)
    forced = {}
    for fmt in sparse.FORMATS:
        try:
            plan = disp.plan(m, d, strategy=fmt)
        except ValueError:
            continue
        forced[fmt] = plan.candidate(fmt).amortized_gflops
    assert auto.chosen in forced
    assert auto.candidate(auto.chosen).amortized_gflops >= \
        (1 - disp.tree_margin) * max(forced.values()), (auto.chosen, forced)


def _tpu_with(**fields):
    import dataclasses
    from repro.core.hardware import TPU_V5E
    return dataclasses.replace(TPU_V5E, **fields)


def test_pallas_candidate_over_vmem_budget_is_skipped():
    """Row-split holds all of B in VMEM: past the kernel budget the
    dispatcher skips it with the reason, and forcing it is refused."""
    hw = _tpu_with(vmem_bytes=2 * 2 ** 20)       # 1 MiB kernel budget
    m = erdos_renyi(4096, 8, seed=21)
    disp = sparse.Dispatcher(hardware=hw, backend="pallas",
                             calibration=False, tree=False)
    plan = disp.plan(m, 64)
    assert "rowsplit" in plan.skips
    assert "VMEM footprint" in plan.skips["rowsplit"]
    assert "1 MiB kernel budget" in plan.skips["rowsplit"]
    assert plan.chosen != "rowsplit"
    assert plan.candidate("csr").eligible       # B in HBM: no VMEM limit
    with pytest.raises(ValueError, match="VMEM footprint"):
        disp.plan(m, 64, strategy="rowsplit")


def test_pallas_binned_packing_and_smem_gates():
    """Binned visits padded past MAX_PACKED_INFLATION and metadata over
    the SMEM budget are device-limit skips with recorded reasons."""
    from repro.sparse.dispatch import MAX_PACKED_INFLATION
    # 128 KiB budget: 4096-row slabs cut each row tile's 8 nonzeros of a
    # 16384-row ER matrix into one-nonzero visits.
    hw = _tpu_with(vmem_bytes=256 * 2 ** 10)
    m = erdos_renyi(16384, 8, seed=22)
    disp = sparse.Dispatcher(hardware=hw, backend="pallas",
                             calibration=False, tree=False)
    plan = disp.plan(m, 8)
    assert "binned packing" in plan.skips["binned"]
    assert f"limit {MAX_PACKED_INFLATION}x" in plan.skips["binned"]
    tiny_smem = _tpu_with(smem_bytes=1024)      # 768 B of metadata
    plan = sparse.Dispatcher(hardware=tiny_smem, backend="pallas",
                             calibration=False, tree=False).plan(m, 8)
    assert "SMEM budget" in plan.skips["csr"]
    # The jax backend has neither limit.
    jplan = sparse.Dispatcher(hardware=tiny_smem, backend="jax",
                              calibration=False, tree=False).plan(m, 8)
    assert "csr" not in jplan.skips and "binned" not in jplan.skips

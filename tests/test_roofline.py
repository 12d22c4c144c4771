"""Distributed roofline terms + analyzer on dry-run records."""
import glob
import json

import pytest

from repro.core.analyzer import (analyze_record, format_roofline_table,
                                 sparse_component_ai)
from repro.core.hardware import TPU_V5E
from repro.core.roofline import DistributedRoofline


def _record(flops=1e15, byts=1e12, coll=1e10, chips=256):
    return {
        "arch": "x", "shape": "train_4k", "mesh": "16x16",
        "chips": chips,
        "cost": {"flops_per_device": flops / chips,
                 "bytes_per_device": byts / chips},
        "collectives": {"total": coll / chips},
        "model_flops": flops * 0.6,
    }


def test_three_terms():
    roof = DistributedRoofline(
        name="t", chips=256, hlo_flops=1e15, hlo_bytes=1e12,
        collective_bytes=1e10, hardware=TPU_V5E, model_flops=6e14)
    assert roof.compute_s == pytest.approx(1e15 / (256 * 197e12))
    assert roof.memory_s == pytest.approx(1e12 / (256 * 819e9))
    assert roof.collective_s == pytest.approx(1e10 / (256 * 50e9))
    assert roof.dominant == "compute"
    assert roof.useful_compute_ratio == pytest.approx(0.6)
    assert 0 < roof.mfu_upper_bound <= 1


def test_analyze_record_roundtrip():
    rec = analyze_record(_record())
    r = rec["roofline"]
    assert r["dominant"] in ("compute", "memory", "collective")
    assert "hint" in r
    table = format_roofline_table([rec])
    assert "train_4k" in table and "|" in table


def test_dominant_switches():
    mem = analyze_record(_record(flops=1e12, byts=1e14))
    assert mem["roofline"]["dominant"] == "memory"
    assert "AI" in mem["roofline"]["hint"] or \
        "memory" in mem["roofline"]["hint"]
    coll = analyze_record(_record(flops=1e12, byts=1e9, coll=1e13))
    assert coll["roofline"]["dominant"] == "collective"


def test_sparse_component_blocked():
    comp = {"name": "moe", "regime": "blocked_tpu", "n": 8192,
            "nnz": 8192 * 128, "t": 128, "num_blocks": 64, "d": 4096}
    out = sparse_component_ai(comp)
    assert out["mxu_utilization"] == 1.0
    assert out["ai"] > 0


def test_real_dryrun_records_if_present():
    """Schema validation over whatever the background sweep has produced."""
    paths = glob.glob("experiments/dryrun/*.json")
    if not paths:
        pytest.skip("no dry-run records yet")
    for p in paths[:10]:
        with open(p) as f:
            rec = json.load(f)
        out = analyze_record(rec)
        r = out["roofline"]
        # batch-1 decode steps can lower every matvec into reduce fusions
        # on CPU, leaving zero counted dot FLOPs — memory term still real.
        assert r["compute_s"] >= 0
        if rec["step_kind"] != "decode":
            assert r["compute_s"] > 0
        assert r["memory_s"] > 0
        assert rec["chips"] in (256, 512)
        assert rec["memory"]["temp_size_in_bytes"] >= 0


def test_device_kind_table():
    """One table maps a device kind to its spec; unknown kinds raise."""
    from repro.core.hardware import HOST_CPU, for_device_kind
    assert for_device_kind("TPU v5 lite") is TPU_V5E
    assert for_device_kind("cpu") is HOST_CPU
    for unknown in ("TPU v4", "TPU v6 lite", "NVIDIA H100"):
        with pytest.raises(ValueError, match="no HardwareSpec"):
            for_device_kind(unknown)


def test_kernel_memory_limits_fit_the_chip():
    """Kernels request half the v5e VMEM; metadata gets 3/4 of SMEM."""
    from repro.core.hardware import (HOST_CPU, kernel_smem_limit,
                                     kernel_vmem_limit)
    assert kernel_vmem_limit(TPU_V5E) == 64 * 2 ** 20
    assert kernel_smem_limit(TPU_V5E) == 768 * 2 ** 10
    assert kernel_smem_limit(HOST_CPU) == 0          # no SMEM on the host

"""Core contribution: sparsity-aware roofline models for SpMM."""
from repro.core.hardware import HardwareSpec, PERLMUTTER_MILAN, TPU_V5E, by_name
from repro.core.roofline import DistributedRoofline, RooflinePoint, place
from repro.core.sparsity_models import (
    TrafficBreakdown,
    ai_blocked,
    ai_blocked_tpu,
    ai_diagonal,
    ai_random,
    ai_scale_free,
    arithmetic_intensity,
    expected_occupied_columns,
    flops_spmm,
    hub_edge_fraction,
    mxu_utilization,
)
from repro.core.patterns import (
    COOMatrix, banded, block_diagonal, blocked, erdos_renyi, fit_generator,
    hex_mesh, scale_free, serving_suite,
)
from repro.core.classify import StructureReport, classify

__all__ = [
    "HardwareSpec", "PERLMUTTER_MILAN", "TPU_V5E", "by_name",
    "DistributedRoofline", "RooflinePoint", "place",
    "TrafficBreakdown", "ai_blocked", "ai_blocked_tpu", "ai_diagonal",
    "ai_random", "ai_scale_free", "arithmetic_intensity",
    "expected_occupied_columns", "flops_spmm", "hub_edge_fraction",
    "mxu_utilization",
    "COOMatrix", "banded", "block_diagonal", "blocked", "erdos_renyi",
    "fit_generator", "hex_mesh", "scale_free", "serving_suite",
    "StructureReport", "classify",
]

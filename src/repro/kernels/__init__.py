"""Pallas TPU kernels for the paper's compute hot-spots (SpMM variants).

``repro.kernels.registry`` is the uniform entry point: one ``KernelSpec``
(layout prep, launch, roofline estimate, VMEM footprint) per
``(format, backend)`` pair.  A caller that packs its own layout calls the
``*_pallas`` kernel in its module directly.
"""
from repro.kernels import registry
from repro.kernels.registry import (
    KernelContext, KernelRoofline, KernelSpec, band_to_blocks,
    bcsr_kernel_roofline, choose_b_tile, csr_kernel_roofline,
    dia_kernel_roofline, feature_matrix, formats_for,
    grouped_matmul_roofline, pad_empty_block_rows,
)

__all__ = [
    "registry",
    "KernelContext", "KernelRoofline", "KernelSpec", "band_to_blocks",
    "bcsr_kernel_roofline", "choose_b_tile", "csr_kernel_roofline",
    "dia_kernel_roofline", "feature_matrix", "formats_for",
    "grouped_matmul_roofline", "pad_empty_block_rows",
]

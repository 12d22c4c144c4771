"""Hardware descriptions used by the roofline models.

The paper's testbed is one socket of an AMD EPYC 7763 (Perlmutter CPU node);
our deployment target is a TPU v5e pod slice.  Both are expressed with the
same dataclass so every roofline routine is hardware-agnostic.

:func:`for_device_kind` is the one place a running device is mapped to its
spec (``DEVICE_KINDS``, keyed by ``jax.Device.device_kind``); a device kind
missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Architectural ceilings for a single device (chip / socket)."""

    name: str
    peak_flops: float          # FLOP/s (per device) at the relevant precision
    hbm_bandwidth: float       # bytes/s main-memory bandwidth (per device)
    link_bandwidth: float      # bytes/s per inter-device link (0 => none)
    vmem_bytes: int = 0        # software-managed fast memory (VMEM / LLC)
    smem_bytes: int = 0        # scalar memory (TPU SMEM; 0 = no limit)
    hbm_bytes: int = 0         # main memory capacity per device
    mxu_tile: tuple = (128, 128)  # native matmul tile (rows, cols)
    #: Aggregate interconnect bandwidth one device can drive during a
    #: collective (bytes/s).  0 means "unknown": model collectives at the
    #: per-link ``link_bandwidth``, or — when that is 0 too (single-host
    #: virtual devices) — at ``hbm_bandwidth``, since virtual-device
    #: collectives are memcpys through the same DRAM.
    ici_bytes_per_s: float = 0.0
    #: Fixed launch/synchronization latency per collective hop (seconds);
    #: collectives pay ``ceil(log2(devices))`` hops in the cost model.
    collective_latency_s: float = 10e-6

    @property
    def ridge_point(self) -> float:
        """Arithmetic intensity where memory-bound meets compute-bound."""
        return self.peak_flops / self.hbm_bandwidth

    def attainable(self, ai: float) -> float:
        """Classic roofline: P = min(beta * AI, pi)."""
        return min(self.hbm_bandwidth * ai, self.peak_flops)

    @property
    def collective_bandwidth(self) -> float:
        """Effective bytes/s a device moves during collectives (see
        ``ici_bytes_per_s`` for the fallback chain)."""
        return (self.ici_bytes_per_s or self.link_bandwidth
                or self.hbm_bandwidth)

    def fingerprint(self) -> str:
        """Stable id of this spec's *compute* identity (12 hex chars).

        Keys persisted kernel calibrations (``repro.core.calibrate``): a
        calibration fitted on one device must not be applied to another.
        Bandwidth fields are deliberately excluded — ``hbm_bandwidth`` is
        routinely replaced by the run-time STREAM measurement
        (``benchmarks/spmm_suite.make_dispatcher``), and the fitted
        ``(peak_fraction, d_half)`` ceilings describe the compute side
        of the roofline, which that substitution does not change.  The
        interconnect fields (``ici_bytes_per_s``,
        ``collective_latency_s``) are excluded for the same reason: they
        only enter the sharded communication model, never the per-device
        compute ceiling a calibration fits.
        """
        payload = json.dumps({
            "name": self.name, "peak_flops": self.peak_flops,
            "vmem_bytes": self.vmem_bytes,
            "mxu_tile": list(self.mxu_tile),
        }, sort_keys=True)
        return hashlib.sha256(payload.encode()).hexdigest()[:12]


# --- The paper's evaluation platform (Table IV + measured STREAM beta). ---
PERLMUTTER_MILAN = HardwareSpec(
    name="amd-epyc-7763-1socket",
    peak_flops=64 * 2.45e9 * 16,      # 64 cores x 2.45 GHz x (AVX2 FMA: 16 dp flop/cyc)
    hbm_bandwidth=122.6e9,            # STREAM-measured in the paper
    link_bandwidth=0.0,
    vmem_bytes=256 * 2**20,           # 256 MiB L3 per socket
    hbm_bytes=512 * 2**30,
    mxu_tile=(1, 4),                  # AVX2 dp vector as the "tile"
)

# --- Deployment target: TPU v5e (per chip).  Peaks: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s ICI); 128 MiB of VMEM per TensorCore. ---
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops=197e12,                # bf16
    hbm_bandwidth=819e9,
    link_bandwidth=50e9,              # per ICI link
    vmem_bytes=128 * 2**20,
    smem_bytes=2**20,                 # the compiler's SMEM capacity
    hbm_bytes=16 * 2**30,
    mxu_tile=(128, 128),
    ici_bytes_per_s=4 * 50e9,         # 4 ICI links per chip (2D torus)
    collective_latency_s=1e-6,
)

# Host CPU of this container (used only for wall-clock benchmark *context*;
# beta is measured at runtime by benchmarks/stream.py, mirroring the paper).
HOST_CPU = HardwareSpec(
    name="container-host-cpu",
    peak_flops=50e9,
    hbm_bandwidth=10e9,               # placeholder; STREAM overrides at runtime
    link_bandwidth=0.0,
    vmem_bytes=32 * 2**20,
    hbm_bytes=35 * 2**30,
    mxu_tile=(1, 4),
    # Virtual host devices share one DRAM: collectives are memcpys, so
    # collective_bandwidth falls back to hbm_bandwidth (ici stays 0).
    collective_latency_s=20e-6,
)


#: ``jax.Device.device_kind`` -> spec.  "cpu" is the CPU backend the
#: tests run on (Pallas kernels in interpret mode).
DEVICE_KINDS = {
    "TPU v5 lite": TPU_V5E,
    "cpu": HOST_CPU,
}


@dataclasses.dataclass(frozen=True)
class KernelCosts:
    """Seconds one step of each Pallas kernel takes on a chip, measured
    there with the kernel alone (``tools/kernel_costs.py``).

    The roofline says what bytes and FLOPs allow; these say what the
    kernels issue, which on the chip takes longer.  The dispatcher floors
    each Pallas candidate's predicted time by them.
    """

    row_dma_s: float       # CSR family: issuing one packed slot's B-row DMA
    block_step_s: float    # BCSR: one stored block's share of a call,
    #                        after the block's bytes at HBM bandwidth
    row_load_s: float      # binned / rowsplit: one B-row load from VMEM


#: ``device_kind`` -> measured kernel costs; a kind missing here plans on
#: the roofline alone.  TPU v5 lite: one chip, d = 128, float32, the
#: chip benchmark's structures (a CSR call 0.3257 s for 20.25M slots on
#: ``lj_powerlaw``, 1.2229 s for 76.55M on ``fem_audikw``; the block-row
#: BCSR kernel 0.0379 s for 284,193 blocks of 64; binned 0.7938 s for
#: 77.10M slots).
KERNEL_COSTS = {
    "TPU v5 lite": KernelCosts(row_dma_s=16.0e-9, block_step_s=0.1135e-6,
                               row_load_s=10.3e-9),
}


def kernel_costs(hw: HardwareSpec):
    """The :class:`KernelCosts` measured on the device ``hw`` describes,
    or None: a spec that differs from every known device kind's (a
    replaced field, the host CPU) has no measured costs."""
    for kind, spec in DEVICE_KINDS.items():
        if spec == hw:
            return KERNEL_COSTS.get(kind)
    return None


def for_device_kind(kind: str) -> HardwareSpec:
    """The spec of a device reporting ``device_kind == kind``.

    Raises:
        ValueError: for a kind that is not in ``DEVICE_KINDS``: planning
            an unknown chip with another chip's ceilings would be wrong.
    """
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"no HardwareSpec for device kind {kind!r}; known kinds: "
            f"{sorted(DEVICE_KINDS)}") from None


def device_hardware() -> HardwareSpec:
    """The spec of JAX's default device (``jax.devices()[0]``)."""
    import jax
    return for_device_kind(jax.devices()[0].device_kind)


def kernel_vmem_limit(hw: HardwareSpec) -> int:
    """Scoped VMEM every Pallas kernel requests (``vmem_limit_bytes``).

    Half of the physical VMEM: the rest stays with Mosaic's own scratch.
    Every resident block a kernel holds, double-buffered, is sized from
    this budget, and the dispatcher skips a Pallas candidate whose
    modelled footprint exceeds it.
    """
    return hw.vmem_bytes // 2


def kernel_smem_limit(hw: HardwareSpec) -> int:
    """SMEM a Pallas kernel's scalar-prefetched metadata may take.

    Three quarters of SMEM (the rest holds the kernels' scalar scratch);
    0 where the spec states no SMEM size (no limit is checked).
    """
    return hw.smem_bytes * 3 // 4


def by_name(name: str) -> HardwareSpec:
    table = {h.name: h for h in (PERLMUTTER_MILAN, TPU_V5E, HOST_CPU)}
    table.update({"v5e": TPU_V5E, "milan": PERLMUTTER_MILAN, "host": HOST_CPU})
    return table[name]

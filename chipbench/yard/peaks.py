"""Peak rates of each device the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not here is an error.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16 and 819 GB/s HBM per
chip.  The bf16 rate is the chip's highest, so a least time taken from it
stays a lower bound for float32 work too.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}

#: Used only by ``--rehearse`` on the CPU, to exercise the arithmetic.
#: A rehearsal's result says ``cpu`` and is no measurement.
REHEARSAL = {"flops_per_s": 1e11, "bytes_per_s": 1e10}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them, with their source, to chipbench/yard/peaks.py")
    return PEAKS[device_kind]

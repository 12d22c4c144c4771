"""On-host calibration of the per-format compute ceilings.

The dispatcher caps every bandwidth roofline with a format compute
ceiling ``peak * peak_fraction * useful * d / (d + d_half)``.  The
``(peak_fraction, d_half)`` pairs shipped in
``repro.sparse.dispatch.DEFAULT_EFFICIENCY`` were measured on one
container and baked in — exactly the practice SpChar (Sgherzi et al.,
2023) warns against: ceiling parameters are properties of a (host,
implementation) pair and must be learned where the code runs.

This module replaces the constants with a measurement:

    from repro.core.calibrate import CalibrationStore, calibrate
    cal = calibrate(hw)                  # short microbenchmark sweep
    CalibrationStore().save(cal)         # persists per-host JSON
    # Dispatcher picks it up automatically; CandidateEval.ceiling_source
    # flips from "default" to "calibrated".

``calibrate`` runs, per registered kernel spec (``(format, backend)`` in
``repro.kernels.registry``), a small structure-matched SpMM at several
dense widths, and fits the ceiling shape ``g(d) = G * d / (d + d_half)``
to the measured useful GFLOP/s via the linearization

    1/g = (1/G) + (d_half/G) * (1/d)        (least squares on 1/d)

so ``peak_fraction = G / (peak * useful_fraction)``.  Results are
persisted as JSON under ``~/.cache/repro/calibrations/`` (override with
``$REPRO_CALIBRATION_DIR``), one file per ``HardwareSpec.name``, stamped
with ``HardwareSpec.fingerprint()``; a stale file whose fingerprint no
longer matches the active spec is ignored, falling back to the defaults.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.hardware import HardwareSpec

#: Dense widths for the fit: spread in 1/d so both the asymptote and the
#: half-saturation width are constrained.
DEFAULT_D_VALUES: Tuple[int, ...] = (4, 16, 64, 256)

#: Clamps keeping a noisy fit inside physically meaningful territory.
PEAK_FRACTION_RANGE: Tuple[float, float] = (1e-5, 1.0)
D_HALF_RANGE: Tuple[float, float] = (0.0, 4096.0)


def fit_ceiling(d_values: Sequence[int],
                gflops: Sequence[float]) -> Tuple[float, float]:
    """Fit ``g(d) = G * d / (d + d_half)`` to measured throughputs.

    Args:
        d_values: dense widths of the sweep (>= 2 distinct values).
        gflops: measured useful GFLOP/s at each width.

    Returns:
        ``(G, d_half)`` — the saturated throughput (same unit as the
        input) and the half-saturation width.  Degenerate sweeps (flat,
        noisy-decreasing, or non-positive) fall back to
        ``(max(gflops), 0.0)``.
    """
    d = np.asarray(list(d_values), dtype=np.float64)
    g = np.asarray(list(gflops), dtype=np.float64)
    if d.shape != g.shape or d.size < 2:
        raise ValueError(f"need matched sweeps of >= 2 points, got "
                         f"{d.size} vs {g.size}")
    if np.any(g <= 0) or np.unique(d).size < 2:
        return float(max(g.max(), 1e-9)), 0.0
    slope, intercept = np.polyfit(1.0 / d, 1.0 / g, 1)
    if intercept <= 0 or slope < 0:
        # Throughput not saturating (or decreasing with d): the model's
        # asymptote is unconstrained; report the best measurement flat.
        return float(g.max()), 0.0
    return float(1.0 / intercept), float(slope / intercept)


@dataclasses.dataclass(frozen=True)
class FormatCalibration:
    """Fitted ceiling for one (format, backend, precision) on one host."""

    format: str
    backend: str
    peak_fraction: float
    d_half: float
    sustained_gflops: float           # fitted asymptote, useful GFLOP/s
    useful_fraction: float            # of the calibration matrix
    measured: Dict[int, float]        # d -> measured useful GFLOP/s
    #: Storage precision token the sweep ran at ("f32i32" default keeps
    #: files saved before the precision axis loading cleanly).
    precision: str = "f32i32"


@dataclasses.dataclass(frozen=True)
class Calibration:
    """A full calibration run: per-format ceilings + provenance."""

    hardware: str                     # HardwareSpec.name
    fingerprint: str                  # HardwareSpec.fingerprint()
    backend: str
    entries: Tuple[FormatCalibration, ...]
    #: ``repro.kernels.registry.REGISTRY_VERSION`` at fit time; 0 marks
    #: files saved before versioning existed.  ``staleness_note`` flags
    #: calibrations predating the active kernel set.
    registry_version: int = 0

    def efficiency(self, precision: str = "f32i32"
                   ) -> Dict[str, Tuple[float, float]]:
        """The ``format -> (peak_fraction, d_half)`` ceiling table.

        ``precision`` selects dtype-specific fits: a format's entry for
        the requested token wins; formats calibrated only at fp32 fall
        back to that fit (operand rounding barely moves the *compute*
        ceiling — what a reduced precision changes is the bandwidth
        roofline, which the dispatcher sizes separately).
        """
        out = {e.format: (e.peak_fraction, e.d_half)
               for e in self.entries if e.precision == "f32i32"}
        if precision != "f32i32":
            out.update({e.format: (e.peak_fraction, e.d_half)
                        for e in self.entries if e.precision == precision})
        return out

    def summary(self) -> str:
        """Render the fitted ceilings as a human-readable table."""
        lines = [f"Calibration({self.hardware}, fp={self.fingerprint}, "
                 f"backend={self.backend})"]
        for e in self.entries:
            lines.append(
                f"  {e.format:8s} {e.precision:7s} "
                f"peak_fraction={e.peak_fraction:.4f} "
                f"d_half={e.d_half:6.1f}  "
                f"(sustained {e.sustained_gflops:.2f} GF/s useful, "
                f"useful_fraction {e.useful_fraction:.3f})")
        return "\n".join(lines)


class CalibrationStore:
    """Persistence for :class:`Calibration` results, one file per
    (host, backend).

    Files live under ``$REPRO_CALIBRATION_DIR`` (or
    ``~/.cache/repro/calibrations``) as
    ``<HardwareSpec.name>-<backend>.json`` — jax and pallas ceilings for
    the same host describe different implementations and must not
    overwrite or answer for each other.  ``load`` validates both the
    stored fingerprint against the active spec and the stored backend
    against the requested one: any mismatch returns ``None`` so callers
    fall back to the default ceilings.
    """

    def __init__(self, root: Optional[os.PathLike] = None):
        """Open (without touching the filesystem) the store at ``root``.

        Args:
            root: directory for the JSON files; defaults to
                ``$REPRO_CALIBRATION_DIR`` or ``~/.cache/repro/calibrations``.
        """
        if root is None:
            root = os.environ.get("REPRO_CALIBRATION_DIR") or (
                pathlib.Path.home() / ".cache" / "repro" / "calibrations")
        self.root = pathlib.Path(root)

    def path_for(self, hw: HardwareSpec,
                 backend: str = "jax") -> pathlib.Path:
        """The JSON path holding ``hw``'s calibration for ``backend``."""
        return self.root / f"{hw.name}-{backend}.json"

    def save(self, cal: Calibration) -> pathlib.Path:
        """Write ``cal`` (creating the store directory) and return the path."""
        self.root.mkdir(parents=True, exist_ok=True)
        path = self.root / f"{cal.hardware}-{cal.backend}.json"
        payload = dataclasses.asdict(cal)
        payload["saved_unix"] = time.time()
        path.write_text(json.dumps(payload, indent=2), encoding="utf-8")
        return path

    def load(self, hw: HardwareSpec,
             backend: str = "jax") -> Optional[Calibration]:
        """Read the calibration for ``(hw, backend)``; None when absent
        or stale.

        Stale means the stored fingerprint differs from
        ``hw.fingerprint()`` (fitted against a different compute
        identity) or the stored backend differs from the requested one
        (fitted against a different kernel implementation); either way
        the calibration must not be applied.
        """
        path = self.path_for(hw, backend)
        if not path.is_file():
            return None
        try:
            payload = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            return None
        if payload.get("fingerprint") != hw.fingerprint():
            return None
        if payload.get("backend", "jax") != backend:
            return None
        entries = tuple(
            FormatCalibration(
                format=e["format"], backend=e["backend"],
                peak_fraction=float(e["peak_fraction"]),
                d_half=float(e["d_half"]),
                sustained_gflops=float(e["sustained_gflops"]),
                useful_fraction=float(e["useful_fraction"]),
                measured={int(k): float(v)
                          for k, v in e["measured"].items()},
                precision=e.get("precision", "f32i32"))
            for e in payload.get("entries", ()))
        return Calibration(hardware=payload["hardware"],
                           fingerprint=payload["fingerprint"],
                           backend=payload.get("backend", "jax"),
                           entries=entries,
                           registry_version=int(
                               payload.get("registry_version", 0)))

    def staleness_note(self, hw: HardwareSpec,
                       backend: str = "jax") -> Optional[str]:
        """One-line staleness warning for ``(hw, backend)``, or ``None``.

        Two conditions earn a note (both mean the persisted numbers do
        not describe what is about to run): the stored fingerprint does
        not match the active spec (``load`` already refuses it — this
        surfaces *why* the dispatcher fell back to defaults), or the
        calibration was fitted against an older kernel registry version
        than the one registered now.  A missing file is not stale:
        defaults are then the intended behavior.
        """
        path = self.path_for(hw, backend)
        if not path.is_file():
            return None
        try:
            payload = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            return (f"calibration file {path.name} is unreadable; using "
                    f"default ceilings (re-run python -m "
                    f"repro.launch.serve --calibrate)")
        if payload.get("fingerprint") != hw.fingerprint():
            return (f"calibration {path.name} was fitted for fingerprint "
                    f"{payload.get('fingerprint')}, active spec is "
                    f"{hw.fingerprint()}; using default ceilings (re-run "
                    f"python -m repro.launch.serve --calibrate)")
        from repro.kernels import registry
        stored = int(payload.get("registry_version", 0))
        if stored < registry.REGISTRY_VERSION:
            return (f"calibration {path.name} predates kernel registry "
                    f"v{registry.REGISTRY_VERSION} (fitted at "
                    f"v{stored}); ceilings may describe retired kernels "
                    f"(re-run python -m repro.launch.serve --calibrate)")
        return None


def _calibration_matrices(scale: int, bcsr_block: int) -> Dict[str, object]:
    """One structure-matched COOMatrix generator thunk per format.

    Each format gets the structure it exists for, sized to clear the
    dispatch policy gates (BCSR divisibility + dense blocks, DIA band
    width, ELL balanced degrees).
    """
    from repro.core import patterns
    n = 2 ** scale
    t = bcsr_block
    return {
        "csr": lambda: patterns.erdos_renyi(n, 8, seed=11),
        "ell": lambda: patterns.erdos_renyi(n, 8, seed=12),
        "bcsr": lambda: patterns.blocked(
            n, t=t, num_blocks=max(2 * (n // t), 1),
            nnz_per_block=int(t * t * 0.8), seed=13),
        "dia": lambda: patterns.banded(n, 3, fill=1.0, seed=14),
        # The scale-free tier calibrates on the structure it targets:
        # skewed degree distributions with hub rows/columns.
        "binned": lambda: patterns.scale_free(n, 8, alpha=2.05, seed=15),
        "rowsplit": lambda: patterns.scale_free(n, 8, alpha=2.2, seed=16),
        "ell_coo": lambda: patterns.scale_free(n, 8, seed=17),
    }


def _best_of(fn, repeats: int) -> float:
    import jax
    jax.block_until_ready(fn())          # warm-up: jit compile, caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate(hw: HardwareSpec, *, backend: str = "jax",
              formats: Optional[Sequence[str]] = None,
              d_values: Sequence[int] = DEFAULT_D_VALUES,
              scale: int = 9, repeats: int = 3, bcsr_block: int = 32,
              precisions: Sequence[str] = ("f32i32",),
              store: Optional[CalibrationStore] = None) -> Calibration:
    """Measure and fit the per-format compute ceilings on this host.

    For each format, runs the registered kernel (through the dispatcher's
    executor, so the measured path is the served path) on a
    structure-matched matrix across ``d_values``, fits
    ``(peak_fraction, d_half)`` (see :func:`fit_ceiling`), and optionally
    persists the result.

    Args:
        hw: the hardware spec the ceilings are expressed against
            (``peak_fraction`` is relative to ``hw.peak_flops``).
        backend: ``"jax"`` or ``"pallas"`` — which registered kernels to
            calibrate.  Off-TPU, pallas interpret-mode timings measure
            the interpreter, not the kernel; calibrate ``"jax"`` there.
        formats: formats to sweep; defaults to every format registered
            under ``backend`` that is also a dispatch format.
        d_values: dense widths of the sweep.
        scale: matrix dimension exponent (n = 2**scale).
        repeats: min-of-N timing repeats per cell.
        bcsr_block: BCSR block edge for the blocked calibration matrix.
        precisions: precision tokens to fit per format (each a separate
            :class:`FormatCalibration` entry); combos a kernel spec does
            not support, or that this matrix cannot legally pack (int16
            extent), are skipped.  Default fits fp32 only.
        store: when given, ``store.save`` the result before returning.

    Returns:
        The fitted :class:`Calibration`.
    """
    from repro import sparse
    from repro.kernels import registry

    if formats is None:
        formats = [f for f in sparse.FORMATS
                   if f in registry.formats_for(backend)]
    gens = _calibration_matrices(scale, bcsr_block)
    unknown = sorted(set(formats) - set(gens))
    if unknown:
        raise ValueError(f"no calibration matrix for formats {unknown}")

    # Ceilings must not influence the measurement: strategies are forced,
    # and the dispatcher is isolated from any existing calibration file.
    disp = sparse.Dispatcher(hardware=hw, backend=backend,
                             bcsr_block=bcsr_block, calibration=False)
    entries = []
    for fmt in formats:
        spec = registry.get(fmt, backend)
        for prec in precisions:
            if prec not in spec.supported_precisions:
                continue
            m = gens[fmt]()
            rng = np.random.default_rng(7)
            measured: Dict[int, float] = {}
            useful_fraction = 1.0
            try:
                for d in d_values:
                    import jax.numpy as jnp
                    b = jnp.asarray(
                        rng.normal(size=(m.n, d)).astype(np.float32))
                    plan = disp.plan(m, d, strategy=fmt, precision=prec)
                    useful_fraction = plan.candidate(fmt).useful_fraction
                    run = disp.executor(m, plan)
                    dt = _best_of(lambda run=run, b=b: run(b), repeats)
                    measured[int(d)] = 2.0 * m.nnz * d / dt / 1e9
            except ValueError:
                # e.g. int16 extent illegal for this matrix: skip the
                # combo, the fp32 fit still answers for the format.
                continue
            g_inf, d_half = fit_ceiling(list(measured),
                                        list(measured.values()))
            lo, hi = PEAK_FRACTION_RANGE
            peak_fraction = float(np.clip(
                g_inf * 1e9 / (hw.peak_flops * max(useful_fraction, 1e-9)),
                lo, hi))
            d_half = float(np.clip(d_half, *D_HALF_RANGE))
            entries.append(FormatCalibration(
                format=fmt, backend=backend, peak_fraction=peak_fraction,
                d_half=d_half, sustained_gflops=g_inf,
                useful_fraction=useful_fraction, measured=measured,
                precision=prec))
    cal = Calibration(hardware=hw.name, fingerprint=hw.fingerprint(),
                      backend=backend, entries=tuple(entries),
                      registry_version=registry.REGISTRY_VERSION)
    if store is not None:
        store.save(cal)
    return cal

"""Structure-aware SpMM dispatch: the paper's thesis as runtime architecture.

The paper's core claim is that no single roofline model predicts SpMM
across sparsity structures — the right storage format (and kernel) must be
chosen per matrix structure.  This module turns that claim into the
system's dispatch layer:

    plan = plan_spmm(m, d)            # inspectable decision record
    c = spmm(m, b, strategy="auto")   # classify -> model -> convert -> run

For each candidate format (CSR / ELL / BCSR / DIA) the dispatcher

  1. applies the *applicability policy* (the SpChar-style structural
     gates), emitting a skip reason when a format is rejected;
  2. evaluates the candidate's sparsity-aware arithmetic intensity on the
     active HardwareSpec: B-traffic from the detected structural regime
     (Section III models), A-traffic from the format's actual storage;
  3. caps the bandwidth roofline ``beta * AI`` with a format compute
     ceiling ``peak * efficiency * useful_fraction`` — dense-padded formats
     (ELL padding, BCSR's t x t blocks, DIA's in-band zeros) issue more
     FLOPs than the 2*d*nnz useful ones, and on gather-bound hosts the
     implementation efficiency, not DRAM, is the binding resource (the
     refuted-claims discussion in the benchmark suite);
  4. amortizes the one-time format conversion cost over an expected reuse
     count, so a format that is 10% faster per call but costs 50 calls to
     build loses at reuse=8 and wins at reuse=1000.

The winning ``(format, kernel)`` pair is returned as a cached
``DispatchPlan``; ``spmm`` executes it with per-matrix conversion caching,
selecting the pure-JAX or the Pallas kernel path per ``backend``.

Conversion-cost caveat: conversion time is modeled as streaming the built
format at ``beta`` (read + write); the host-side converters are not that
fast, so treat amortized numbers as a lower bound on the break-even reuse.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibrate import CalibrationStore
from repro.core.classify import StructureReport, block_stats, classify
from repro.core.precision import (DEFAULT_PRECISION, INT16_MAX_EXTENT,
                                  PRECISIONS, Precision, as_precision)
from repro.data.dtree import (DecisionTree, DispatchTreeStore,
                              features_from_report)
from repro.core.hardware import (
    HardwareSpec, KernelCosts, device_hardware, kernel_costs,
    kernel_smem_limit)
from repro.core.roofline import ComputeCeiling
from repro.core import sparsity_models as sm
from repro.core.patterns import COOMatrix
from repro import obs
from repro.sparse import formats as fmt

FORMATS: Tuple[str, ...] = ("csr", "ell", "bcsr", "dia",
                            "binned", "rowsplit", "ell_coo")
STRATEGIES: Tuple[str, ...] = ("auto",) + FORMATS
#: Formats whose Pallas picks all run the CSR gather kernel on one layout.
CSR_FAMILY: Tuple[str, ...] = ("csr", "ell", "ell_coo")

#: Per-format compute ceiling: ``(peak_fraction, d_half)``.  Each
#: implementation sustains ``peak * peak_fraction * d / (d + d_half)`` on
#: its *issued* FLOPs (padding included): per-nonzero index/bookkeeping
#: work is amortized over the d dense columns, so throughput saturates
#: with growing d at a format-specific rate — CSR's scalar segment-sum has
#: the largest per-nonzero overhead (d_half ~ 100), DIA's streaming axpy
#: almost none (d_half ~ 3).  These are the *fallback* constants, once
#: measured on one reference container; ``repro.core.calibrate`` fits
#: host-specific replacements and the dispatcher prefers a persisted
#: calibration whenever one matches the active HardwareSpec fingerprint
#: (each candidate records its provenance in ``ceiling_source``).
#: Override per dispatcher via ``Dispatcher(efficiency=...)``.
DEFAULT_EFFICIENCY: Dict[str, Tuple[float, float]] = {
    "csr": (0.030, 112.0),
    "ell": (0.040, 8.0),
    "bcsr": (0.600, 28.0),
    "dia": (0.057, 3.0),
    # Scale-free-regime kernels (PR 8).  On compute-bound hosts these sit
    # strictly below CSR (same gather/segment-sum algebra plus binning /
    # window bookkeeping), so they only win where their *bandwidth* model
    # does — i.e. on bandwidth-bound parts where slab binning collapses
    # the B-traffic term.  Calibration replaces these like any other.
    "binned": (0.022, 112.0),
    "rowsplit": (0.027, 104.0),
    # ell_coo's jax path is an ELL body scan *plus* a COO-tail
    # segment-sum; the tail pass inherits CSR's gather d-scaling, so the
    # blended d_half sits between ELL's 8 and CSR's 112.  (With ELL's
    # d_half=8 it over-predicted small-d launches on *blocked* matrices
    # and beat BCSR on FEM suites it measures 2x slower on.)
    "ell_coo": (0.036, 40.0),
}

#: Largest packed-slot count per nonzero a Pallas binned layout may have.
#: Each (slab, row tile) visit is padded to whole chunks; past this the
#: layout (and the HBM it takes) grows with the padding, not with nnz.
MAX_PACKED_INFLATION: float = 1.5


@dataclasses.dataclass(frozen=True)
class CandidateEval:
    """One (format, precision) audit record inside a DispatchPlan."""

    format: str
    eligible: bool
    skip_reason: Optional[str]        # None when eligible
    ai: Optional[float]               # sparsity-aware arithmetic intensity
    useful_fraction: Optional[float]  # useful FLOPs / issued FLOPs
    predicted_gflops: Optional[float]     # steady-state (no conversion)
    amortized_gflops: Optional[float]     # incl. conversion / reuse
    conversion_bytes: Optional[float]
    params: dict = dataclasses.field(default_factory=dict)
    #: Compute-ceiling provenance: "default" | "calibrated" | "override".
    ceiling_source: str = "default"
    #: Storage precision token this row was modeled at
    #: (``repro.core.precision.Precision.token``): "f32i32" | "bf16i32" |
    #: "bf16i16".  Reduced-precision rows gated out by the caller's
    #: ``tolerance`` (or an int16-illegal extent) keep their predictions
    #: for audit but carry ``eligible=False`` and the gate's
    #: ``skip_reason``.
    precision: str = "f32i32"


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """The dispatcher's full, inspectable decision for one (matrix, d)."""

    chosen: str                       # winning format
    strategy: str                     # "auto" or the forced format
    regime: str                       # detected sparsity regime
    d: int
    reuse: int                        # conversion amortization horizon
    backend: str                      # "jax" | "pallas"
    hardware: str                     # HardwareSpec.name used for prediction
    candidates: Tuple[CandidateEval, ...]
    #: Winning storage precision (token): the layouts are packed and the
    #: kernel launched at these value/index dtypes.
    precision: str = "f32i32"
    #: The relative error budget the accuracy gate ran with; reduced
    #: value dtypes were eligible only where ``tolerance >= dtype eps``.
    tolerance: float = 0.0
    #: Staleness warning from the CalibrationStore (fingerprint mismatch
    #: or a calibration predating the kernel registry version); None when
    #: the store is silent.  Rendered by :meth:`summary`.
    calibration_note: Optional[str] = None
    #: Who made the final call: ``"analytic"`` (the roofline ranking) or
    #: ``"tree"`` (the fitted dispatch tree, consulted because the
    #: analytic top two were within ``tree_margin`` of each other).
    #: Provenance, exactly like ``ceiling_source`` for ceilings.
    decision_source: str = "analytic"
    #: The tree's split trail (``feature<=thr`` ... ``leaf:fmt(n=..)``)
    #: when ``decision_source == "tree"``; empty otherwise.
    decision_path: Tuple[str, ...] = ()

    @property
    def skips(self) -> Dict[str, str]:
        """format -> reason, for every policy-rejected candidate.

        Keyed off the baseline fp32 rows (every format has one and the
        baseline is never precision-gated), so the reasons here are
        exactly the structural policy reasons; precision-gate rejections
        live in :attr:`precision_skips`.
        """
        return {c.format: c.skip_reason for c in self.candidates
                if not c.eligible and c.precision == "f32i32"}

    @property
    def precision_skips(self) -> Dict[Tuple[str, str], str]:
        """(format, precision) -> reason for precision-gated rows.

        Only rows whose *precision* was rejected (tolerance too tight for
        bf16, int16 extent overflow) appear; rows skipped for structural
        policy are in :attr:`skips`.
        """
        return {(c.format, c.precision): c.skip_reason
                for c in self.candidates
                if not c.eligible and c.precision != "f32i32"
                and c.format not in self.skips}

    @property
    def ceiling_sources(self) -> Dict[str, str]:
        """format -> compute-ceiling provenance (default/calibrated/override)."""
        return {c.format: c.ceiling_source for c in self.candidates}

    def candidate(self, name: str,
                  precision: Optional[str] = None) -> CandidateEval:
        """Return the :class:`CandidateEval` for format ``name``.

        Args:
            name: one of ``FORMATS`` (``"csr" | "ell" | "bcsr" | "dia" |
                "binned" | "rowsplit" | "ell_coo"``).
            precision: a precision token ("f32i32", "bf16i32", "bf16i16")
                to pick that exact row.  ``None`` returns the row the
                plan actually ranked for this format: the chosen row when
                ``name`` won, else the best eligible row, else the fp32
                baseline.

        Returns:
            The audit record for that (format, precision).

        Raises:
            KeyError: if the pair was not evaluated in this plan.
        """
        if precision is not None:
            token = as_precision(precision).token
            for c in self.candidates:
                if c.format == name and c.precision == token:
                    return c
            raise KeyError((name, token))
        if name == self.chosen:
            return self.candidate(name, self.precision)
        rows = [c for c in self.candidates if c.format == name]
        if not rows:
            raise KeyError(name)
        eligible = [c for c in rows if c.eligible]
        if eligible:
            return max(eligible, key=lambda c: c.amortized_gflops or 0.0)
        return next(c for c in rows if c.precision == "f32i32")

    def summary(self) -> str:
        """Render the decision as a human-readable multi-line table."""
        lines = [f"DispatchPlan(regime={self.regime}, d={self.d}, "
                 f"backend={self.backend}, hw={self.hardware}, "
                 f"reuse={self.reuse}, tol={self.tolerance:.1e}, "
                 f"decision={self.decision_source})"
                 f" -> {self.chosen} @ {self.precision}"]
        for c in self.candidates:
            mark = "*" if (c.format == self.chosen
                           and c.precision == self.precision) else " "
            if c.predicted_gflops is not None:
                perf = (f"AI={c.ai:6.3f}  pred={c.predicted_gflops:7.2f}"
                        f"  amort={c.amortized_gflops:7.2f} GF/s"
                        f" [{c.ceiling_source}]")
            else:
                perf = "(not modeled)"
            tail = "" if c.eligible else f"  SKIP: {c.skip_reason}"
            lines.append(f" {mark} {c.format:8s} {c.precision:7s} "
                         f"{perf}{tail}")
        if self.decision_path:
            lines.append(" ~ tree: " + " -> ".join(self.decision_path))
        if self.calibration_note:
            lines.append(f" ! {self.calibration_note}")
        return "\n".join(lines)


def _degree_stats(m: COOMatrix) -> Tuple[float, int]:
    deg = np.bincount(m.rows, minlength=m.n)
    return float(deg.mean()), int(deg.max())


def _num_diagonals(m: COOMatrix) -> int:
    return int(np.unique(m.cols.astype(np.int64) - m.rows).shape[0])


def _evict_cb(dispatcher_ref: "weakref.ref", key: int) -> None:
    """Finalizer body: must not hold the Dispatcher alive (weakref only),
    or every tracked matrix would pin the dispatcher's caches."""
    disp = dispatcher_ref()
    if disp is not None:
        disp._evict(key)


class Dispatcher:
    """Plans, caches, and executes structure-aware SpMM.

    One instance owns two caches keyed by matrix identity (entries are
    evicted when the COOMatrix is garbage collected):

      * plan cache:        (matrix, d, strategy, knobs) -> DispatchPlan
      * conversion cache:  (matrix, format, t)          -> format container
    """

    def __init__(self, hardware: Optional[HardwareSpec] = None, *,
                 backend: str = "auto", reuse: int = 32,
                 bcsr_block: int = 64, max_dia_offsets: int = 64,
                 bcsr_max_inflation: float = 64.0,
                 efficiency: Optional[Dict[str, Tuple[float, float]]] = None,
                 calibration=None,
                 tree=None, tree_margin: float = 0.10,
                 sizeof_val: int = 4, sizeof_idx: int = 4,
                 tolerance: float = 0.0):
        if backend not in ("auto", "jax", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        if not 0.0 <= tree_margin < 1.0:
            raise ValueError(f"tree_margin must be in [0, 1), "
                             f"got {tree_margin}")
        if tolerance < 0.0:
            raise ValueError(f"tolerance must be >= 0, got {tolerance}")
        self.backend = backend
        self.hardware = hardware
        self.reuse = reuse
        self.bcsr_block = bcsr_block
        self.max_dia_offsets = max_dia_offsets
        self.bcsr_max_inflation = bcsr_max_inflation
        self.efficiency = dict(DEFAULT_EFFICIENCY, **(efficiency or {}))
        #: Formats whose ceiling was pinned by the caller: calibration
        #: never overrides an explicit ``efficiency=`` entry.
        self._overridden = frozenset(efficiency or ())
        #: ``None`` = the default CalibrationStore (resolved lazily so
        #: ``$REPRO_CALIBRATION_DIR`` is honored at first use, not at
        #: import); a ``CalibrationStore`` to use explicitly; ``False``
        #: disables calibration lookup (the calibrator itself does this).
        self.calibration = calibration
        #: Learned dispatch fallback: ``None`` = the persisted tree from
        #: :class:`repro.data.dtree.DispatchTreeStore` (resolved lazily,
        #: like ``calibration``); a ``DecisionTree`` to use explicitly;
        #: ``False`` disables tree consultation entirely.  The tree is
        #: only consulted under ``strategy="auto"`` when the analytic
        #: top-two candidates sit within ``tree_margin`` (relative
        #: amortized-GFLOP/s gap) — the roofline model stays
        #: authoritative wherever it is confident.
        self.tree = tree
        self.tree_margin = tree_margin
        self._cal_cache: Dict[str, Dict[str, Tuple[float, float]]] = {}
        self._note_cache: Dict[tuple, Optional[str]] = {}
        self._tree_cache: Dict[str, Optional[DecisionTree]] = {}
        #: Legacy fp32 element sizes (kept for external byte-model
        #: callers, e.g. ``repro.sparse.shard``); the candidate models
        #: themselves size traffic from each row's ``Precision``.
        self.sizeof_val = sizeof_val
        self.sizeof_idx = sizeof_idx
        #: Default relative error budget of the accuracy gate: reduced
        #: value dtypes (bf16) are auto-eligible only when the budget
        #: covers the dtype's rounding (``tolerance >= eps``).  The fp32
        #: default 0.0 means "exact": auto dispatch never degrades
        #: numerics unless the caller opts in per-plan or per-dispatcher.
        self.tolerance = tolerance
        self._plans: Dict[tuple, DispatchPlan] = {}
        self._converted: Dict[tuple, object] = {}
        self._reports: Dict[int, StructureReport] = {}
        self._tracked: set = set()

    # ----------------------------------------------------------------- #
    # Cache plumbing
    # ----------------------------------------------------------------- #

    def _track(self, m: COOMatrix) -> int:
        key = id(m)
        if key not in self._tracked:
            self._tracked.add(key)
            weakref.finalize(m, _evict_cb, weakref.ref(self), key)
        return key

    def _evict(self, key: int) -> None:
        self._tracked.discard(key)
        self._reports.pop(key, None)
        for cache in (self._plans, self._converted):
            for k in [k for k in cache if k[0] == key]:
                cache.pop(k, None)

    def _report(self, m: COOMatrix) -> StructureReport:
        key = self._track(m)
        if key not in self._reports:
            with obs.span("repro.dispatch.classify"):
                self._reports[key] = classify(m)
        return self._reports[key]

    def _memo(self, m: COOMatrix, key: tuple, compute: Callable):
        """``compute()`` once per matrix and ``key``: the layout counts
        that several candidate rows of one plan share (dropped with
        ``m``, like every cache here)."""
        k = (self._track(m), "count") + key
        if k not in self._converted:
            self._converted[k] = compute()
        return self._converted[k]

    def convert(self, m: COOMatrix, format: str, precision=None):
        """Convert (and cache) m into ``format``'s container.

        ``precision`` (a :class:`Precision`, token string, or ``None``
        for fp32) sets the packed *value* dtype and is part of the cache
        key; containers keep int32 indices — compact int16 indices are a
        property of the Pallas layout packing, not of the container.
        """
        prec = as_precision(precision)
        key = (self._track(m), format, self.bcsr_block, prec.value_dtype)
        if key not in self._converted:
            dtype = prec.value_jnp
            if format == "csr":
                out = fmt.coo_to_csr(m, dtype=dtype)
            elif format == "ell":
                out = fmt.coo_to_ell(m, dtype=dtype)
            elif format == "bcsr":
                out = fmt.coo_to_bcsr(m, self.bcsr_block, dtype=dtype)
            elif format == "dia":
                out = fmt.coo_to_dia(m, dtype=dtype,
                                     max_offsets=self.max_dia_offsets)
            elif format == "binned":
                out = fmt.coo_to_binned(m, dtype=dtype)
            elif format == "rowsplit":
                out = fmt.coo_to_rowsplit(m, dtype=dtype, chunk=128)
            elif format == "ell_coo":
                out = fmt.coo_to_ell_coo(m, dtype=dtype)
            else:
                raise ValueError(f"unknown format {format!r}")
            self._converted[key] = out
        return self._converted[key]

    # ----------------------------------------------------------------- #
    # Modeling
    # ----------------------------------------------------------------- #

    def _calibrated(self, hw: HardwareSpec, backend: str,
                    precision: str = "f32i32"
                    ) -> Dict[str, Tuple[float, float]]:
        """The persisted calibration for ``(hw, backend)`` ({} if absent).

        The backend is part of the key: jax and pallas ceilings describe
        different kernel implementations, so a calibration fitted for one
        must never answer for the other.  ``precision`` selects
        dtype-specific fits where the calibration has them (ceilings are
        fitted per (format, dtype) since registry v4), falling back to
        the format's fp32 fit otherwise.
        """
        if self.calibration is False:
            return {}
        key = (hw.fingerprint(), backend, precision)
        if key not in self._cal_cache:
            store = self.calibration or CalibrationStore()
            cal = store.load(hw, backend)
            self._cal_cache[key] = \
                cal.efficiency(precision=precision) if cal else {}
        return self._cal_cache[key]

    def _staleness(self, hw: HardwareSpec, backend: str) -> Optional[str]:
        """The CalibrationStore's staleness note for ``(hw, backend)``,
        cached per fingerprint so planning does not reread the file."""
        if self.calibration is False:
            return None
        key = (hw.fingerprint(), backend)
        if key not in self._note_cache:
            store = self.calibration or CalibrationStore()
            self._note_cache[key] = store.staleness_note(hw, backend)
        return self._note_cache[key]

    def refresh_calibration(self) -> None:
        """Drop cached calibration/tree lookups and plans (e.g. after a
        new ``repro.core.calibrate.calibrate(..., store=...)`` run or a
        ``tools/harvest_dispatch.py`` refit)."""
        self._cal_cache.clear()
        self._note_cache.clear()
        self._tree_cache.clear()
        self._plans.clear()

    def _tree(self, backend: str) -> Optional[DecisionTree]:
        """Resolve the dispatch tree for ``backend`` (None = no tree).

        Mirrors :meth:`_calibrated`: an explicit ``tree=`` instance wins,
        ``tree=False`` disables lookup, and ``tree=None`` loads (and
        caches) the persisted ``dispatch_tree-<backend>.json`` from the
        default :class:`DispatchTreeStore` — absent or stale files
        resolve to ``None`` and dispatch stays purely analytic.
        """
        if self.tree is False:
            return None
        if isinstance(self.tree, DecisionTree):
            return self.tree
        if backend not in self._tree_cache:
            self._tree_cache[backend] = DispatchTreeStore().load(backend)
        return self._tree_cache[backend]

    def _ceiling(self, format: str, hw: HardwareSpec, backend: str,
                 precision: str = "f32i32") -> ComputeCeiling:
        """Resolve the compute ceiling with provenance.

        Order: an explicit ``efficiency=`` entry from the constructor
        ("override") > a persisted on-host calibration matching the
        HardwareSpec fingerprint and resolved backend ("calibrated",
        dtype-specific fit preferred, the format's fp32 fit as fallback)
        > the baked-in ``DEFAULT_EFFICIENCY`` constants ("default").
        """
        if format in self._overridden:
            return ComputeCeiling(*self.efficiency[format],
                                  source="override")
        calibrated = self._calibrated(hw, backend, precision)
        if format in calibrated:
            return ComputeCeiling(*calibrated[format], source="calibrated")
        return ComputeCeiling(*self.efficiency[format], source="default")

    def _resolve_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        return "pallas" if jax.default_backend() == "tpu" else "jax"

    def _resolve_hardware(self) -> HardwareSpec:
        """The spec planning runs against: the constructor's, else the
        default device's (``repro.core.hardware.device_hardware``)."""
        if self.hardware is not None:
            return self.hardware
        return device_hardware()

    def _pallas_gate(self, m: COOMatrix, format: str, d: int,
                     hw: HardwareSpec) -> Optional[str]:
        """Why the Pallas kernel for ``format`` cannot run ``m`` on ``hw``.

        Device limits, recorded like any policy skip: a modelled VMEM
        working set over the kernel's scoped VMEM limit (the row-split
        kernel holds all of B), scalar-prefetched metadata over the SMEM
        budget, and a binned packing whose per-visit chunk padding
        exceeds ``MAX_PACKED_INFLATION``.  None when all hold.
        """
        from repro.kernels import registry as kreg
        ctx = kreg.KernelContext(hardware=hw, bcsr_block=self.bcsr_block,
                                 plan_d=d)
        spec = kreg.get(format, "pallas")
        footprint = spec.vmem_footprint(m.n, d, ctx)
        if footprint > ctx.vmem_limit:
            return (f"VMEM footprint {footprint / 2 ** 20:.1f} MiB exceeds "
                    f"the {ctx.vmem_limit / 2 ** 20:.0f} MiB kernel budget")
        smem, smem_limit = spec.smem_footprint(m, ctx), kernel_smem_limit(hw)
        if smem_limit and smem > smem_limit:
            return (f"scalar-prefetched metadata {smem / 2 ** 10:.0f} KiB "
                    f"exceeds the {smem_limit / 2 ** 10:.0f} KiB SMEM "
                    f"budget")
        if format == "binned" and m.nnz:
            slots = self._binned_slots(m, ctx.resolve_b_tile(m.n) or m.n)
            if slots > MAX_PACKED_INFLATION * m.nnz:
                return (f"binned packing pads its slab visits to "
                        f"{slots / m.nnz:.1f}x the nonzeros (limit "
                        f"{MAX_PACKED_INFLATION}x)")
        return None

    def _policy(self, m: COOMatrix, report: StructureReport,
                format: str) -> Tuple[bool, Optional[str], dict]:
        """Applicability gate + the structural params the model needs.

        These are the benchmark suite's former inline heuristics, promoted
        to policy with recorded reasons (SpChar-style structural gating).
        """
        avg_deg, max_deg = _degree_stats(m)
        if format == "csr":
            return True, None, {}
        if format == "ell":
            k = max(max_deg, 1)
            params = {"k": k}
            if max_deg > max(64, 16 * max(avg_deg, 1)):
                return False, (
                    f"ELL padding explodes: max_deg {max_deg} >> avg "
                    f"{avg_deg:.1f} (vendor kernels fall back to CSR here)"
                ), params
            return True, None, params
        if format == "bcsr":
            t = self.bcsr_block
            if m.n % t != 0:
                return False, (f"matrix dim {m.n} not divisible by BCSR "
                               f"block {t}"), {}
            if report.stats.get("block_t") == t:
                bstats = {k[len("block_"):]: v for k, v in
                          report.stats.items() if k.startswith("block_")}
            else:
                bstats = block_stats(m, t)
            inflation = (t * t) / max(bstats["D"], 1e-9)
            params = {"t": t, "N": bstats["N"], "D": bstats["D"],
                      "z": bstats["z_emp"], "inflation": inflation}
            if inflation > self.bcsr_max_inflation:
                return False, (
                    f"dense-block inflation {inflation:.0f}x exceeds "
                    f"{self.bcsr_max_inflation:.0f}x (ai_blocked_tpu "
                    f"predicts mxu_util {1 / inflation:.3f})"), params
            return True, None, params
        if format == "dia":
            k = _num_diagonals(m)
            params = {"num_offsets": k}
            if k > self.max_dia_offsets:
                return False, (
                    f"{k} distinct diagonals exceed "
                    f"{self.max_dia_offsets}; DIA only suits banded "
                    f"matrices"), params
            return True, None, params
        if format in ("binned", "rowsplit"):
            # Structurally both run anything (binned collapses to CSR
            # order when one slab covers the matrix; rowsplit's padding
            # is bounded by one chunk); their Pallas kernels' device
            # limits are checked by ``_pallas_gate``.
            return True, None, {}
        if format == "ell_coo":
            deg = np.bincount(m.rows, minlength=m.n)
            k_cut = fmt.ell_coo_cutoff(deg)
            tail = int(np.clip(deg - k_cut, 0, None).sum())
            # The cutoff *is* the padding-explosion defense that forces
            # plain ELL to skip: hub rows overflow into the COO tail.
            return True, None, {"k_cut": k_cut, "tail_nnz": tail}
        raise ValueError(f"unknown format {format!r}")

    def _model(self, m: COOMatrix, report: StructureReport, format: str,
               params: dict, d: int, hw: HardwareSpec, reuse: int,
               backend: str, prec: Precision = DEFAULT_PRECISION
               ) -> Tuple[float, float, float, float, float, str]:
        """(ai, useful_fraction, predicted, amortized, conv_bytes, source).

        AI composes structure and storage: the B-traffic term comes from
        the detected regime's Section III model (structure controls B
        reuse no matter how A is stored), the A-traffic term from the
        format's actual storage footprint.  Every byte term is sized by
        ``prec``'s actual element widths — the precision axis changes
        *traffic*, not FLOPs, which is exactly why it moves the
        bandwidth roofline ``beta * AI``.
        """
        sv, si = prec.sizeof_val, prec.sizeof_idx
        n, nnz = m.n, m.nnz
        flops = sm.flops_spmm(nnz, d)
        regime_tb = report.traffic(d, sizeof_val=sv, sizeof_idx=si)
        bytes_b = regime_tb.bytes_b
        bytes_c = n * d * sv

        if format == "csr":
            bytes_a = nnz * (sv + si) + (n + 1) * si
            useful = 1.0
            conv = nnz * (sv + 2 * si) + (n + 1) * si   # data+cols+row_ids
        elif format == "ell":
            k = params["k"]
            bytes_a = n * k * (sv + si)
            useful = nnz / float(n * k)
            conv = n * k * (sv + si)
        elif format == "bcsr":
            t, N = params["t"], max(params["N"], 1)
            bytes_a = N * t * t * sv + 2 * N * si
            useful = sm.mxu_utilization(nnz, t, N)
            # Deterministic block reuse: Eq. 4's B term with measured z.
            bytes_b = 0.25 * N * params["z"] * d * sv
            conv = N * t * t * sv + 3 * N * si
        elif format == "dia":
            k = max(params["num_offsets"], 1)
            bytes_a = k * n * sv
            useful = nnz / float(k * n)
            # DIA's traversal streams B exactly once (Eq. 3) regardless of
            # the detected regime — that is the point of choosing it.
            bytes_b = n * d * sv
            conv = k * n * sv
        elif format == "binned":
            # Slab-binned traversal: B traffic is slabs fetched, not
            # nonzeros gathered — the scale-free regime's escape hatch
            # from the Eq. 2 worst case.  (Lazy import: repro.kernels
            # imports this package for its format containers.)
            from repro.kernels import registry as kreg
            slab = self._binned_slab(n, d, hw, backend)
            touched, visits = self._memo(
                m, ("binned_stats", slab),
                lambda: kreg.binned_layout_stats(m, slab_rows=slab))
            tb = sm.ai_binned(n, nnz, d, slab_rows=slab,
                              slabs_touched=touched, num_visits=visits,
                              row_tile=kreg.ROW_TILE,
                              sizeof_val=sv, sizeof_idx=si)
            bytes_a, bytes_b, bytes_c = tb.bytes_a, tb.bytes_b, tb.bytes_c
            useful = 1.0
            # Conversion re-sorts the whole nonzero stream (an extra
            # binning pass over the layout on top of writing it).
            conv = 2.0 * (nnz * (sv + 2 * si) + (touched + 1) * si)
            params.update(slab_rows=slab, slabs_touched=touched,
                          num_visits=visits)
        elif format == "rowsplit":
            from repro.kernels import registry as kreg
            n_nonempty = int(np.unique(m.rows).shape[0])
            window = kreg.rowsplit_window_model(n_nonempty, nnz)
            # B locality is whatever the structural regime grants — the
            # row split changes load balance, not the gather pattern.
            tb = sm.ai_rowsplit(n, nnz, d, window=window,
                                bytes_b=regime_tb.bytes_b,
                                sizeof_val=sv, sizeof_idx=si)
            bytes_a, bytes_b, bytes_c = tb.bytes_a, tb.bytes_b, tb.bytes_c
            useful = 1.0
            conv = nnz * (sv + 2 * si)
            params.update(window=window)
        elif format == "ell_coo":
            k_cut, tail = params["k_cut"], params["tail_nnz"]
            issued = max(n * k_cut + tail, 1)
            # Body padding issues extra gathers; scale the regime's
            # per-gather B model by issued/nnz to charge for them.
            tb = sm.ai_ell_coo(
                n, nnz, d, k_cut=k_cut, tail_nnz=tail,
                bytes_b=regime_tb.bytes_b * issued / max(nnz, 1),
                sizeof_val=sv, sizeof_idx=si)
            bytes_a, bytes_b, bytes_c = tb.bytes_a, tb.bytes_b, tb.bytes_c
            useful = nnz / float(issued)
            conv = n * k_cut * (sv + si) + tail * (sv + 2 * si)
        else:
            raise ValueError(f"unknown format {format!r}")

        ai = flops / (bytes_a + bytes_b + bytes_c)
        bandwidth_roof = hw.hbm_bandwidth * ai
        ceiling = self._ceiling(format, hw, backend, prec.token)
        compute_roof = ceiling.attainable(hw.peak_flops, useful, d)
        predicted = min(bandwidth_roof, compute_roof)
        if flops <= 0 or predicted <= 0:   # empty matrix: nothing to do
            return ai, useful, 0.0, 0.0, conv, ceiling.source
        t_spmm = flops / predicted
        costs = kernel_costs(hw) if backend == "pallas" else None
        if costs is not None:
            # On a chip with measured kernel costs, a launch takes at least
            # what its kernel issues.
            t_spmm = max(t_spmm, self._issue_s(m, format, params, d, hw,
                                               costs, prec))
            predicted = flops / t_spmm
            if format in CSR_FAMILY:
                # The Pallas ell / ell_coo picks pack the CSR layout.
                conv = nnz * (sv + 2 * si) + (n + 1) * si
        t_conv = 2.0 * conv / hw.hbm_bandwidth          # read COO + write
        amortized = flops / (t_spmm + t_conv / max(reuse, 1))
        return (ai, useful, predicted / 1e9, amortized / 1e9, conv,
                ceiling.source)

    def _binned_slots(self, m: COOMatrix, slab: int) -> int:
        """Packed slots of the Pallas binned layout with ``slab``-row B
        slabs (``registry.binned_padded_slots``), once per matrix."""
        from repro.kernels import registry as kreg
        return self._memo(m, ("binned_slots", slab),
                          lambda: kreg.binned_padded_slots(m, slab_rows=slab))

    def _issue_s(self, m: COOMatrix, format: str, params: dict, d: int,
                 hw: HardwareSpec, costs: KernelCosts,
                 prec: Precision) -> float:
        """Seconds the Pallas kernel for ``format`` takes to issue one
        launch at width ``d``, counted from the layout the model sizes:
        packed slots times a row DMA (CSR family), stored blocks times a
        block's share of the block-row kernel plus the block's bytes at
        HBM bandwidth (BCSR), B-row loads from VMEM (binned, rowsplit).
        0 for a kernel with no measured cost (DIA)."""
        from repro.kernels import registry as kreg
        passes = d // kreg.pallas_block_d(d)
        chunk = 128
        if format in CSR_FAMILY:
            slots = self._memo(m, ("csr_slots",), lambda: int(
                (-(-np.bincount(m.rows // kreg.ROW_TILE) // chunk)).sum())
                * chunk)
            return passes * slots * costs.row_dma_s
        if format == "bcsr":
            t = params["t"]
            nb = m.n // t
            empty = nb - np.count_nonzero(np.bincount(m.rows // t,
                                                      minlength=nb))
            blocks = params["N"] + empty
            return passes * blocks * (costs.block_step_s + t * t
                                      * prec.sizeof_val / hw.hbm_bandwidth)
        if format == "binned":
            return passes * self._binned_slots(m, params["slab_rows"]) \
                * costs.row_load_s
        if format == "rowsplit":
            return passes * -(-m.nnz // chunk) * chunk * costs.row_load_s
        return 0.0

    @staticmethod
    def _binned_slab(n: int, d: int, hw: HardwareSpec, backend: str) -> int:
        """B slab height of the binned kernel ``backend`` runs: the same
        functions the kernels' layouts are packed with."""
        if backend == "pallas":
            from repro.kernels import registry as kreg
            return kreg.KernelContext(hardware=hw,
                                      plan_d=d).resolve_b_tile(n) or n
        return fmt.default_slab_rows(n)

    def _index_extent(self, m: COOMatrix, format: str, d: int,
                      hw: HardwareSpec, backend: str) -> int:
        """The largest extent a packed index of this layout addresses.

        The binned layout stores slab-local column ids, so its extent is
        the B row-slab height; every other layout keeps global column
        ids, so the extent is n.  Matches the packers' own
        ``index_extent_check`` at prepare time.
        """
        if format == "binned":
            return self._binned_slab(m.n, d, hw, backend)
        return m.n

    def _precision_gate(self, m: COOMatrix, format: str, prec: Precision,
                        d: int, hw: HardwareSpec, backend: str,
                        tolerance: float,
                        forced: bool) -> Tuple[bool, Optional[str]]:
        """Accuracy/legality gate for one (format, precision) row.

        int16 extent legality is *correctness* and never waived; the
        bf16 tolerance gate is *preference* — an explicitly forced
        ``precision=`` is itself the opt-in and bypasses it, exactly as
        a forced strategy bypasses the auto ranking (but not policy).
        """
        if prec.index_dtype == "int16":
            extent = self._index_extent(m, format, d, hw, backend)
            if not fmt.int16_extent_ok(extent):
                return False, (
                    f"int16 indices cannot address extent {extent} "
                    f"(> {INT16_MAX_EXTENT}; the packers reserve a "
                    f"sentinel slot equal to the extent)")
        if prec.reduced and not forced and tolerance < prec.eps:
            return False, (
                f"bf16 values round at eps={prec.eps:.1e} > tolerance "
                f"{tolerance:.1e}; pass tolerance= or force precision= "
                f"to opt in")
        return True, None

    def _score(self, m: COOMatrix, report: StructureReport, d: int,
               hw: HardwareSpec, reuse: int, backend: str, tolerance: float,
               forced_tok: Optional[str]) -> list:
        """One :class:`CandidateEval` per (format, precision) row the
        backend's kernels support, each timed as a
        ``repro.dispatch.candidate`` span."""
        from repro.kernels import registry as kreg
        cands = []
        for f in FORMATS:
            eligible, reason, params = self._policy(m, report, f)
            if eligible and backend == "pallas":
                reason = self._pallas_gate(m, f, d, hw)
                eligible = reason is None
            spec_tokens = kreg.get(f, backend).supported_precisions
            for prec in PRECISIONS:
                if prec.token not in spec_tokens:
                    continue
                with obs.span("repro.dispatch.candidate", format=f,
                              precision=prec.token):
                    p_ok, p_reason = self._precision_gate(
                        m, f, prec, d, hw, backend, tolerance,
                        forced=prec.token == forced_tok)
                    source = "default"
                    row_params = dict(params)
                    try:
                        ai, useful, pred, amort, conv, source = self._model(
                            m, report, f, row_params, d, hw, reuse, backend,
                            prec)
                    except (KeyError, ValueError):
                        ai = useful = pred = amort = conv = None
                    cands.append(CandidateEval(
                        format=f, eligible=eligible and p_ok,
                        skip_reason=reason if not eligible else p_reason,
                        ai=ai, useful_fraction=useful, predicted_gflops=pred,
                        amortized_gflops=amort, conversion_bytes=conv,
                        params=row_params, ceiling_source=source,
                        precision=prec.token))
        return cands

    # ----------------------------------------------------------------- #
    # Public API
    # ----------------------------------------------------------------- #

    def plan(self, m: COOMatrix, d: int, *, strategy: str = "auto",
             reuse: Optional[int] = None, precision=None,
             tolerance: Optional[float] = None) -> DispatchPlan:
        """Plan (and cache) the (format, kernel) choice for ``(m, d)``.

        Args:
            m: square sparse pattern, ``[n, n]``.
            d: dense operand width (``B`` is ``[n, d]``).
            strategy: ``"auto"`` (roofline-predicted best) or a format name
                from ``FORMATS`` to force that format.
            reuse: conversion amortization horizon — the expected number of
                SpMM executions this plan will serve.  Defaults to the
                dispatcher's ``reuse`` (32).  Higher values let formats with
                expensive one-time conversions (e.g. BCSR's dense blocks)
                win on amortized throughput.
            precision: force one precision (a token like ``"bf16"`` /
                ``"bf16i16"`` / ``"fp32"``, or a ``Precision``) the way a
                format name forces ``strategy`` — restricting every
                candidate to that row.  Forcing is itself the accuracy
                opt-in (the bf16 tolerance gate is waived), but int16
                extent legality still raises.  ``None`` enumerates every
                precision each kernel supports and lets the roofline
                ranking pick.
            tolerance: relative error budget of the accuracy gate for
                this plan (defaults to the dispatcher's ``tolerance``,
                0.0).  Reduced value dtypes are auto-eligible only when
                ``tolerance >= dtype eps`` (bf16: ~7.8e-3); gated rows
                stay in the audit with a recorded skip reason.

        Under ``strategy="auto"``, when a fitted dispatch tree is
        available (see the ``tree`` constructor arg) and the analytic
        top-two candidates sit within ``tree_margin`` of each other, the
        tree breaks the tie; the plan records ``decision_source="tree"``
        and the tree's ``decision_path``.

        Returns:
            The cached :class:`DispatchPlan` with per-candidate predictions.

        Raises:
            ValueError: on an unknown strategy, ``d < 1``, a forced
                format the applicability policy rejects for this matrix
                (the error carries the recorded skip reason), or a forced
                precision no eligible kernel can run here (unsupported
                by the (format, backend) specs, or int16-illegal extent).
        """
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {strategy!r}; choose from "
                             f"{STRATEGIES}")
        if d < 1:
            raise ValueError(f"dense width d must be >= 1, got {d}")
        reuse = self.reuse if reuse is None else reuse
        tolerance = self.tolerance if tolerance is None else float(tolerance)
        forced_tok = None if precision is None \
            else as_precision(precision).token
        backend = self._resolve_backend()
        hw = self._resolve_hardware()
        # The fitted tree is part of the plan identity: refitting (or
        # deleting) the persisted tree must not replay stale decisions.
        tree = self._tree(backend) if strategy == "auto" else None
        tree_token = tree.fingerprint() if tree is not None else "none"
        key = (self._track(m), d, strategy, reuse, backend, hw.name,
               tree_token, self.tree_margin, forced_tok, tolerance)
        if key in self._plans:
            return self._plans[key]

        report = self._report(m)
        with obs.span("repro.dispatch.score"):
            cands = self._score(m, report, d, hw, reuse, backend, tolerance,
                                forced_tok)

        pool = cands if forced_tok is None else \
            [c for c in cands if c.precision == forced_tok]
        decision_source, decision_path = "analytic", ()
        if strategy == "auto":
            viable = [c for c in pool
                      if c.eligible and c.amortized_gflops is not None]
            if not viable:
                if forced_tok is not None:
                    raise ValueError(
                        f"no eligible kernel on backend {backend!r} can "
                        f"run precision {forced_tok!r} for this matrix")
                # CSR at fp32 is always eligible; belt and braces.
                viable = [c for c in cands
                          if c.format == "csr" and c.precision == "f32i32"]
            ranked = sorted(viable, key=lambda c: c.amortized_gflops or 0.0,
                            reverse=True)
            # Tie-breaking and the tree speak *formats*: collapse to the
            # best precision row per format before ranking gaps, so two
            # precisions of one format never masquerade as a near-tie.
            best_by_fmt: Dict[str, CandidateEval] = {}
            for c in ranked:
                best_by_fmt.setdefault(c.format, c)
            franked = list(best_by_fmt.values())
            chosen_c = franked[0]
            # Learned fallback (SpChar): only where the analytic model
            # cannot separate its top two candidates.  The tree's pick
            # must itself be within the margin of the analytic winner —
            # the tree breaks ties, it never overrules a confident
            # roofline ranking — so any tree-induced regression is
            # bounded by tree_margin by construction.
            if tree is not None and len(franked) >= 2:
                top = franked[0].amortized_gflops or 0.0
                gap = (top - (franked[1].amortized_gflops or 0.0)) \
                    / max(top, 1e-12)
                if gap <= self.tree_margin:
                    x = features_from_report(report, d)
                    pick = tree.predict(x)
                    near = {c.format for c in franked
                            if top - (c.amortized_gflops or 0.0)
                            <= self.tree_margin * top}
                    if pick in near:
                        chosen_c = best_by_fmt[pick]
                        decision_source = "tree"
                        decision_path = tree.decision_path(x)
        else:
            rows = [c for c in pool if c.format == strategy]
            if not rows:
                raise ValueError(
                    f"kernel ({strategy!r}, {backend!r}) does not "
                    f"support precision {forced_tok!r}")
            eligible_rows = [c for c in rows if c.eligible]
            if not eligible_rows:
                raise ValueError(
                    f"strategy {strategy!r} is policy-ineligible for "
                    f"this matrix: {rows[0].skip_reason}")
            chosen_c = max(eligible_rows,
                           key=lambda c: c.amortized_gflops or 0.0)
        plan = DispatchPlan(
            chosen=chosen_c.format, strategy=strategy, regime=report.regime,
            d=d, reuse=reuse, backend=backend, hardware=hw.name,
            candidates=tuple(cands), precision=chosen_c.precision,
            tolerance=tolerance,
            calibration_note=self._staleness(hw, backend),
            decision_source=decision_source, decision_path=decision_path)
        self._plans[key] = plan
        return plan

    def spmm(self, m: COOMatrix, b: jnp.ndarray, *,
             strategy: str = "auto",
             reuse: Optional[int] = None, precision=None,
             tolerance: Optional[float] = None) -> jnp.ndarray:
        """Compute ``C = A @ B`` through the planned (format, kernel) pair.

        Args:
            m: square sparse pattern, ``[n, n]``.
            b: dense right-hand side, ``[n, d]``.
            strategy: ``"auto"`` or a forced format name (see :meth:`plan`).
            reuse: conversion amortization horizon (see :meth:`plan`).
            precision: force one storage precision (see :meth:`plan`).
            tolerance: accuracy-gate budget for reduced precisions (see
                :meth:`plan`).

        Returns:
            ``C`` as a dense ``[n, d]`` array.  Under a reduced plan
            precision the kernel rounds B to the storage dtype and
            returns C in it (accumulation stays fp32 throughout).

        Raises:
            ValueError: on a shape-incompatible ``b``, or a forced format
                / precision the policy rejects for this matrix (see
                :meth:`plan`).
        """
        if b.ndim != 2 or b.shape[0] != m.n:
            raise ValueError(
                f"operand shape {tuple(b.shape)} incompatible with "
                f"[{m.n}, {m.n}] sparse matrix; expected [{m.n}, d]")
        plan = self.plan(m, int(b.shape[1]), strategy=strategy, reuse=reuse,
                         precision=precision, tolerance=tolerance)
        return self.executor(m, plan)(b)

    def executor(self, m: COOMatrix,
                 plan: DispatchPlan) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Bind ``plan`` to ``m``: the execute phase, split from planning.

        All one-time work — format conversion and host-side kernel layout
        packing (row-tile chunking, band extraction, empty-block-row
        padding) — happens here, once; the returned closure holds the
        prepared containers directly, so replaying it across many
        right-hand sides does no cache lookups, no classification, and no
        conversion.  This is the primitive under
        :class:`repro.sparse.stream.StreamPlan`.

        Args:
            m: the matrix the plan was made for.
            plan: a :class:`DispatchPlan` from :meth:`plan`.

        Returns:
            ``run(b) -> c`` executing the chosen kernel; ``b`` is ``[n, d]``
            (any ``d`` — the kernel tile width adapts per call), ``c`` is
            ``[n, d]``.
        """
        from repro.kernels import registry
        spec = registry.get(plan.chosen, plan.backend)
        ctx = self._kernel_context(plan)
        layout = self.layout(m, plan)
        return lambda b: spec.run(layout, b, ctx)

    def _kernel_context(self, plan: DispatchPlan):
        """The KernelContext ``plan``'s kernel is prepared and run with."""
        from repro.kernels import registry
        prec = as_precision(plan.precision)

        def _convert(mm, format, _prec=prec):
            # prepare shares the conversion cache, pinned to the plan's
            # precision (the registry's hook is two-argument).
            return self.convert(mm, format, precision=_prec)

        return registry.KernelContext(
            hardware=self._resolve_hardware(),
            bcsr_block=self.bcsr_block,
            max_dia_offsets=self.max_dia_offsets,
            plan_d=plan.d,          # per-d B-slab re-packing
            precision=prec,         # dtype-sized footprints, packed indices
            convert=_convert)

    def layout(self, m: COOMatrix, plan: DispatchPlan):
        """The prepared kernel layout ``plan`` replays for ``m`` (cached).

        Uniform path: the KernelSpec for (format, backend) prepares it once
        per matrix — per-call packing would dominate the kernel.
        """
        # Lazy import: repro.kernels imports this package for its format
        # containers.
        from repro.kernels import registry
        spec = registry.get(plan.chosen, plan.backend)
        prec = as_precision(plan.precision)
        # The resolved d-tile and the storage precision are part of the
        # layout identity: two plans whose widths map to different slab
        # sizings, or whose layouts pack different dtypes, must not
        # share one packed layout.
        ck = (self._track(m), "layout", *spec.layout_cache_key,
              self.bcsr_block, registry.pallas_block_d(plan.d),
              prec.token)
        if ck not in self._converted:
            with obs.span("repro.dispatch.prepare", format=plan.chosen,
                          precision=prec.token):
                self._converted[ck] = spec.prepare(
                    m, self._kernel_context(plan))
        return self._converted[ck]


#: Module-level dispatcher behind the one-call public API.
_DEFAULT = Dispatcher()


def default_dispatcher() -> Dispatcher:
    """Return the module-level :class:`Dispatcher` behind ``spmm``/``plan_spmm``."""
    return _DEFAULT


def plan_spmm(m: COOMatrix, d: int, *, strategy: str = "auto",
              reuse: Optional[int] = None, precision=None,
              tolerance: Optional[float] = None) -> DispatchPlan:
    """Plan the (format, kernel) choice for ``(m, d)`` on the default dispatcher.

    Args:
        m: square sparse pattern (``repro.core.patterns.COOMatrix``), [n, n].
        d: dense operand width.
        strategy: ``"auto"`` or a format from ``FORMATS`` to force.
        reuse: conversion amortization horizon (default 32 executions).
        precision: ``None`` (enumerate every supported precision, gated
            by ``tolerance``) or a forced precision token / ``Precision``
            (``"fp32"``, ``"bf16"``, ``"bf16i32"``, ``"bf16i16"``).
        tolerance: relative error budget enabling reduced value dtypes
            (bf16 needs ~7.8e-3); default 0.0 keeps dispatch exact.

    Returns:
        An inspectable :class:`DispatchPlan`; ``plan.summary()`` renders the
        per-candidate predictions, precisions, and skip reasons.

    Raises:
        ValueError: on an unknown strategy, ``d < 1``, or a forced format
            / precision the policy rejects for this matrix.
    """
    return _DEFAULT.plan(m, d, strategy=strategy, reuse=reuse,
                         precision=precision, tolerance=tolerance)


def spmm(m: COOMatrix, b: jnp.ndarray, *, strategy: str = "auto",
         reuse: Optional[int] = None, precision=None,
         tolerance: Optional[float] = None) -> jnp.ndarray:
    """Structure-aware SpMM: ``C = A @ B`` via the default dispatcher.

    ``strategy="auto"`` classifies the matrix structure, evaluates each
    candidate format's sparsity-aware roofline, and executes the winning
    (format, kernel) pair; a format name forces that format.  Plans and
    conversions are cached per matrix.  For a stream of right-hand sides
    against one matrix, prefer :func:`repro.sparse.stream.plan` — it binds
    the kernel once and replays it with zero dispatch overhead.

    Args:
        m: square sparse pattern (``repro.core.patterns.COOMatrix``), [n, n].
        b: dense right-hand side, ``[n, d]``.
        strategy: ``"auto"`` or a format from ``FORMATS`` to force.
        reuse: conversion amortization horizon (default 32 executions).
        precision: ``None`` or a forced precision (see :func:`plan_spmm`).
        tolerance: accuracy-gate budget enabling reduced precisions; with
            the default 0.0 dispatch stays fp32-exact.

    Returns:
        ``C`` as a dense ``[n, d]`` array (in the plan's value dtype:
        fp32 unless a reduced precision was chosen or forced).

    Raises:
        ValueError: on a shape-incompatible ``b``, or a forced format /
            precision the applicability policy rejects for this matrix
            (the error carries the recorded skip reason).
    """
    return _DEFAULT.spmm(m, b, strategy=strategy, reuse=reuse,
                         precision=precision, tolerance=tolerance)

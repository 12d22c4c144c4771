"""Kernel registry: every SpMM kernel behind one ``KernelSpec`` interface.

This module keeps layout packing, kernel call and VMEM assumptions in
one place per kernel: each ``(format, backend)`` pair registers a
:class:`KernelSpec` bundling

  * ``prepare(m, ctx)``  — one-time host-side layout prep (format
    conversion, row-tile chunking, band extraction, empty-row padding);
  * ``run(layout, b, ctx)`` — the per-call kernel launch (Pallas call or
    pure-JAX implementation), tile widths adapted to ``b``;
  * ``estimate(m, d, ctx)`` — the sparsity-aware roofline placement of a
    launch (AI, useful vs issued FLOPs, attainable GFLOP/s);
  * ``vmem_footprint(n, d, ctx)`` — the kernel's modeled resident VMEM
    working set in bytes (0 for XLA-managed jax backends);
  * ``smem_footprint(m, ctx)`` — the scalar-prefetched metadata it keeps
    in SMEM for matrix ``m`` (0 where it prefetches nothing).

``repro.sparse.dispatch.Dispatcher.executor`` resolves the winning plan
through :func:`get`; ``repro.sparse.stream`` replays the bound closure;
and ``repro.core.calibrate`` sweeps every registered spec to fit
measured compute ceilings.  :func:`spmm` is the one-call
registry entry point for direct use.

Every Pallas kernel is launched with the same scoped VMEM limit,
``KernelContext.vmem_limit`` (``hardware.kernel_vmem_limit``), and every
resident block is sized from it counting double-buffering: the binned
spec's B row slab comes from ``choose_b_tile`` on that budget, and the
dispatcher skips a Pallas candidate whose ``vmem_footprint`` exceeds it.
The CSR kernel gathers rows of B straight from HBM and holds no slab.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparsity_models as sm
from repro.core.hardware import (
    TPU_V5E, HardwareSpec, device_hardware, kernel_smem_limit,
    kernel_vmem_limit)
from repro.core.precision import (
    DEFAULT_PRECISION, INT16_MAX_EXTENT, Precision)
from repro.kernels.banded_spmm import banded_spmm_pallas
from repro.kernels.bcsr_spmm import (COORD_BYTES, bcsr_segments,
                                     bcsr_spmm_pallas, d_passes, pack_bcsr,
                                     ring_counts, ring_slots, segment_rows)
from repro.kernels.binned_spmm import (
    binned_spmm_pallas, csr_to_slab_bins, pack_rowsplit_chunks,
    rowsplit_spmm_pallas)
from repro.kernels.csr_spmm import (csr_spmm_pallas, csr_to_row_tiles,
                                    pipeline_counts)
from repro.kernels.grouped_matmul import grouped_matmul_pallas

BACKENDS: Tuple[str, ...] = ("jax", "pallas")

#: Monotone version of the registered kernel set and their layout/sizing
#: rules.  Bump it whenever a change invalidates previously measured
#: compute ceilings (new kernels, retuned slab sizing, layout changes);
#: ``repro.core.calibrate`` stamps saved calibrations with it so
#: ``plan.summary()`` can nudge when a calibration predates the kernels
#: it would be applied to.  History: 1 = initial KernelSpec registry,
#: 2 = per-d B-slab re-packing (``KernelContext.plan_d``),
#: 3 = scale-free kernel tier (binned / rowsplit / ell_coo),
#: 4 = precision axis (bf16 values / int16 indices; dtype-sized slabs
#: and footprints), 5 = chip bring-up (CSR gathers from HBM, int8 row
#: slots, 32-row tiles, slabs sized from the scoped VMEM limit).
REGISTRY_VERSION: int = 5

#: Rows per output tile of the CSR-family kernels (and per binned visit).
ROW_TILE: int = 32

#: ``KernelSpec.counters`` keys that describe the layout rather than count
#: a launch's work: logged once, not summed over launches.
LAYOUT_COUNTERS = frozenset({"block_t"})


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode when nobody says.

    Only on the CPU backend (the tests): on an accelerator the kernels
    are compiled, and a kernel the chip refuses is an error.
    """
    return jax.default_backend() == "cpu"


def pallas_block_d(d: int) -> int:
    """Largest d-tile (<= 512) dividing d; the kernels require d % bd == 0."""
    for bd in (512, 256, 128, 64, 32, 16, 8, 4, 2):
        if d % bd == 0:
            return bd
    return 1


def pallas_band_tile(n: int) -> int:
    """Largest MXU-friendly tile edge dividing n (banded Pallas kernel)."""
    for t in (128, 64, 32, 16, 8, 4, 2):
        if n % t == 0:
            return t
    return 1


def choose_b_tile(n: int, vmem_budget: int, *,
                  bd: int = 512) -> Optional[int]:
    """B row-slab size for the binned kernel, from the VMEM budget.

    ``vmem_budget`` is the kernel's scoped VMEM limit.  Half of it goes to
    the resident B slab, which the pipeline double-buffers, so one slab
    takes a quarter; the rest covers the partial-C block, index chunks
    and the gather scratch.  Gathered rows are fp32 whatever the storage
    precision (see ``repro.kernels.csr_spmm``).  Slabs stop at
    ``INT16_MAX_EXTENT`` rows so slab-local columns stay int16-legal.
    Returns ``None`` when all of B fits — the layout then reduces to one
    slab with global column ids.

    ``bd`` is the kernel's d-tile width the slab must host.  The default
    512 is the widest tile; callers that know ``d`` at plan time pass the
    actual tile (``KernelContext.plan_d`` routes this through
    ``resolve_b_tile``), so small-d plans get taller slabs.
    """
    if vmem_budget <= 0:
        return None
    slab_rows = vmem_budget // 4 // (bd * 4)
    if slab_rows >= n:
        return None
    return max(8, min(int(slab_rows), INT16_MAX_EXTENT) // 8 * 8)


@dataclasses.dataclass(frozen=True)
class KernelContext:
    """Knobs a :class:`KernelSpec` needs to prepare and launch.

    Attributes:
        hardware: ceilings of the target device (default: the spec of
            JAX's default device, ``hardware.device_hardware``); its
            ``vmem_bytes`` sets the kernels' scoped VMEM limit.
        bcsr_block: BCSR block edge t.
        max_dia_offsets: DIA conversion cap (mirrors the dispatch policy).
        interpret: force Pallas interpret mode; None = on the CPU backend
            only (``default_interpret``).
        row_tile: CSR-family kernel rows per C tile (at most 128).
        chunk: CSR-family kernel nonzeros per packed chunk.
        b_tile: explicit B row-slab override for the binned kernel; None
            picks it from the VMEM limit (``choose_b_tile``).
        plan_d: the dense width the plan was made for, when known; lets
            ``resolve_b_tile`` size the B slab for the actual d-tile
            instead of the worst-case 512 (per-d slab re-packing).  None
            keeps the conservative sizing.
        precision: value/index storage dtypes the layouts are packed at
            (``repro.core.precision.Precision``); sizes the footprints by
            the actual element widths.
        convert: optional ``(m, format) -> container`` hook so prepare
            reuses the caller's conversion cache (the dispatcher passes
            its own ``convert`` method, bound to this precision); None
            converts directly at ``precision``'s value dtype.
    """

    hardware: HardwareSpec = dataclasses.field(
        default_factory=device_hardware)
    bcsr_block: int = 64
    max_dia_offsets: int = 64
    interpret: Optional[bool] = None
    row_tile: int = ROW_TILE
    chunk: int = 128
    b_tile: Optional[int] = None
    plan_d: Optional[int] = None
    precision: Precision = DEFAULT_PRECISION
    convert: Optional[Callable[[Any, str], Any]] = None

    def resolve_interpret(self) -> bool:
        """Pallas interpret flag: forced value, else CPU backend only."""
        return default_interpret() if self.interpret is None \
            else self.interpret

    @property
    def vmem_limit(self) -> int:
        """Scoped VMEM every Pallas launch requests, in bytes."""
        return kernel_vmem_limit(self.hardware)

    def resolve_b_tile(self, n: int) -> Optional[int]:
        """The binned kernel's B slab height for an ``[n, n]`` matrix."""
        if self.b_tile is not None:
            return self.b_tile if self.b_tile < n else None
        bd = 512 if self.plan_d is None else min(512,
                                                 pallas_block_d(self.plan_d))
        return choose_b_tile(n, self.vmem_limit, bd=bd)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: layout prep, launch, estimate, VMEM model."""

    format: str                  # "csr" | "ell" | "bcsr" | "dia" | "binned"
    #                            # | "rowsplit" | "ell_coo" | "grouped"
    backend: str                 # "jax" | "pallas"
    description: str
    prepare: Callable[[Any, KernelContext], Any]
    run: Callable[[Any, jnp.ndarray, KernelContext], jnp.ndarray]
    estimate: Callable[[Any, int, KernelContext], "KernelRoofline"]
    vmem_footprint: Callable[[int, int, KernelContext], int]
    #: Specs producing identical prepared layouts share this key so
    #: callers cache one layout for all of them (ELL's pallas pick lowers
    #: to the CSR kernel and reuses its row-tile packing verbatim).
    layout_key: Optional[str] = None
    #: Execution metadata the serving engine (``repro.sparse.engine``)
    #: consults when staging right-hand sides.
    #:
    #: ``async_dispatch``: ``run`` only *enqueues* the launch and returns
    #: before the result materializes (every XLA-lowered kernel — jax
    #: eager ops, jitted shard_map programs, and pallas_call all dispatch
    #: asynchronously; completion is observed at ``block_until_ready``).
    #: The engine overlaps host→device staging of the next micro-batch
    #: with device compute of the current one only when this is set; a
    #: synchronous host kernel would make that overlap a lie.
    async_dispatch: bool = True
    #: ``donate_b``: the launch may alias B's device buffer for its
    #: output (``input_output_aliases`` / jit donation), so the caller
    #: must treat the staged buffer as consumed at dispatch.  None of the
    #: registered kernels alias B today — C has B's shape but every
    #: kernel reads B throughout the launch — so the engine keeps its
    #: staging buffer alive until materialization unless this flips.
    donate_b: bool = False
    #: What ``prepare``/``bind`` accept as the matrix operand.  ``"coo"``
    #: specs take a ``repro.core.patterns.COOMatrix`` and compute
    #: ``C = A @ B`` — the contract the cross-kernel differential suite
    #: (``tests/test_differential.py``) verifies against the dense
    #: reference for every registered pair.  Specs with another operand
    #: (the MoE grouped matmul's ``(w, group_ids, bm, bk, bn)`` tuple)
    #: declare it here so generic sweeps can skip them explicitly
    #: instead of special-casing format names.
    operand: str = "coo"
    #: Precision tokens (``Precision.token``) this kernel can execute.
    #: Every spec speaks fp32+int32; jax-backend specs add bf16 values
    #: over their int32 containers; the Pallas packers that store
    #: slab-local / chunk-local indices add compact int16 too (legality
    #: of a *particular* matrix is still checked at prepare time — an
    #: extent past ``2**15 - 1`` raises ``ValueError``).
    supported_precisions: Tuple[str, ...] = ("f32i32",)
    #: Bytes of scalar-prefetched metadata (per-tile chunk ranges, block
    #: coordinates) the kernel holds in SMEM for matrix ``m``.
    smem_footprint: Callable[[Any, KernelContext], int] = \
        lambda m, ctx: 0
    #: Static counts of one launch at width ``d`` from the prepared
    #: layout, ``counters(layout, d) -> {name: int}``, logged as attrs of
    #: the ``repro.execute`` span (``repro.sparse.stream``) and summed
    #: over an ``execute_wide``'s launches, except those in
    #: ``LAYOUT_COUNTERS``; None logs none.
    counters: Optional[Callable[[Any, int], Dict[str, int]]] = None

    def supports_precision(self, precision: Precision) -> bool:
        """True iff this kernel can execute at ``precision``."""
        return precision.token in self.supported_precisions

    @property
    def key(self) -> Tuple[str, str]:
        """The registry key, ``(format, backend)``."""
        return (self.format, self.backend)

    @property
    def layout_cache_key(self) -> Tuple[str, str]:
        """Cache identity of ``prepare``'s output, ``(layout, backend)``."""
        return (self.layout_key or self.format, self.backend)

    def bind(self, m, ctx: KernelContext) -> Callable[[jnp.ndarray],
                                                      jnp.ndarray]:
        """Prepare the layout for ``m`` once and return ``run(b) -> c``."""
        layout = self.prepare(m, ctx)
        return lambda b: self.run(layout, b, ctx)


_REGISTRY: Dict[Tuple[str, str], KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    """Add ``spec`` under ``(spec.format, spec.backend)``; reject dupes."""
    if spec.key in _REGISTRY:
        raise ValueError(f"kernel {spec.key} already registered")
    _REGISTRY[spec.key] = spec
    return spec


def get(format: str, backend: str) -> KernelSpec:
    """Resolve the spec for ``(format, backend)``.

    Raises:
        KeyError: when the pair is unregistered; the message lists what is.
    """
    try:
        return _REGISTRY[(format, backend)]
    except KeyError:
        raise KeyError(
            f"no kernel registered for format={format!r} "
            f"backend={backend!r}; available: {sorted(_REGISTRY)}") from None


def specs() -> Tuple[KernelSpec, ...]:
    """All registered specs, sorted by (format, backend)."""
    return tuple(_REGISTRY[k] for k in sorted(_REGISTRY))


def formats_for(backend: str) -> Tuple[str, ...]:
    """Formats with a kernel registered under ``backend``."""
    return tuple(sorted(f for f, b in _REGISTRY if b == backend))


def feature_matrix() -> Dict[Tuple[str, str], str]:
    """(format, backend) -> one-line description, for docs and tests."""
    return {k: _REGISTRY[k].description for k in sorted(_REGISTRY)}


def spmm(m, b: jnp.ndarray, *, format: str, backend: str = "jax",
         ctx: Optional[KernelContext] = None) -> jnp.ndarray:
    """One-call registry entry point: prepare + run in one shot.

    For repeated execution against one matrix, use
    ``repro.sparse.dispatch`` (cached layouts) or ``spec.bind``.
    """
    spec = get(format, backend)
    return spec.bind(m, ctx or KernelContext())(b)


# ------------------------------------------------------------------ #
# Layout helpers (host-side, shared by specs and the ops compat layer)
# ------------------------------------------------------------------ #

def pad_empty_block_rows(a):
    """Ensure every block row owns >= 1 block (zero block on the diagonal).

    The Pallas kernel writes a C tile only when its block row is visited;
    padding guarantees total coverage without in-kernel masking.
    """
    from repro.sparse.formats import BCSRMatrix
    nb = a.nb
    present = np.zeros(nb, dtype=bool)
    rows_np = np.asarray(a.block_rows)
    present[rows_np] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    if missing.size == 0:
        return a
    blocks = jnp.concatenate(
        [a.blocks, jnp.zeros((missing.size, a.t, a.t), a.blocks.dtype)])
    rows = np.concatenate([rows_np, missing])
    cols = np.concatenate([np.asarray(a.block_cols), missing])
    order = np.argsort(rows, kind="stable")
    counts = np.bincount(rows, minlength=nb)
    ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return BCSRMatrix(
        blocks=blocks[jnp.asarray(order)],
        block_rows=jnp.asarray(rows[order].astype(np.int32)),
        block_cols=jnp.asarray(cols[order].astype(np.int32)),
        block_ptr=jnp.asarray(ptr),
        n=a.n, t=a.t, nnz=a.nnz,
    )


def band_to_blocks(dia_data: np.ndarray, offsets, *, n: int, t: int):
    """Convert DIA storage to the banded kernel's block-band tensor.

    Args:
        dia_data: DIA values, [num_offsets, n] indexed by row.
        offsets: diagonal offsets matching ``dia_data`` rows.
        n: matrix dimension; t must divide n for the kernel grid.
        t: block edge of the band tensor.

    Returns:
        ``(band, w)``: band tensor [nb, 2w+1, t, t] (nb = n / t) and the
        block half-bandwidth w, as consumed by the banded kernel.
    """
    nb = (n + t - 1) // t
    max_off = max(abs(int(o)) for o in offsets) if len(offsets) else 0
    w = (max_off + t - 1) // t
    dia = np.asarray(dia_data)
    band = np.zeros((nb, 2 * w + 1, t, t), dtype=dia.dtype)
    rows = np.arange(n, dtype=np.int64)
    for oi, off in enumerate(offsets):
        c = rows + int(off)
        keep = (c >= 0) & (c < n) & (dia[oi, :n] != 0)
        r, c = rows[keep], c[keep]
        band[r // t, c // t - r // t + w, r % t, c % t] = dia[oi, :n][keep]
    return jnp.asarray(band), w


# ------------------------------------------------------------------ #
# Roofline estimates
# ------------------------------------------------------------------ #

@dataclasses.dataclass(frozen=True)
class KernelRoofline:
    """Sparsity-aware placement of one kernel launch on a roofline."""

    name: str
    ai: float
    useful_flops: float
    mxu_flops: float
    attainable_flops_per_s: float
    mxu_utilization: float


def csr_kernel_roofline(a, d: int, *, regime: str = "random",
                        hw: HardwareSpec = TPU_V5E) -> KernelRoofline:
    """Place a CSR kernel launch on the roofline under its regime model.

    The CSR kernel issues exactly the useful FLOPs (padding slots multiply
    zeros, a negligible <1/chunk overhead), so MXU utilization is reported
    as 1.0; what varies with structure is the B-traffic term of the AI.
    """
    tb = sm.arithmetic_intensity(regime, a.n, a.nnz, d,
                                 sizeof_val=a.data.dtype.itemsize)
    return KernelRoofline(
        name="csr_spmm", ai=tb.ai, useful_flops=tb.flops,
        mxu_flops=tb.flops,
        attainable_flops_per_s=hw.attainable(tb.ai),
        mxu_utilization=1.0)


def bcsr_kernel_roofline(a, d: int,
                         hw: HardwareSpec = TPU_V5E) -> KernelRoofline:
    """Apply the TPU blocked model (DESIGN.md Section 3) to a launch."""
    tb = sm.ai_blocked_tpu(a.n, a.nnz, d, t=a.t, num_blocks=a.num_blocks,
                           sizeof_val=a.blocks.dtype.itemsize)
    util = sm.mxu_utilization(a.nnz, a.t, a.num_blocks)
    return KernelRoofline(
        name="bcsr_spmm", ai=tb.ai, useful_flops=tb.flops,
        mxu_flops=2.0 * d * a.t * a.t * a.num_blocks,
        attainable_flops_per_s=hw.attainable(tb.ai),
        mxu_utilization=util)


def dia_kernel_roofline(m, d: int,
                        hw: HardwareSpec = TPU_V5E) -> KernelRoofline:
    """Diagonal-regime placement: B streamed once, k full diagonals issued."""
    k = max(int(np.unique(m.cols.astype(np.int64) - m.rows).shape[0]), 1)
    tb = sm.arithmetic_intensity("diagonal", m.n, m.nnz, d)
    return KernelRoofline(
        name="banded_spmm", ai=tb.ai, useful_flops=tb.flops,
        mxu_flops=2.0 * d * k * m.n,
        attainable_flops_per_s=hw.attainable(tb.ai),
        mxu_utilization=m.nnz / float(k * m.n))


def grouped_matmul_roofline(T: int, K: int, N: int, E: int, *,
                            itemsize: int = 2,
                            hw: HardwareSpec = TPU_V5E) -> KernelRoofline:
    """Block-diagonal case: every block dense => MXU utilization 1.0."""
    flops = 2.0 * T * K * N
    bytes_moved = itemsize * (T * K + E * K * N + T * N)
    ai = flops / bytes_moved
    return KernelRoofline(
        name="grouped_matmul", ai=ai, useful_flops=flops, mxu_flops=flops,
        attainable_flops_per_s=hw.attainable(ai), mxu_utilization=1.0)


# ------------------------------------------------------------------ #
# Spec implementations
# ------------------------------------------------------------------ #

def _convert(ctx: KernelContext, m, format: str):
    """Convert ``m`` to ``format``'s container, honoring ``ctx.convert``
    (the caller's conversion cache, already bound to the precision) when
    provided; the direct path packs values at the precision's dtype."""
    if ctx.convert is not None:
        return ctx.convert(m, format)
    from repro.sparse import formats as fmt
    dtype = ctx.precision.value_jnp
    if format == "csr":
        return fmt.coo_to_csr(m, dtype=dtype)
    if format == "ell":
        return fmt.coo_to_ell(m, dtype=dtype)
    if format == "bcsr":
        return fmt.coo_to_bcsr(m, ctx.bcsr_block, dtype=dtype)
    if format == "dia":
        return fmt.coo_to_dia(m, dtype=dtype,
                              max_offsets=ctx.max_dia_offsets)
    if format == "binned":
        return fmt.coo_to_binned(m, dtype=dtype)
    if format == "rowsplit":
        return fmt.coo_to_rowsplit(m, dtype=dtype, chunk=ctx.chunk)
    if format == "ell_coo":
        return fmt.coo_to_ell_coo(m, dtype=dtype)
    raise ValueError(f"unknown format {format!r}")


# ------------------------------------------------------------------ #
# Layout statistics shared by the estimates and the dispatch models
# ------------------------------------------------------------------ #

def binned_layout_stats(m, *, slab_rows: int,
                        row_tile: int = ROW_TILE) -> Tuple[int, int]:
    """(slabs_touched, num_visits) of the slab-binned layout for ``m``.

    A visit is one (B slab, row tile) pair with nonzeros — the unit the
    binned kernel writes one partial C block for.  Both counts feed
    ``sm.ai_binned``: B is read once per touched slab, partials cost
    ``2 * num_visits * row_tile * d`` extra C traffic.
    """
    if m.nnz == 0:
        return 1, 1
    slabs = np.asarray(m.cols, dtype=np.int64) // slab_rows
    tiles = np.asarray(m.rows, dtype=np.int64) // row_tile
    num_slabs = max(1, -(-m.n // slab_rows))
    visits = np.unique(tiles * num_slabs + slabs).shape[0]
    return int(np.unique(slabs).shape[0]), int(visits)


def binned_padded_slots(m, *, slab_rows: int, row_tile: int = ROW_TILE,
                        chunk: int = 128) -> int:
    """Slots of the packed binned layout: each visit padded to chunks.

    The packing-inflation gate compares this against ``m.nnz``: where the
    nonzeros of a row tile scatter over many slabs, every visit holds a
    few nonzeros and still pays a whole chunk.
    """
    if m.nnz == 0:
        return chunk
    slabs = np.asarray(m.cols, dtype=np.int64) // slab_rows
    tiles = np.asarray(m.rows, dtype=np.int64) // row_tile
    num_slabs = max(1, -(-m.n // slab_rows))
    _, counts = np.unique(tiles * num_slabs + slabs, return_counts=True)
    return int((-(-counts // chunk)).sum()) * chunk


def rowsplit_window_model(n_nonempty: int, nnz: int,
                          chunk: int = 128) -> int:
    """Expected row-window width of the row-split packing (model side).

    A chunk of ``chunk`` nonzeros spans ~``chunk / avg_degree`` rows;
    rounded up to the kernel's multiple-of-8 output tile.  The packed
    layout computes the exact maximum; the model uses this expectation
    so planning never needs the layout.
    """
    if nnz <= 0 or n_nonempty <= 0:
        return 8
    span = min(chunk, -(-n_nonempty * chunk // nnz) + 1)
    return max(8, -(-span // 8) * 8)


def ell_coo_split_stats(m) -> Tuple[int, int]:
    """(k_cut, tail_nnz) of the hybrid ELL/COO layout for ``m``."""
    from repro.sparse import formats as fmt
    if m.nnz == 0:
        return 1, 0
    deg = np.bincount(np.asarray(m.rows), minlength=m.n)
    k_cut = fmt.ell_coo_cutoff(deg)
    return k_cut, int(np.maximum(deg - k_cut, 0).sum())


def _jax_prepare(format: str):
    def prepare(m, ctx: KernelContext):
        return _convert(ctx, m, format)
    return prepare


def _jax_run(format: str):
    def run(layout, b, ctx: KernelContext):
        # NB: any attribute-style import of repro.sparse.spmm grabs the
        # dispatcher's spmm *function* exported by the package __init__,
        # which shadows the submodule; go through importlib.
        jax_spmm = importlib.import_module("repro.sparse.spmm")
        if ctx.precision.reduced:
            # Reduced precision: B rounds to the storage dtype (the
            # container values already are); accumulation stays fp32
            # inside the implementations.
            b = b.astype(ctx.precision.value_jnp)
        return jax_spmm.IMPLEMENTATIONS[format](layout, b)
    return run


def _jax_estimate(format: str):
    regime = {"csr": "random", "ell": "random", "dia": "diagonal"}

    def estimate(m, d, ctx: KernelContext) -> KernelRoofline:
        if format == "bcsr":
            roof = _bcsr_estimate(m, d, ctx)
            return dataclasses.replace(roof, name="bcsr_spmm_jax")
        tb = sm.arithmetic_intensity(regime[format], m.n, m.nnz, d)
        return KernelRoofline(
            name=f"{format}_spmm_jax", ai=tb.ai, useful_flops=tb.flops,
            mxu_flops=tb.flops,
            attainable_flops_per_s=ctx.hardware.attainable(tb.ai),
            mxu_utilization=1.0)
    return estimate


def _zero_footprint(n: int, d: int, ctx: KernelContext) -> int:
    return 0


#: jax-backend containers keep int32 global indices (the XLA gather
#: operand), so the jax specs support bf16 values but not compact
#: indices; the Pallas packers store slab-/chunk-local indices and add
#: int16.
_JAX_PRECISIONS = ("f32i32", "bf16i32")
_PALLAS_STREAM_PRECISIONS = ("f32i32", "bf16i32", "bf16i16")

for _f, _desc in (("csr", "gather + segment-sum (XLA)"),
                  ("ell", "padded slot scan (XLA)"),
                  ("bcsr", "batched dense-block einsum (XLA)"),
                  ("dia", "static shifted axpy (XLA)")):
    register(KernelSpec(
        format=_f, backend="jax", description=_desc,
        prepare=_jax_prepare(_f), run=_jax_run(_f),
        estimate=_jax_estimate(_f), vmem_footprint=_zero_footprint,
        supported_precisions=_JAX_PRECISIONS))


def _binned_estimate(name: str, resolve_slab):
    def estimate(m, d, ctx: KernelContext) -> KernelRoofline:
        slab = resolve_slab(m, ctx)
        touched, visits = binned_layout_stats(m, slab_rows=slab,
                                              row_tile=ctx.row_tile)
        tb = sm.ai_binned(m.n, m.nnz, d, slab_rows=slab,
                          slabs_touched=touched, num_visits=visits,
                          row_tile=ctx.row_tile)
        return KernelRoofline(
            name=name, ai=tb.ai, useful_flops=tb.flops, mxu_flops=tb.flops,
            attainable_flops_per_s=ctx.hardware.attainable(tb.ai),
            mxu_utilization=1.0)
    return estimate


def _jax_slab(m, ctx: KernelContext) -> int:
    from repro.sparse import formats as fmt
    return fmt.default_slab_rows(m.n)


def _pallas_slab(m, ctx: KernelContext) -> int:
    return ctx.resolve_b_tile(m.n) or m.n


def _rowsplit_estimate(name: str):
    def estimate(m, d, ctx: KernelContext) -> KernelRoofline:
        n_nonempty = int(np.unique(np.asarray(m.rows)).shape[0])
        window = rowsplit_window_model(n_nonempty, m.nnz, ctx.chunk)
        tb = sm.ai_rowsplit(m.n, m.nnz, d, window=window, chunk=ctx.chunk)
        return KernelRoofline(
            name=name, ai=tb.ai, useful_flops=tb.flops, mxu_flops=tb.flops,
            attainable_flops_per_s=ctx.hardware.attainable(tb.ai),
            mxu_utilization=1.0)
    return estimate


def _ell_coo_estimate(name: str):
    def estimate(m, d, ctx: KernelContext) -> KernelRoofline:
        k_cut, tail = ell_coo_split_stats(m)
        tb = sm.ai_ell_coo(m.n, m.nnz, d, k_cut=k_cut, tail_nnz=tail)
        issued = max(m.n * k_cut + tail, 1)
        return KernelRoofline(
            name=name, ai=tb.ai, useful_flops=tb.flops,
            mxu_flops=2.0 * d * issued,
            attainable_flops_per_s=ctx.hardware.attainable(tb.ai),
            mxu_utilization=min(1.0, m.nnz / issued))
    return estimate


for _f, _desc, _est in (
        ("binned", "slab-binned gather + segment-sum (XLA)",
         _binned_estimate("binned_spmm_jax", _jax_slab)),
        ("rowsplit", "equal-nnz chunk gather + segment-sum (XLA)",
         _rowsplit_estimate("rowsplit_spmm_jax")),
        ("ell_coo", "padded-body slot scan + COO-tail segment-sum (XLA)",
         _ell_coo_estimate("ell_coo_spmm_jax"))):
    register(KernelSpec(
        format=_f, backend="jax", description=_desc,
        prepare=_jax_prepare(_f), run=_jax_run(_f),
        estimate=_est, vmem_footprint=_zero_footprint,
        supported_precisions=_JAX_PRECISIONS))


def _csr_pallas_prepare(m, ctx: KernelContext):
    csr = _convert(ctx, m, "csr")
    arrays = csr_to_row_tiles(
        np.asarray(csr.indptr), np.asarray(csr.indices),
        np.asarray(csr.data), n=csr.n, row_tile=ctx.row_tile,
        chunk=ctx.chunk, index_dtype=ctx.precision.index_np)
    return {"n": csr.n, "row_tile": ctx.row_tile,
            "chunks": int(arrays[0][-1]),
            "arrays": tuple(jnp.asarray(x) for x in arrays)}


def _csr_pallas_run(layout, b, ctx: KernelContext):
    if ctx.precision.reduced:
        b = b.astype(ctx.precision.value_jnp)
    return csr_spmm_pallas(
        *layout["arrays"], b, n=layout["n"],
        row_tile=layout["row_tile"], block_d=pallas_block_d(b.shape[1]),
        vmem_limit=ctx.vmem_limit, interpret=ctx.resolve_interpret())


def _csr_pallas_estimate(m, d, ctx: KernelContext) -> KernelRoofline:
    tb = sm.arithmetic_intensity("random", m.n, m.nnz, d)
    return KernelRoofline(
        name="csr_spmm", ai=tb.ai, useful_flops=tb.flops, mxu_flops=tb.flops,
        attainable_flops_per_s=ctx.hardware.attainable(tb.ai),
        mxu_utilization=1.0)


def _chunk_footprint(rows: int, bd: int, ctx: KernelContext,
                     slots: int = 1) -> int:
    """VMEM of the CSR-family chunk machinery, double-buffered blocks.

    ``slots`` fp32 ``[chunk, bd]`` gather buffers, two entries each of
    the value, column and int8 slot chunks, and two buffers of the fp32
    ``[rows, bd]`` output block.
    """
    p = ctx.precision
    return (slots * 4 * ctx.chunk * bd
            + 2 * ctx.chunk * (p.sizeof_val + p.sizeof_idx + 1)
            + 2 * 4 * rows * bd)


def _csr_pallas_footprint(n: int, d: int, ctx: KernelContext) -> int:
    # B stays in HBM: nothing in the working set grows with n.  The
    # gather is pipelined over two slots (``repro.kernels.csr_spmm``).
    return _chunk_footprint(ctx.row_tile, min(512, pallas_block_d(d)), ctx,
                            slots=2)


def _csr_pallas_counters(layout, d: int) -> Dict[str, int]:
    return pipeline_counts(layout["chunks"], d, pallas_block_d(d))


def _csr_pallas_smem(m, ctx: KernelContext) -> int:
    return 4 * (-(-m.n // ctx.row_tile) + 1)        # per-tile chunk starts


for _f in ("csr", "ell"):
    # ELL exists for VPU-style padding; the row-tiled CSR kernel already
    # vectorizes on TPU, so ELL picks lower to it (layout_key="csr":
    # both specs share one cached row-tile packing per matrix).
    register(KernelSpec(
        format=_f, backend="pallas",
        description="row-tiled gather/segment-sum kernel, B rows DMA'd "
                    "from HBM",
        prepare=_csr_pallas_prepare, run=_csr_pallas_run,
        estimate=_csr_pallas_estimate, vmem_footprint=_csr_pallas_footprint,
        smem_footprint=_csr_pallas_smem, counters=_csr_pallas_counters,
        layout_key="csr", supported_precisions=_PALLAS_STREAM_PRECISIONS))


def _binned_pallas_prepare(m, ctx: KernelContext):
    csr = _convert(ctx, m, "csr")
    bt = ctx.resolve_b_tile(m.n)
    arrays = csr_to_slab_bins(
        np.asarray(csr.indptr), np.asarray(csr.indices),
        np.asarray(csr.data), n=csr.n, row_tile=ctx.row_tile,
        chunk=ctx.chunk, b_tile=bt,
        index_dtype=ctx.precision.index_np)
    return {"n": csr.n, "b_tile": bt, "row_tile": ctx.row_tile,
            "arrays": tuple(jnp.asarray(x) for x in arrays)}


def _binned_pallas_run(layout, b, ctx: KernelContext):
    if ctx.precision.reduced:
        b = b.astype(ctx.precision.value_jnp)
    return binned_spmm_pallas(
        *layout["arrays"], b, n=layout["n"],
        row_tile=layout["row_tile"], b_tile=layout["b_tile"],
        block_d=pallas_block_d(b.shape[1]), vmem_limit=ctx.vmem_limit,
        interpret=ctx.resolve_interpret())


def _binned_pallas_footprint(n: int, d: int, ctx: KernelContext) -> int:
    bd = min(512, pallas_block_d(d))
    bt = ctx.resolve_b_tile(n) or -(-n // 8) * 8
    # One fp32 B slab, double-buffered, on top of the chunk machinery
    # (the visit partials live in HBM and stream through the C block).
    return 2 * 4 * bt * bd + _chunk_footprint(ctx.row_tile, bd, ctx)


def _binned_pallas_smem(m, ctx: KernelContext) -> int:
    _, visits = binned_layout_stats(m, slab_rows=ctx.resolve_b_tile(m.n)
                                    or m.n, row_tile=ctx.row_tile)
    return 4 * (2 * visits + 1)               # visit slabs + chunk starts


register(KernelSpec(
    format="binned", backend="pallas",
    description="two-phase binned kernel: slab-major accumulation over "
                "VMEM-resident B slabs, segment-sum epilogue",
    prepare=_binned_pallas_prepare, run=_binned_pallas_run,
    estimate=_binned_estimate("binned_spmm", _pallas_slab),
    vmem_footprint=_binned_pallas_footprint,
    smem_footprint=_binned_pallas_smem,
    layout_key="binned", supported_precisions=_PALLAS_STREAM_PRECISIONS))


def _rowsplit_pallas_prepare(m, ctx: KernelContext):
    csr = _convert(ctx, m, "csr")
    row_map, cols, slots, vals = pack_rowsplit_chunks(
        np.asarray(csr.indptr), np.asarray(csr.indices),
        np.asarray(csr.data), n=csr.n, chunk=ctx.chunk,
        index_dtype=ctx.precision.index_np)
    return {"n": csr.n, "window": int(row_map.shape[1]),
            "arrays": tuple(jnp.asarray(x)
                            for x in (row_map, cols, slots, vals))}


def _rowsplit_pallas_run(layout, b, ctx: KernelContext):
    row_map, cols, slots, vals = layout["arrays"]
    if ctx.precision.reduced:
        b = b.astype(ctx.precision.value_jnp)
    return rowsplit_spmm_pallas(
        row_map, cols, slots, vals, b, n=layout["n"],
        window=layout["window"], block_d=pallas_block_d(b.shape[1]),
        vmem_limit=ctx.vmem_limit, interpret=ctx.resolve_interpret())


def _rowsplit_pallas_footprint(n: int, d: int, ctx: KernelContext) -> int:
    bd = min(512, pallas_block_d(d))
    n_pad = -(-n // 8) * 8
    # Whole fp32 B resident and double-buffered (the load-balance kernel
    # does not stream B), plus the chunk machinery with the widest
    # possible window (one row per nonzero of a chunk).
    return 2 * 4 * n_pad * bd + _chunk_footprint(ctx.chunk, bd, ctx)


register(KernelSpec(
    format="rowsplit", backend="pallas",
    description="equal-nnz row-split kernel (merge-path load balance), "
                "windowed partials + scatter epilogue",
    prepare=_rowsplit_pallas_prepare, run=_rowsplit_pallas_run,
    estimate=_rowsplit_estimate("rowsplit_spmm"),
    vmem_footprint=_rowsplit_pallas_footprint,
    layout_key="rowsplit", supported_precisions=_PALLAS_STREAM_PRECISIONS))


# The hybrid ELL/COO pick lowers to the row-tiled CSR kernel on TPU
# (like ELL): the CSR kernel's sliced-ELL chunk packing already realizes
# the body/tail split physically — short rows pack densely, hub-row
# overflow lands in extra chunks — so the pallas pair shares the cached
# CSR row-tile layout and differs only in its estimate.
register(KernelSpec(
    format="ell_coo", backend="pallas",
    description="hybrid ELL/COO pick lowered to the row-tiled CSR kernel",
    prepare=_csr_pallas_prepare, run=_csr_pallas_run,
    estimate=_ell_coo_estimate("ell_coo_spmm"),
    vmem_footprint=_csr_pallas_footprint, smem_footprint=_csr_pallas_smem,
    counters=_csr_pallas_counters,
    layout_key="csr", supported_precisions=_PALLAS_STREAM_PRECISIONS))


def bcsr_segment_blocks(hw: HardwareSpec) -> Optional[int]:
    """Blocks whose coordinates one BCSR segment prefetches into SMEM
    (``kernel_smem_limit``); None where the spec states no SMEM size."""
    limit = kernel_smem_limit(hw)
    return limit // COORD_BYTES if limit else None


def _bcsr_pallas_prepare(m, ctx: KernelContext):
    # Packed on the host straight into the kernel's lane-packed layout: a
    # [N, t, t] device copy beside it would double the largest array.
    t = ctx.bcsr_block
    blocks, _, cols, ptr = pack_bcsr(m.rows, m.cols, m.vals, n=m.n, t=t,
                                     dtype=ctx.precision.value_jnp)
    cap = bcsr_segment_blocks(ctx.hardware) or blocks.shape[0]
    segments = bcsr_segments(ptr, cap)
    rows = segment_rows(ptr, segments)
    return {"n": m.n, "t": t, "segments": segments, "rows": rows,
            "counts": ring_counts(ptr, rows, t),
            "arrays": tuple(jnp.asarray(x) for x in (blocks, ptr, cols))}


def _bcsr_pallas_run(layout, b, ctx: KernelContext):
    if ctx.precision.reduced:
        b = b.astype(ctx.precision.value_jnp)
    return bcsr_spmm_pallas(
        *layout["arrays"], b, n=layout["n"], t=layout["t"],
        block_d=pallas_block_d(b.shape[1]), vmem_limit=ctx.vmem_limit,
        interpret=ctx.resolve_interpret(), segments=layout["segments"],
        rows=layout["rows"])


def _bcsr_pallas_counters(layout, d: int) -> Dict[str, int]:
    """Stored blocks visited, grid steps (block rows) and cold blocks of
    a d-pass (``ring_counts``) times the d-passes; the block edge, and
    the ``pallas_call`` segments of one launch."""
    width, bd = d_passes(d, pallas_block_d(d))
    return {**{k: width // bd * v for k, v in layout["counts"].items()},
            "block_t": layout["t"],
            "segments": len(layout["segments"])}


def _bcsr_estimate(m, d, ctx: KernelContext) -> KernelRoofline:
    from repro.core.classify import block_stats
    t = ctx.bcsr_block
    stats = block_stats(m, t)
    N = max(int(stats["N"]), 1)
    tb = sm.ai_blocked_tpu(m.n, m.nnz, d, t=t, num_blocks=N)
    return KernelRoofline(
        name="bcsr_spmm", ai=tb.ai, useful_flops=tb.flops,
        mxu_flops=2.0 * d * t * t * N,
        attainable_flops_per_s=ctx.hardware.attainable(tb.ai),
        mxu_utilization=sm.mxu_utilization(m.nnz, t, N))


def _block_footprint(t: int, bd: int, ctx: KernelContext) -> int:
    """A ``t x t`` block and a ``t x bd`` B tile at the value width plus
    the fp32 ``t x bd`` C tile, each double-buffered."""
    return 2 * (ctx.precision.sizeof_val * (t * t + t * bd) + 4 * t * bd)


def _bcsr_pallas_footprint(n: int, d: int, ctx: KernelContext) -> int:
    """The ring's slots of a ``t x t`` block and a ``t x bd`` B tile at
    the value width, and the fp32 ``t x bd`` C tile the blocks accumulate
    into, double-buffered by the output pipeline."""
    t, (_, bd) = ctx.bcsr_block, d_passes(d, pallas_block_d(d))
    return (ring_slots(t) * ctx.precision.sizeof_val * (t * t + t * bd)
            + 2 * 4 * t * bd)


def _bcsr_pallas_smem(m, ctx: KernelContext) -> int:
    """Bytes of the largest segment's coordinates, bounded from above
    without packing: segments hold at most ``bcsr_segment_blocks`` blocks
    unless one block row holds more, and a block row holds at most ``n /
    t`` blocks and at most its nonzeros."""
    t = ctx.bcsr_block
    nb = -(-m.n // t)
    per_row = np.bincount(np.asarray(m.rows) // t, minlength=nb)
    widest = min(nb, int(per_row.max(initial=1)))
    blocks = min(m.nnz, nb * nb) + nb           # + one pad per empty row
    cap = bcsr_segment_blocks(ctx.hardware) or blocks
    return COORD_BYTES * max(widest, min(blocks, cap))


register(KernelSpec(
    format="bcsr", backend="pallas",
    description="dense-block MXU kernel (a grid step per block row, "
                "blocks streamed through a DMA ring, segmented to fit "
                "SMEM)",
    prepare=_bcsr_pallas_prepare, run=_bcsr_pallas_run,
    estimate=_bcsr_estimate, vmem_footprint=_bcsr_pallas_footprint,
    smem_footprint=_bcsr_pallas_smem, counters=_bcsr_pallas_counters,
    # Block coordinates are scalar-prefetch metadata, not per-nonzero
    # traffic, so bcsr gains nothing from int16 and keeps int32.
    supported_precisions=_JAX_PRECISIONS))


def _dia_pallas_prepare(m, ctx: KernelContext):
    dia = _convert(ctx, m, "dia")
    t = pallas_band_tile(m.n)
    band, w = band_to_blocks(np.asarray(dia.data), dia.offsets, n=m.n, t=t)
    return {"band": band, "w": w, "t": t}


def _dia_pallas_run(layout, b, ctx: KernelContext):
    if ctx.precision.reduced:
        b = b.astype(ctx.precision.value_jnp)
    return banded_spmm_pallas(
        layout["band"], b, t=layout["t"], w=layout["w"],
        block_d=pallas_block_d(b.shape[1]), vmem_limit=ctx.vmem_limit,
        interpret=ctx.resolve_interpret())


def _dia_pallas_estimate(m, d, ctx: KernelContext) -> KernelRoofline:
    return dia_kernel_roofline(m, d, hw=ctx.hardware)


def _dia_pallas_footprint(n: int, d: int, ctx: KernelContext) -> int:
    return _block_footprint(pallas_band_tile(n), min(512, pallas_block_d(d)),
                            ctx)


register(KernelSpec(
    format="dia", backend="pallas",
    description="block-band kernel (B streamed once)",
    prepare=_dia_pallas_prepare, run=_dia_pallas_run,
    estimate=_dia_pallas_estimate, vmem_footprint=_dia_pallas_footprint,
    # DIA stores no per-nonzero indices at all (offsets are static), so
    # the index axis is moot; bf16 values still halve the band traffic.
    supported_precisions=_JAX_PRECISIONS))


def _grouped_prepare(operand, ctx: KernelContext):
    # Operand: (w[E, K, N], group_ids[T // bm], bm, bk, bn).
    return operand


def _grouped_run(layout, x, ctx: KernelContext):
    w, group_ids, bm, bk, bn = layout
    return grouped_matmul_pallas(x, w, group_ids, bm=bm, bk=bk, bn=bn,
                                 vmem_limit=ctx.vmem_limit,
                                 interpret=ctx.resolve_interpret())


def _grouped_estimate(operand, d, ctx: KernelContext) -> KernelRoofline:
    w, group_ids, bm, _, _ = operand
    E, K, N = w.shape
    T = int(np.asarray(group_ids).shape[0]) * bm
    return grouped_matmul_roofline(T, K, N, E, hw=ctx.hardware)


def _grouped_footprint(n: int, d: int, ctx: KernelContext) -> int:
    bm = bk = bn = 128
    return 2 * 4 * (bm * bk + bk * bn + bm * bn)


register(KernelSpec(
    format="grouped", backend="pallas",
    description="MoE expert FFN as block-diagonal grouped matmul",
    prepare=_grouped_prepare, run=_grouped_run,
    estimate=_grouped_estimate, vmem_footprint=_grouped_footprint,
    operand="moe"))

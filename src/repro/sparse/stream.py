"""Batched/streamed SpMM: plan once, execute across many right-hand sides.

The dispatcher (``repro.sparse.dispatch``) already splits SpMM into a plan
phase (classify the structure, evaluate each format's sparsity-aware
roofline, amortize conversion cost over an expected reuse count) and an
execute phase (convert once, run the chosen kernel).  This module is the
serving-path API on top of that split:

    spec = BSpec(d=64, reuse=256)        # 256 RHS batches expected
    plan = sparse.plan(m, spec)          # classify + model + convert ONCE
    c0 = plan.execute(b0)                # zero-dispatch replay
    cs = plan.execute_many(bs)           # a stream of [n, d] batches
    cw = plan.execute_wide(b_wide)       # one [n, D] B, column-sharded

Two things distinguish this from calling ``sparse.spmm`` per batch:

1. **Amortized planning.**  The expected reuse count in the ``BSpec`` is
   fed into the DispatchPlan's conversion-cost model, so the chosen format
   can differ from the single-shot choice: a format that is faster per
   call but expensive to build (BCSR's dense t x t blocks) loses at
   ``reuse=1`` and wins at ``reuse=1000`` (the paper's conversion-cost
   amortization term, Section III).

2. **Zero-dispatch replay.**  ``execute`` holds the bound kernel closure
   from ``Dispatcher.executor`` — no classification, no plan-cache or
   conversion-cache lookups, no policy checks per call.  Per-call dispatch
   pays those on every batch; the streamed benchmark
   (``benchmarks/stream.py``) measures the gap across the four paper
   sparsity structures.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Iterable, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.patterns import COOMatrix
from repro.sparse import dispatch as _dispatch

_LOG = logging.getLogger(__name__)

#: ``execute_many`` warns when realized reuse exceeds the planned horizon
#: by more than this factor (the conversion-amortization model was fed a
#: horizon off by >2x, so the format choice may be stale).
REUSE_DRIFT_FACTOR = 2.0


@dataclasses.dataclass(frozen=True)
class BSpec:
    """Static description of the dense right-hand-side stream.

    Attributes:
        d: width of each right-hand side (every ``B`` is ``[n, d]``).
        reuse: expected number of executions the plan will serve.  This is
            the conversion amortization horizon fed to the dispatcher's
            cost model; under-estimating it biases the choice toward
            cheap-to-build formats, over-estimating toward
            fast-steady-state ones.
        dtype: element dtype of the stream (informational; kernels follow
            the dtype of each ``B`` actually passed).
        precision: optional storage precision to force on the plan — a
            :class:`repro.core.precision.Precision` or a token like
            ``"bf16"`` / ``"bf16i32"``.  ``None`` (default) lets the
            dispatcher pick per the roofline and the accuracy gate.
        tolerance: elementwise accuracy budget handed to the dispatcher's
            precision gate; reduced-precision candidates become eligible
            only when ``tolerance`` covers their rounding eps (see
            ``Dispatcher.plan``).  ``None`` uses the dispatcher default.
    """

    d: int
    reuse: int = 32
    dtype: Any = jnp.float32
    precision: Any = None
    tolerance: Optional[float] = None

    def __post_init__(self):
        """Validate widths and horizons at construction time."""
        if self.d < 1:
            raise ValueError(f"BSpec.d must be >= 1, got {self.d}")
        if self.reuse < 1:
            raise ValueError(f"BSpec.reuse must be >= 1, got {self.reuse}")


def as_b_spec(spec: Union[int, BSpec, jnp.ndarray],
              *, reuse: Optional[int] = None) -> BSpec:
    """Coerce a width, an example batch, or a BSpec into a ``BSpec``.

    Args:
        spec: an ``int`` width ``d``, an example ``[n, d]`` array, or an
            existing :class:`BSpec` (returned as-is unless ``reuse`` is
            given).
        reuse: optional override for the expected execution count.

    Returns:
        A normalized :class:`BSpec`.
    """
    if isinstance(spec, BSpec):
        return spec if reuse is None else dataclasses.replace(
            spec, reuse=reuse)
    if isinstance(spec, (int, np.integer)):
        return BSpec(d=int(spec), reuse=32 if reuse is None else reuse)
    shape = getattr(spec, "shape", None)
    if shape is not None and len(shape) == 2:
        return BSpec(d=int(shape[1]), reuse=32 if reuse is None else reuse,
                     dtype=getattr(spec, "dtype", jnp.float32))
    raise TypeError(
        f"b_spec must be an int width, a BSpec, or an example [n, d] "
        f"array; got {type(spec).__name__}")


class StreamPlan:
    """A persistent, replayable SpMM plan for one matrix and a RHS stream.

    Construction runs the whole one-time pipeline — structure
    classification, per-format roofline evaluation with the stream's reuse
    horizon, format conversion, and kernel layout packing — so every
    ``execute`` afterwards is a bare kernel launch.  Instances are
    intended to live as long as the serving process holds the matrix.
    """

    def __init__(self, dispatcher: _dispatch.Dispatcher, m: COOMatrix,
                 spec: BSpec, *, strategy: str = "auto"):
        """Plan and bind; see :func:`plan` for the usual entry point.

        Args:
            dispatcher: the :class:`repro.sparse.dispatch.Dispatcher` that
                owns caches and hardware model.
            m: square sparse pattern, ``[n, n]``.
            spec: the stream description (width + expected reuse).
            strategy: ``"auto"`` or a forced format name.
        """
        self._m = m
        self._dispatcher = dispatcher
        self._strategy = strategy
        self.spec = spec
        self.dispatch = dispatcher.plan(m, spec.d, strategy=strategy,
                                        reuse=spec.reuse,
                                        precision=spec.precision,
                                        tolerance=spec.tolerance)
        # Eager bind: conversion + packing happen NOW, not on first
        # execute.  (The first execute still pays the kernel's one-time
        # XLA compile for this shape — latency-sensitive servers should
        # warm up with one batch, as launch/serve.py does.)
        with obs.span("repro.plan.bind"):
            self._run = self._bind()
        self._counters: dict = {}        # width -> _launch_counters(width)
        self.executed = 0
        self._reuse_warned = False

    def _bind(self):
        """Resolve the executor this plan replays.

        Subclasses override this hook to bind a different execution
        tier over the same DispatchPlan — ``repro.sparse.shard``'s
        :class:`~repro.sparse.shard.ShardedPlan` returns a ``shard_map``
        closure here instead of the single-device kernel.
        """
        return self._dispatcher.executor(self._m, self.dispatch)

    def _launch_counters(self, d: int) -> dict:
        """Static counts of one launch at width ``d``, from the bound
        kernel's ``KernelSpec.counters`` over its prepared layout."""
        from repro.kernels import registry
        spec = registry.get(self.dispatch.chosen, self.dispatch.backend)
        if spec.counters is None:
            return {}
        return spec.counters(self._dispatcher.layout(self._m, self.dispatch),
                             d)

    def _span_attrs(self, *widths: int) -> dict:
        """Attrs of a ``repro.execute`` span over launches at ``widths``:
        the format, and each launch counter summed over the launches."""
        from repro.kernels.registry import LAYOUT_COUNTERS
        attrs = {"format": self.chosen}
        for w in widths:
            if w not in self._counters:
                self._counters[w] = self._launch_counters(w)
            for k, v in self._counters[w].items():
                attrs[k] = v if k in LAYOUT_COUNTERS else attrs.get(k, 0) + v
        return attrs

    @property
    def n(self) -> int:
        """Matrix dimension; every RHS must have ``n`` rows."""
        return self._m.n

    @property
    def chosen(self) -> str:
        """The format the amortized roofline model selected."""
        return self.dispatch.chosen

    @property
    def precision(self) -> str:
        """The storage-precision token the plan executes at (e.g.
        ``"f32i32"`` or ``"bf16i16"``); replays pack values and indices
        at these dtypes and accumulate in fp32."""
        return self.dispatch.precision

    def _check(self, b: jnp.ndarray, *, width: Optional[int] = None) -> None:
        """Reject shape-mismatched operands with a precise message."""
        if b.ndim != 2 or b.shape[0] != self.n:
            raise ValueError(
                f"operand shape {tuple(b.shape)} incompatible with plan for "
                f"[{self.n}, {self.n}] matrix; expected [{self.n}, d]")
        if width is not None and b.shape[1] != width:
            raise ValueError(
                f"operand width {b.shape[1]} != planned width {width}; "
                f"use execute_wide for other widths")

    def execute(self, b: jnp.ndarray) -> jnp.ndarray:
        """Run ``C = A @ B`` for one planned-width batch.

        Args:
            b: dense right-hand side, ``[n, spec.d]``.

        Returns:
            ``C`` as a dense ``[n, spec.d]`` array.  The call is logged as
            a ``repro.execute`` span (:mod:`repro.obs`) that times the
            host side: the launch is enqueued, not waited for.  Its attrs
            are the format and the kernel's launch counters
            (``KernelSpec.counters``; the CSR kernel's ``chunks`` and
            ``cold_chunks``, the BCSR kernel's ``blocks``, ``steps``,
            ``cold_blocks``, ``block_t`` and ``segments``).
        """
        self._check(b, width=self.spec.d)
        with obs.span("repro.execute", **self._span_attrs(self.spec.d)):
            out = self._run(b)
        self.executed += 1          # count only replays that succeeded
        self._audit_reuse()
        return out

    def execute_async(self, b: jnp.ndarray) -> jnp.ndarray:
        """Dispatch one planned-width batch without a sync point.

        Identical to :meth:`execute` except the caller owns the sync:
        the returned array is an in-flight device value (XLA dispatches
        asynchronously; see ``KernelSpec.async_dispatch``), so the host
        is free to stage the next operand while the device computes —
        the overlap the serving engine (``repro.sparse.engine``) builds
        its double buffering on.  Materialize with
        ``jax.block_until_ready``.

        Args:
            b: dense right-hand side, ``[n, spec.d]``.

        Returns:
            ``C`` as an un-materialized ``[n, spec.d]`` device array; the
            ``repro.execute`` span times the enqueue only.
        """
        self._check(b, width=self.spec.d)
        with obs.span("repro.execute", **self._span_attrs(self.spec.d)):
            out = self._run(b)
        self.executed += 1
        self._audit_reuse()
        return out

    def execute_many_async(self, bs: Union[jnp.ndarray, Sequence[jnp.ndarray],
                                           Iterable[jnp.ndarray]]) -> list:
        """Dispatch a whole stream with no sync point and no stacking.

        The async counterpart of :meth:`execute_many` (ROADMAP's async
        ``execute_many``): every batch is enqueued back-to-back so the
        device pipeline stays full, and the un-materialized per-batch
        results come back as a list — no ``jnp.stack`` barrier forcing a
        layout copy before the caller even needs the values.

        Args:
            bs: a stacked ``[k, n, d]`` array or an iterable of ``k``
                arrays of shape ``[n, d]``.

        Returns:
            List of ``k`` in-flight ``[n, d]`` device arrays; call
            ``jax.block_until_ready`` on them (or on the list) to wait.
        """
        if hasattr(bs, "ndim") and getattr(bs, "ndim", 0) == 3:
            bs = [bs[i] for i in range(bs.shape[0])]
        outs = []
        for b in bs:
            self._check(b, width=self.spec.d)
            outs.append(self._run(b))
            self.executed += 1
        self._audit_reuse()
        return outs

    def execute_many(self, bs: Union[jnp.ndarray, Sequence[jnp.ndarray],
                                     Iterable[jnp.ndarray]]) -> jnp.ndarray:
        """Replay the bound kernel across a stream of right-hand sides.

        Args:
            bs: either a stacked ``[k, n, d]`` array or an iterable of
                ``k`` arrays of shape ``[n, d]``.

        Returns:
            The stacked results, ``[k, n, d]``.  Result dtype follows the
            operands, except an empty stream, which has no operands to
            follow and returns a ``[0, n, d]`` array of ``spec.dtype``.
        """
        if hasattr(bs, "ndim") and getattr(bs, "ndim", 0) == 3:
            bs = [bs[i] for i in range(bs.shape[0])]
        outs = []
        for b in bs:
            self._check(b, width=self.spec.d)
            outs.append(self._run(b))
            self.executed += 1
        self._audit_reuse()
        if not outs:
            return jnp.zeros((0, self.n, self.spec.d), dtype=self.spec.dtype)
        return jnp.stack(outs)

    def _audit_reuse(self) -> None:
        """Warn (once) when the realized reuse drifts >2x past the plan.

        The reuse horizon is an input to the conversion-amortization
        model; when the stream outlives it by more than
        ``REUSE_DRIFT_FACTOR``, the format choice may no longer be the
        amortized-best one — :meth:`replan` re-evaluates at the observed
        horizon (ROADMAP streamed-dispatch follow-up, minimal version).
        """
        if self._reuse_warned:
            return
        if self.executed > REUSE_DRIFT_FACTOR * self.spec.reuse:
            self._reuse_warned = True
            _LOG.warning(
                "StreamPlan reuse horizon off by >%.0fx: planned %d, "
                "executed %d (utilization %.1fx); the conversion "
                "amortization that picked %r assumed the shorter stream — "
                "consider plan.replan(observed_reuse=%d)",
                REUSE_DRIFT_FACTOR, self.spec.reuse, self.executed,
                self.executed / self.spec.reuse, self.chosen, self.executed)

    def replan(self, observed_reuse: int) -> "StreamPlan":
        """Re-plan at an observed reuse horizon; returns a new StreamPlan.

        Runs the dispatcher's amortized roofline again with
        ``reuse=observed_reuse`` — the chosen format can flip (e.g. to an
        expensive-to-build but faster-steady-state one once the horizon
        justifies its conversion).  Cheap when the format does not change:
        the dispatcher's conversion and layout caches are already warm for
        this matrix.

        Args:
            observed_reuse: the realized (or newly expected) number of
                executions, e.g. ``plan.executed``.

        Returns:
            A fresh bound :class:`StreamPlan`; this plan stays valid.
        """
        if observed_reuse < 1:
            raise ValueError(
                f"observed_reuse must be >= 1, got {observed_reuse}")
        spec = dataclasses.replace(self.spec, reuse=observed_reuse)
        return StreamPlan(self._dispatcher, self._m, spec,
                          strategy=self._strategy)

    def maybe_replan(self) -> Optional["StreamPlan"]:
        """The mid-stream re-plan hook: a fresh plan when the audit fired.

        Returns ``None`` while the planned horizon still holds.  Once the
        realized reuse drifts past ``REUSE_DRIFT_FACTOR`` (the same
        condition that flips ``stats()["replan_suggested"]``), returns
        :meth:`replan` at the observed horizon — a fully bound plan whose
        format choice reflects the stream actually being served.  The
        caller swaps atomically (both plans stay valid; the serving
        engine does this between micro-batches, never mid-batch).
        """
        if not self._reuse_warned:
            return None
        return self.replan(max(self.executed, 1))

    def exec_hints(self) -> dict:
        """Execution metadata for the serving engine's staging policy.

        Resolved from the bound :class:`repro.kernels.registry.KernelSpec`:
        ``async_dispatch`` (the launch enqueues and returns, so staging
        the next micro-batch overlaps device compute) and ``donate_b``
        (the launch may alias B's buffer, so the staged operand is
        consumed at dispatch).  See the field docs on ``KernelSpec``.
        """
        from repro.kernels import registry
        spec = registry.get(self.dispatch.chosen, self.dispatch.backend)
        return {"async_dispatch": spec.async_dispatch,
                "donate_b": spec.donate_b}

    def coalesce_block_d(self, total_cols: int) -> int:
        """Widest per-launch column block a coalesced batch may replay at.

        jax-backend kernels adapt their operand width per call and carry
        no resident-VMEM model, so a whole coalesced micro-batch can run
        as one launch — the engine's throughput win.  The width is
        *quantized* to a power-of-two multiple of the planned ``spec.d``
        rather than the raw column count: every distinct launch width
        jit-compiles its own program, and un-quantized micro-batches
        (whose widths vary with arrival timing) would recompile on
        nearly every batch — ~200 ms a time, swamping the coalescing
        win.  Size classes keep the compiled-shape set logarithmic, and
        the engine's warm-up primes them.  Pallas layouts were packed
        for the planned width (``resolve_b_tile``'s per-d B-slab
        re-packing sized the VMEM slab for ``plan_d = spec.d``), so
        their replay stays at planned-width blocks: a wider launch would
        burst the slab budget the layout was built against.

        Args:
            total_cols: the coalesced batch's total column count.

        Returns:
            The ``block_d`` to pass to :meth:`execute_wide` (the engine
            pads the batch to a multiple of it, keeping launch shapes
            from proliferating).
        """
        if self.dispatch.backend == "jax":
            d = max(self.spec.d, 1)
            blocks = -(-max(int(total_cols), 1) // d)   # ceil-div
            size = 1
            while size < blocks:
                size *= 2
            return size * d
        return self.spec.d

    def execute_wide(self, b: jnp.ndarray,
                     *, block_d: Optional[int] = None) -> jnp.ndarray:
        """Column-shard one wide ``B`` through the plan.

        A ``[n, D]`` operand with ``D`` much larger than the planned width
        is split into column blocks of ``block_d`` (default: the planned
        ``spec.d``), each block executed through the bound kernel, and the
        results concatenated — the sharded-serving shape where one model's
        activation matrix is wider than the per-request batch the plan was
        tuned for.

        Args:
            b: dense right-hand side, ``[n, D]``.
            block_d: column block width; defaults to ``spec.d``.

        Returns:
            ``C`` as a dense ``[n, D]`` array.  One ``repro.execute`` span
            times the host side of all the blocks' launches.
        """
        self._check(b)
        block_d = self.spec.d if block_d is None else int(block_d)
        if block_d < 1:
            raise ValueError(f"block_d must be >= 1, got {block_d}")
        total = b.shape[1]
        if total == 0:
            return jnp.zeros((self.n, 0), dtype=b.dtype)
        widths = [min(block_d, total - lo) for lo in range(0, total, block_d)]
        with obs.span("repro.execute", **self._span_attrs(*widths)):
            outs = []
            for lo in range(0, total, block_d):
                outs.append(self._run(b[:, lo:lo + block_d]))
                self.executed += 1
            out = jnp.concatenate(outs, axis=1)
        self._audit_reuse()
        return out

    def reset_stats(self) -> None:
        """Zero the execution counter (e.g. after warm-up calls, so
        :meth:`stats` reflects served requests only)."""
        self.executed = 0

    def stats(self) -> dict:
        """Amortization audit: planned horizon vs realized executions.

        Returns:
            Dict with ``chosen``, ``regime``, ``backend``, ``precision``
            (the storage-dtype token replays run at), ``planned_reuse``,
            ``executed``, ``reuse_utilization`` (executed / planned —
            below 1.0 means the conversion cost was amortized over fewer
            calls than the model assumed), and ``replan_suggested`` (the
            horizon drifted past ``REUSE_DRIFT_FACTOR``; see
            :meth:`replan`).
        """
        return {
            "chosen": self.dispatch.chosen,
            "regime": self.dispatch.regime,
            "backend": self.dispatch.backend,
            "precision": self.dispatch.precision,
            "planned_reuse": self.spec.reuse,
            "executed": self.executed,
            "reuse_utilization": self.executed / self.spec.reuse,
            "replan_suggested": self._reuse_warned,
        }


def plan(m: COOMatrix, b_spec: Union[int, BSpec, jnp.ndarray], *,
         strategy: str = "auto", reuse: Optional[int] = None,
         precision=None, tolerance: Optional[float] = None,
         mesh=None, b_strategy: str = "auto",
         dispatcher: Optional[_dispatch.Dispatcher] = None) -> StreamPlan:
    """Plan once for a stream of right-hand sides; the serving entry point.

    Args:
        m: square sparse pattern (``repro.core.patterns.COOMatrix``), [n, n].
        b_spec: the stream description — an ``int`` width, a
            :class:`BSpec`, or an example ``[n, d]`` batch.
        strategy: ``"auto"`` or a format name to force.
        reuse: shorthand override for ``BSpec.reuse`` (expected number of
            executions).
        precision: shorthand override for ``BSpec.precision`` — force the
            plan onto one storage precision (``"bf16"``, ``"bf16i32"``, a
            :class:`~repro.core.precision.Precision`).
        tolerance: shorthand override for ``BSpec.tolerance`` — the
            accuracy budget that lets the dispatcher consider
            reduced-precision candidates on its own.
        mesh: optional device mesh (e.g. from ``repro.launch.mesh``).
            When given, returns a :class:`repro.sparse.shard.ShardedPlan`
            that partitions the matrix across the mesh and executes under
            ``shard_map``.
        b_strategy: sharded-tier B-distribution strategy (``"auto"`` or
            one of ``repro.sparse.shard.B_STRATEGIES``); only meaningful
            with ``mesh``.
        dispatcher: dispatcher to plan on; defaults to the module-level one
            shared with ``sparse.spmm``.

    Returns:
        A bound :class:`StreamPlan` (or ``ShardedPlan`` when ``mesh`` is
        given); call ``execute`` / ``execute_many`` / ``execute_wide``.
    """
    spec = as_b_spec(b_spec, reuse=reuse)
    if precision is not None or tolerance is not None:
        spec = dataclasses.replace(
            spec,
            precision=spec.precision if precision is None else precision,
            tolerance=spec.tolerance if tolerance is None else tolerance)
    disp = dispatcher or _dispatch.default_dispatcher()
    if mesh is not None:
        from repro.sparse.shard import ShardedPlan
        return ShardedPlan(disp, m, spec, mesh, strategy=strategy,
                           b_strategy=b_strategy)
    if b_strategy != "auto":
        raise ValueError("b_strategy requires a mesh (sharded tier)")
    return StreamPlan(disp, m, spec, strategy=strategy)

"""Sharded SpMM execution tier: mesh-partitioned plans under ``shard_map``.

The paper's thesis — attainable SpMM is set by sparsity structure, not one
roofline — extends to the multi-device regime: once the sparse operand is
partitioned across a mesh, the binding resource per shard can flip between
DRAM bandwidth, the format compute ceiling, and interconnect/collective
traffic.  This module is that regime's dispatch layer:

    mesh = make_shard_mesh(8)                    # repro.launch.mesh
    plan = sparse.plan(m, BSpec(d=64), mesh=mesh)   # -> ShardedPlan
    c = plan.execute(b)                          # shard_map replay
    print(plan.summary())                        # format + B-strategy audit

Partitioning follows structure, exactly like format choice does:

  * CSR / ELL / BCSR take **contiguous row-block shards**, balanced by
    nnz (not rows) via the prefix-sum splitter
    ``repro.sparse.formats.nnz_balanced_splits`` (BCSR cuts align to the
    block edge t); the reduce-scatter strategy instead partitions by
    **columns** so each shard owns a slice of B and bins its partial
    products by destination row block before reducing — the
    propagation-blocking formulation (arXiv 2002.11302).
  * DIA takes **diagonal-band shards**: contiguous runs of diagonals,
    balanced by per-diagonal nnz.  Every band shard produces a
    full-height partial C, reduced across the mesh.

The dispatcher itself picks the B-distribution strategy per plan —
``replicate`` (broadcast B, row-sharded A and C), ``all_gather``
(row-sharded B gathered in-kernel; composes with an already-sharded
serving pipeline), or ``reduce_scatter`` (column-sharded A, local B
slice, partial C reduce-scattered) — scoring each like a format
candidate: per-shard sparsity-aware AI on the critical (most loaded)
shard plus the strategy's collective cost
(``repro.core.roofline.collective_time`` over
``HardwareSpec.collective_bandwidth``), with skip/selection reasons
recorded in :meth:`ShardedPlan.summary`.

Execution runs under ``jax.shard_map`` over a 1-D flattening
of the caller's mesh, reusing the registry's jax-backend
``KernelSpec.run`` unchanged inside each shard for CSR/ELL/BCSR (padded
per-shard layouts are stacked on a leading device axis).  DIA is the one
exception: its registered kernel unrolls *static* per-matrix offsets, so
heterogeneous band shards use a traced-offset gather body instead.  The
CPU-verifiable path is 8 virtual host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import sparsity_models as sm
from repro.core.patterns import COOMatrix
from repro.core.precision import Precision, as_precision
from repro.core.roofline import ShardRoofline, collective_time
from repro.sparse import formats as fmt
from repro.sparse import stream as _stream

#: The B-distribution strategies the sharded dispatcher scores.
B_STRATEGIES: Tuple[str, ...] = ("replicate", "all_gather", "reduce_scatter")

#: Mesh axis name the sharded tier executes over (the caller's mesh is
#: flattened to one dimension of this name).
SHARD_AXIS = "shard"


@dataclasses.dataclass(frozen=True)
class ShardStrategyEval:
    """One B-distribution strategy's audit record inside a ShardedPlan.

    Mirrors ``repro.sparse.dispatch.CandidateEval`` one level up: the
    dispatcher scores every strategy, keeps the losers' predictions, and
    records a skip reason for the ineligible ones.
    """

    strategy: str                     # one of B_STRATEGIES
    partition: str                    # "row-block" | "column-block" | "diagonal-band"
    eligible: bool
    skip_reason: Optional[str]        # None when eligible
    roofline: Optional[ShardRoofline]  # per-shard AI + collective cost

    @property
    def predicted_gflops(self) -> Optional[float]:
        """Whole-matrix useful GFLOP/s the cost model predicts."""
        if self.roofline is None:
            return None
        return self.roofline.predicted_flops_per_s / 1e9


def _pick_strategy(evals, requested: str) -> str:
    """Resolve the winning strategy ("auto" = best predicted GFLOP/s)."""
    if requested != "auto":
        ev = next(e for e in evals if e.strategy == requested)
        if not ev.eligible:
            raise ValueError(
                f"b_strategy {requested!r} is ineligible for this plan: "
                f"{ev.skip_reason}")
        return requested
    viable = [e for e in evals if e.eligible and e.roofline is not None]
    return max(viable, key=lambda e: e.roofline.predicted_flops_per_s
               ).strategy


class ShardedPlan(_stream.StreamPlan):
    """A StreamPlan whose replay runs SPMD over a device mesh.

    Construction extends the single-device pipeline with three sharded
    phases: partition the chosen format's operand per structure, score
    the three B-distribution strategies with the communication-aware
    roofline, and compile one ``shard_map`` closure for the winner.  The
    inherited ``execute`` / ``execute_many`` / ``execute_wide`` then
    replay that closure — the serving path composes unchanged.

    Attributes:
        mesh: the 1-D execution mesh (caller's mesh flattened).
        num_shards: mesh size D.
        b_strategy: the chosen B-distribution strategy.
        partition: the chosen strategy's partitioning scheme.
        strategy_evals: per-strategy audit records (predictions + skip
            reasons), rendered by :meth:`summary`.
        shard_nnz: nonzeros per shard under the chosen partition.
    """

    def __init__(self, dispatcher, m: COOMatrix, spec, mesh, *,
                 strategy: str = "auto", b_strategy: str = "auto"):
        """Plan, score strategies, and bind the shard_map executor.

        Args:
            dispatcher: the ``repro.sparse.dispatch.Dispatcher`` owning
                caches and the hardware model.
            m: square sparse pattern, ``[n, n]``.
            spec: the stream description (``BSpec``).
            mesh: any ``jax`` mesh (e.g. from ``repro.launch.mesh``);
                its devices are flattened to one ``"shard"`` axis.
            strategy: ``"auto"`` or a forced *format* name.
            b_strategy: ``"auto"`` or a forced B-distribution strategy
                from ``B_STRATEGIES``.

        Raises:
            ValueError: on an unknown or ineligible ``b_strategy``.
        """
        if b_strategy not in ("auto",) + B_STRATEGIES:
            raise ValueError(f"unknown b_strategy {b_strategy!r}; choose "
                             f"from {('auto',) + B_STRATEGIES}")
        devices = np.asarray(mesh.devices).reshape(-1)
        self.mesh = Mesh(devices, (SHARD_AXIS,))
        self.num_shards = int(devices.size)
        self._b_strategy_req = b_strategy
        super().__init__(dispatcher, m, spec, strategy=strategy)

    def _exec_precision(self) -> Precision:
        """The precision the per-shard kernels actually pack and run at.

        Values follow the plan's precision; indices are pinned to int32
        because the sharded tier executes jax-backend kernels inside each
        shard (XLA gathers take int32), so a ``bf16i16`` plan executes as
        ``bf16i32`` here — same value traffic, wider indices.
        """
        prec = as_precision(self.dispatch.precision)
        if prec.index_dtype != "int32":
            prec = Precision(prec.value_dtype, "int32")
        return prec

    def _launch_counters(self, d: int) -> dict:
        """No counters: the shards run jax-backend kernels, whatever the
        plan's backend, so the plan's kernel counters do not describe
        them."""
        return {}

    # ------------------------------------------------------------- #
    # Planning: strategy scoring
    # ------------------------------------------------------------- #

    def _bind(self) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Score B-strategies and compile the winner's shard_map closure."""
        disp, m, plan = self._dispatcher, self._m, self.dispatch
        fmt_name, d, n, nnz = plan.chosen, plan.d, m.n, max(m.nnz, 1)
        D = self.num_shards
        hw = disp._resolve_hardware()
        prec = self._exec_precision()
        sv = prec.sizeof_val
        cand = plan.candidate(fmt_name)
        ceiling = disp._ceiling(fmt_name, hw, plan.backend,
                                plan.precision).attainable(
            hw.peak_flops, cand.useful_fraction or 1.0, d)
        flops = sm.flops_spmm(nnz, d)
        S = float(n * d * sv)                 # one full B or C buffer

        if fmt_name == "dia":
            dia = disp.convert(m, "dia", precision=prec)
            diag_nnz = np.count_nonzero(np.asarray(dia.data), axis=1)
            band_bounds = fmt.nnz_balanced_splits(diag_nnz, D)
            full_tb = sm.TrafficBreakdown(
                flops=flops, bytes_a=dia.num_offsets * n * sv,
                bytes_b=S, bytes_c=S, model="diagonal")
            partitions = {
                "replicate": ("diagonal-band", band_bounds, diag_nnz),
                "reduce_scatter": ("diagonal-band", band_bounds, diag_nnz),
            }
            comm = {"replicate": (S + 2 * (D - 1) / D * S, 2),
                    "reduce_scatter": (S + 2 * (D - 1) / D * S, 3)}
            skip = {"all_gather": (
                "diagonal-band shards read essentially every row of B; "
                "all-gathering a row shard reconstructs the replicate "
                "broadcast with extra latency")}
        else:
            align = disp.bcsr_block if fmt_name == "bcsr" else 1
            row_nnz = np.bincount(m.rows, minlength=n)
            col_nnz = np.bincount(m.cols, minlength=n)
            row_bounds = fmt.nnz_balanced_splits(row_nnz, D, align=align)
            col_bounds = fmt.nnz_balanced_splits(col_nnz, D, align=align)
            bytes_c = S
            total_bytes = flops / cand.ai if cand.ai else bytes_c
            full_tb = sm.TrafficBreakdown(
                flops=flops, bytes_a=max(total_bytes - bytes_c, 0.0),
                bytes_b=0.0, bytes_c=bytes_c, model=plan.regime)
            partitions = {
                "replicate": ("row-block", row_bounds, row_nnz),
                "all_gather": ("row-block", row_bounds, row_nnz),
                "reduce_scatter": ("column-block", col_bounds, col_nnz),
            }
            comm = {"replicate": (S + (D - 1) / D * S, 2),
                    "all_gather": (2 * (D - 1) / D * S, 2),
                    "reduce_scatter": (S / D + 2 * (D - 1) / D * S, 3)}
            skip = {}

        evals = []
        for name in B_STRATEGIES:
            if name in skip:
                evals.append(ShardStrategyEval(
                    strategy=name, partition="-", eligible=False,
                    skip_reason=skip[name], roofline=None))
                continue
            part, bounds, weights = partitions[name]
            shard_nnz = np.add.reduceat(
                weights, bounds[:-1])[:D] if weights.size else np.zeros(D)
            # Guard reduceat's empty-slice quirk (repeated bounds repeat
            # the next value instead of 0).
            shard_nnz = np.where(np.diff(bounds) > 0, shard_nnz, 0)
            worst = ai_crit = fl_crit = 0.0
            for i in range(D):
                frac = shard_nnz[i] / nnz
                if frac <= 0:
                    continue
                rows_frac = ((bounds[i + 1] - bounds[i]) / n
                             if part == "row-block" else 1.0)
                tb_i = sm.shard_traffic(
                    full_tb, nnz_fraction=frac, rows_fraction=rows_frac,
                    bytes_b=S if part == "diagonal-band" else None)
                pred_i = min(hw.hbm_bandwidth * tb_i.ai, ceiling)
                t_i = tb_i.flops / pred_i if pred_i > 0 else 0.0
                if t_i >= worst:
                    worst, ai_crit, fl_crit = t_i, tb_i.ai, tb_i.flops
            bytes_wire, n_coll = comm[name]
            roof = ShardRoofline(
                strategy=name, devices=D, shard_ai=ai_crit,
                critical_flops=fl_crit, total_flops=flops,
                compute_s=worst,
                collective_s=collective_time(bytes_wire, hw, D,
                                             collectives=n_coll),
                collective_bytes=bytes_wire if D > 1 else 0.0)
            evals.append(ShardStrategyEval(
                strategy=name, partition=part, eligible=True,
                skip_reason=None, roofline=roof))

        self.strategy_evals = tuple(evals)
        self.b_strategy = _pick_strategy(evals, self._b_strategy_req)
        chosen_ev = next(e for e in evals if e.strategy == self.b_strategy)
        self.partition = chosen_ev.partition
        part, bounds, weights = (partitions[self.b_strategy]
                                 if self.b_strategy in partitions else
                                 partitions["replicate"])
        self.shard_bounds = np.asarray(bounds)
        counts = np.add.reduceat(weights, bounds[:-1])[:D] \
            if weights.size else np.zeros(D, dtype=np.int64)
        self.shard_nnz = np.where(np.diff(bounds) > 0, counts, 0)
        return self._build_executor(fmt_name, bounds)

    # ------------------------------------------------------------- #
    # Execution: shard_map closures
    # ------------------------------------------------------------- #

    def _kernel_ctx(self):
        """KernelContext for the per-shard jax-backend KernelSpec.run."""
        from repro.kernels import registry
        disp, plan = self._dispatcher, self.dispatch
        prec = self._exec_precision()

        def _convert(mm, format, _prec=prec):
            return disp.convert(mm, format, precision=_prec)

        return registry.KernelContext(
            hardware=disp._resolve_hardware(),
            bcsr_block=disp.bcsr_block,
            max_dia_offsets=disp.max_dia_offsets,
            plan_d=plan.d, precision=prec, convert=_convert)

    def _build_executor(self, fmt_name: str, bounds: np.ndarray):
        """Pack per-shard layouts and compile the strategy's closure.

        The sharded tier always executes the *jax*-backend KernelSpec
        inside each shard: its layouts are plain stacked arrays, so D
        padded shard layouts concatenate on a leading device axis and
        flow through ``shard_map`` untouched.  (The pallas row-tile
        packings are host-side ragged structures; sharding them is a
        ROADMAP follow-up.)
        """
        if fmt_name == "dia":
            return self._bind_dia(bounds)
        if fmt_name in ("binned", "rowsplit", "ell_coo"):
            # CSR-equivalent gather layouts (the scale-free tier): their
            # host-side orderings are whole-matrix properties that do not
            # survive row/column slicing, so per-shard execution reuses
            # the CSR packing and the jax CSR kernel inside each shard.
            fmt_name = "csr"
        if self.b_strategy == "reduce_scatter":
            return self._bind_cols(fmt_name, bounds)
        return self._bind_rows(fmt_name, bounds)

    def _bind_rows(self, fmt_name: str, bounds: np.ndarray):
        """Row-block execution: replicate-B or all-gather-B."""
        from repro.kernels import registry
        disp, m = self._dispatcher, self._m
        mesh, D, n = self.mesh, self.num_shards, self._m.n
        spec_k = registry.get(fmt_name, "jax")
        ctx = self._kernel_ctx()
        prec = self._exec_precision()
        rows_per = np.diff(bounds)
        R = int(max(rows_per.max(), 1))

        if fmt_name == "csr":
            csr = disp.convert(m, "csr", precision=prec)
            indptr = np.asarray(csr.indptr)
            data, idx, rid = (np.asarray(csr.data), np.asarray(csr.indices),
                              np.asarray(csr.row_ids))
            nnz_per = indptr[bounds[1:]] - indptr[bounds[:-1]]
            NNZ = int(max(nnz_per.max(), 1))
            d_s = np.zeros((D, NNZ), data.dtype)
            i_s = np.zeros((D, NNZ), np.int32)
            r_s = np.zeros((D, NNZ), np.int32)
            for i in range(D):
                lo, hi = indptr[bounds[i]], indptr[bounds[i + 1]]
                k = hi - lo
                d_s[i, :k] = data[lo:hi]
                i_s[i, :k] = idx[lo:hi]
                r_s[i, :k] = rid[lo:hi] - bounds[i]
            arrs = tuple(jnp.asarray(a) for a in (d_s, i_s, r_s))

            def local(arrs, b_full):
                a_loc = fmt.CSRMatrix(
                    data=arrs[0][0], indices=arrs[1][0],
                    indptr=jnp.zeros(R + 1, jnp.int32),
                    row_ids=arrs[2][0], n=R)
                return spec_k.run(a_loc, b_full, ctx)

        elif fmt_name == "ell":
            ell = disp.convert(m, "ell", precision=prec)
            data, idx = np.asarray(ell.data), np.asarray(ell.indices)
            k = data.shape[1]
            d_s = np.zeros((D, R, k), data.dtype)
            i_s = np.zeros((D, R, k), np.int32)
            for i in range(D):
                r = rows_per[i]
                d_s[i, :r] = data[bounds[i]:bounds[i + 1]]
                i_s[i, :r] = idx[bounds[i]:bounds[i + 1]]
            arrs = (jnp.asarray(d_s), jnp.asarray(i_s))

            def local(arrs, b_full):
                a_loc = fmt.ELLMatrix(data=arrs[0][0], indices=arrs[1][0],
                                      n=R)
                return spec_k.run(a_loc, b_full, ctx)

        else:                               # bcsr
            bcsr = disp.convert(m, "bcsr", precision=prec)
            t = bcsr.t
            bptr = np.asarray(bcsr.block_ptr)
            blocks = np.asarray(bcsr.blocks)
            brows, bcols = (np.asarray(bcsr.block_rows),
                            np.asarray(bcsr.block_cols))
            sb = bounds // t
            nblk = bptr[sb[1:]] - bptr[sb[:-1]]
            NB = int(max(nblk.max(), 1))
            bl_s = np.zeros((D, NB, t, t), blocks.dtype)
            br_s = np.zeros((D, NB), np.int32)
            bc_s = np.zeros((D, NB), np.int32)
            for i in range(D):
                lo, hi = bptr[sb[i]], bptr[sb[i + 1]]
                kk = hi - lo
                bl_s[i, :kk] = blocks[lo:hi]
                br_s[i, :kk] = brows[lo:hi] - sb[i]
                bc_s[i, :kk] = bcols[lo:hi]
            arrs = tuple(jnp.asarray(a) for a in (bl_s, br_s, bc_s))
            nnz_static = bcsr.nnz

            def local(arrs, b_full):
                # n stays global: bcsr_spmm tiles B by a.nb = n // t, and
                # B here is the full [n, d] operand.  Localized block
                # rows land the shard's output in rows [0, R).
                a_loc = fmt.BCSRMatrix(
                    blocks=arrs[0][0], block_rows=arrs[1][0],
                    block_cols=arrs[2][0],
                    block_ptr=jnp.zeros(n // t + 1, jnp.int32),
                    n=n, t=t, nnz=nnz_static)
                return spec_k.run(a_loc, b_full, ctx)[:R]

        gidx = jnp.asarray(np.concatenate(
            [i * R + np.arange(rows_per[i]) for i in range(D)]
        ).astype(np.int32))

        if self.b_strategy == "replicate":
            body = jax.shard_map(
                lambda a, b: local(a, b)[None], mesh=mesh,
                in_specs=(P(SHARD_AXIS), P()), out_specs=P(SHARD_AXIS),
                check_vma=False)

            def run_impl(arrs, b):
                return body(arrs, b).reshape(D * R, -1)[gidx]
        else:                               # all_gather
            Rb = -(-n // D)
            body = jax.shard_map(
                lambda a, b: local(
                    a, jax.lax.all_gather(b, SHARD_AXIS, tiled=True)[:n]
                )[None],
                mesh=mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                out_specs=P(SHARD_AXIS), check_vma=False)

            def run_impl(arrs, b):
                b_pad = jnp.pad(b, ((0, D * Rb - n), (0, 0)))
                return body(arrs, b_pad).reshape(D * R, -1)[gidx]

        jitted = jax.jit(run_impl)
        return lambda b: jitted(arrs, b)

    def _bind_cols(self, fmt_name: str, bounds: np.ndarray):
        """Column-block execution: reduce-scatter-output.

        Each shard owns the nonzeros whose *columns* fall in its slice,
        consumes only its rows of B, and produces a full-height partial
        C; ``psum_scatter`` then bins the partials by destination row
        block and reduces them there — propagation blocking as a
        collective.
        """
        from repro.kernels import registry
        disp, m = self._dispatcher, self._m
        mesh, D, n = self.mesh, self.num_shards, self._m.n
        spec_k = registry.get(fmt_name, "jax")
        ctx = self._kernel_ctx()
        prec = self._exec_precision()
        vdt = prec.value_jnp                # ml_dtypes type doubles as np
        cols_per = np.diff(bounds)
        Rc = int(max(cols_per.max(), 1))
        Rout = -(-n // D)

        if fmt_name == "csr":
            NNZ = 1
            packs = []
            for i in range(D):
                sel = (m.cols >= bounds[i]) & (m.cols < bounds[i + 1])
                packs.append((m.vals[sel].astype(vdt),
                              (m.cols[sel] - bounds[i]).astype(np.int32),
                              m.rows[sel].astype(np.int32)))
                NNZ = max(NNZ, int(sel.sum()))
            d_s = np.zeros((D, NNZ), vdt)
            i_s = np.zeros((D, NNZ), np.int32)
            r_s = np.zeros((D, NNZ), np.int32)
            for i, (v, c, r) in enumerate(packs):
                d_s[i, :v.size], i_s[i, :v.size], r_s[i, :v.size] = v, c, r
            arrs = tuple(jnp.asarray(a) for a in (d_s, i_s, r_s))
            b_rows = Rc

            def local(arrs, b_loc):
                a_loc = fmt.CSRMatrix(
                    data=arrs[0][0], indices=arrs[1][0],
                    indptr=jnp.zeros(n + 1, jnp.int32),
                    row_ids=arrs[2][0], n=n)
                return spec_k.run(a_loc, b_loc, ctx)

        elif fmt_name == "ell":
            locals_ell = []
            K = 1
            for i in range(D):
                sel = (m.cols >= bounds[i]) & (m.cols < bounds[i + 1])
                lm = COOMatrix(n=n, rows=m.rows[sel],
                               cols=(m.cols[sel] - bounds[i]).astype(
                                   np.int32),
                               vals=m.vals[sel], pattern=m.pattern)
                e = fmt.coo_to_ell(lm, dtype=vdt)
                locals_ell.append(e)
                K = max(K, e.k)
            d_s = np.zeros((D, n, K), vdt)
            i_s = np.zeros((D, n, K), np.int32)
            for i, e in enumerate(locals_ell):
                d_s[i, :, :e.k] = np.asarray(e.data)
                i_s[i, :, :e.k] = np.asarray(e.indices)
            arrs = (jnp.asarray(d_s), jnp.asarray(i_s))
            b_rows = Rc

            def local(arrs, b_loc):
                a_loc = fmt.ELLMatrix(data=arrs[0][0], indices=arrs[1][0],
                                      n=n)
                return spec_k.run(a_loc, b_loc, ctx)

        else:                               # bcsr
            bcsr = disp.convert(m, "bcsr", precision=prec)
            t = bcsr.t
            blocks = np.asarray(bcsr.blocks)
            brows, bcols = (np.asarray(bcsr.block_rows),
                            np.asarray(bcsr.block_cols))
            sb = bounds // t
            NB = 1
            packs = []
            for i in range(D):
                sel = (bcols >= sb[i]) & (bcols < sb[i + 1])
                packs.append((blocks[sel], brows[sel], bcols[sel] - sb[i]))
                NB = max(NB, int(sel.sum()))
            bl_s = np.zeros((D, NB, t, t), blocks.dtype)
            br_s = np.zeros((D, NB), np.int32)
            bc_s = np.zeros((D, NB), np.int32)
            for i, (bl, br, bc) in enumerate(packs):
                kk = bl.shape[0]
                bl_s[i, :kk], br_s[i, :kk], bc_s[i, :kk] = bl, br, bc
            arrs = tuple(jnp.asarray(a) for a in (bl_s, br_s, bc_s))
            nnz_static = bcsr.nnz
            # bcsr_spmm tiles B by n // t, so the local B slice is padded
            # to full height; the zero tail multiplies nothing.
            b_rows = n

            def local(arrs, b_loc):
                a_loc = fmt.BCSRMatrix(
                    blocks=arrs[0][0], block_rows=arrs[1][0],
                    block_cols=arrs[2][0],
                    block_ptr=jnp.zeros(n // t + 1, jnp.int32),
                    n=n, t=t, nnz=nnz_static)
                return spec_k.run(a_loc, b_loc, ctx)

        def body_fn(arrs, b_chunks):
            partial = local(arrs, b_chunks[0])          # [n, d]
            partial = jnp.pad(partial, ((0, D * Rout - n), (0, 0)))
            return jax.lax.psum_scatter(partial, SHARD_AXIS,
                                        scatter_dimension=0, tiled=True)

        body = jax.shard_map(body_fn, mesh=mesh,
                         in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                         out_specs=P(SHARD_AXIS), check_vma=False)
        b_lo = [int(x) for x in bounds[:-1]]
        b_hi = [int(x) for x in bounds[1:]]

        def run_impl(arrs, b):
            chunks = jnp.stack([
                jnp.pad(b[lo:hi], ((0, b_rows - (hi - lo)), (0, 0)))
                for lo, hi in zip(b_lo, b_hi)])
            return body(arrs, chunks)[:n]

        jitted = jax.jit(run_impl)
        return lambda b: jitted(arrs, b)

    def _bind_dia(self, bounds: np.ndarray):
        """Diagonal-band execution with traced per-shard offsets.

        The registered DIA kernel unrolls *static* offsets, which cannot
        differ across SPMD shards; the band body instead gathers
        ``B[r + offset]`` with offsets as data (padded diagonals carry
        zero values, so they contribute nothing).
        """
        disp, m = self._dispatcher, self._m
        mesh, D, n = self.mesh, self.num_shards, self._m.n
        dia = disp.convert(m, "dia", precision=self._exec_precision())
        offs = np.asarray(dia.offsets, dtype=np.int32)
        data = np.asarray(dia.data)
        K = int(max(np.diff(bounds).max(), 1))
        off_s = np.zeros((D, K), np.int32)
        dat_s = np.zeros((D, K, n), data.dtype)
        for i in range(D):
            k = bounds[i + 1] - bounds[i]
            off_s[i, :k] = offs[bounds[i]:bounds[i + 1]]
            dat_s[i, :k] = data[bounds[i]:bounds[i + 1]]
        arrs = (jnp.asarray(off_s), jnp.asarray(dat_s))
        r = jnp.arange(n)

        def partial_fn(arrs, b_full):
            offsets, dat = arrs[0][0], arrs[1][0]
            idx = r[None, :] + offsets[:, None]          # [K, n]
            valid = (idx >= 0) & (idx < n)
            g = b_full[jnp.clip(idx, 0, n - 1)]          # [K, n, d]
            # Products round at the storage dtype; the band reduction
            # accumulates in fp32 per the precision contract.
            prod = (dat[..., None] * g).astype(jnp.float32)
            contrib = jnp.where(valid[..., None], prod, 0.0)
            return contrib.sum(0).astype(b_full.dtype)   # [n, d]

        if self.b_strategy == "replicate":
            body = jax.shard_map(
                lambda a, b: jax.lax.psum(partial_fn(a, b), SHARD_AXIS),
                mesh=mesh, in_specs=(P(SHARD_AXIS), P()), out_specs=P(),
                check_vma=False)

            def run_impl(arrs, b):
                return body(arrs, b)
        else:                               # reduce_scatter
            Rout = -(-n // D)

            def body_fn(arrs, b):
                partial = jnp.pad(partial_fn(arrs, b),
                                  ((0, D * Rout - n), (0, 0)))
                return jax.lax.psum_scatter(partial, SHARD_AXIS,
                                            scatter_dimension=0,
                                            tiled=True)

            body = jax.shard_map(body_fn, mesh=mesh,
                             in_specs=(P(SHARD_AXIS), P()),
                             out_specs=P(SHARD_AXIS), check_vma=False)

            def run_impl(arrs, b):
                return body(arrs, b)[:n]

        jitted = jax.jit(run_impl)
        return lambda b: jitted(arrs, b)

    # ------------------------------------------------------------- #
    # Introspection
    # ------------------------------------------------------------- #

    def summary(self) -> str:
        """The format decision table plus the B-strategy audit."""
        single = self.dispatch.candidate(self.chosen).predicted_gflops
        nz = self.shard_nnz[self.shard_nnz > 0]
        imbalance = float(nz.max() / nz.mean()) if nz.size else 1.0
        lines = [self.dispatch.summary(),
                 f"ShardedPlan(devices={self.num_shards}, "
                 f"partition={self.partition}, "
                 f"nnz_imbalance={imbalance:.2f}) -> {self.b_strategy}"]
        for ev in self.strategy_evals:
            mark = "*" if ev.strategy == self.b_strategy else " "
            if ev.roofline is not None:
                r = ev.roofline
                perf = (f"comm={r.collective_bytes / 1e6:7.2f}MB"
                        f"  t_comp={r.compute_s * 1e6:9.1f}us"
                        f"  t_coll={r.collective_s * 1e6:9.1f}us"
                        f"  pred={r.predicted_flops_per_s / 1e9:7.2f} GF/s"
                        f" [{r.dominant}-bound]")
            else:
                perf = "(not modeled)"
            tail = "" if ev.eligible else f"  SKIP: {ev.skip_reason}"
            lines.append(f" {mark} {ev.strategy:14s} {perf}{tail}")
        best = next(e for e in self.strategy_evals
                    if e.strategy == self.b_strategy)
        if single and best.predicted_gflops is not None:
            lines.append(f"   model speedup vs single device: "
                         f"{best.predicted_gflops / single:.2f}x")
        return "\n".join(lines)

    def stats(self) -> dict:
        """StreamPlan stats extended with the sharded decision record."""
        out = super().stats()
        out.update({
            "devices": self.num_shards,
            "b_strategy": self.b_strategy,
            "partition": self.partition,
            # Per-shard kernels run the jax backend (int32 gathers), so a
            # bf16i16 plan executes shards at bf16i32.
            "shard_precision": self._exec_precision().token,
            "shard_nnz": [int(x) for x in self.shard_nnz],
        })
        return out

    def exec_hints(self) -> dict:
        """Engine staging metadata for sharded replay.

        The shard_map program is jitted, so dispatch is asynchronous like
        every other backend — but the operand is re-laid-out inside the
        traced closure (padded, chunked, or all-gathered per strategy),
        so donating the caller's staged buffer never helps: the hints pin
        ``donate_b`` False regardless of the per-shard kernel, and the
        jax-backend spec that actually runs inside each shard is the one
        consulted (``ShardedPlan`` executes jax kernels per shard even
        when the single-device plan resolved pallas).
        """
        from repro.kernels import registry
        spec = registry.get(self.dispatch.chosen, "jax")
        return {"async_dispatch": spec.async_dispatch, "donate_b": False,
                "devices": self.num_shards}

    def coalesce_block_d(self, total_cols: int) -> int:
        """Coalesced replay width for the engine: always the planned d.

        Every distinct operand width compiles a fresh shard_map program
        (the closure is jitted over concrete shapes), so an engine whose
        micro-batches vary in total width would recompile per batch.
        Pinning the block to ``spec.d`` keeps one compiled program serving
        every batch — the engine pads the batch to a multiple of it.
        """
        return self.spec.d

    def replan(self, observed_reuse: int) -> "ShardedPlan":
        """Re-plan at an observed horizon, keeping the mesh (see
        ``StreamPlan.replan``)."""
        if observed_reuse < 1:
            raise ValueError(
                f"observed_reuse must be >= 1, got {observed_reuse}")
        spec = dataclasses.replace(self.spec, reuse=observed_reuse)
        return ShardedPlan(self._dispatcher, self._m, spec, self.mesh,
                           strategy=self._strategy,
                           b_strategy=self._b_strategy_req)

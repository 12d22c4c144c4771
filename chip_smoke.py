"""Smoke run of the structure-aware SpMM system on a TPU chip.

Drives the main path — ``sparse.plan`` / ``StreamPlan.execute`` and the
``ServingEngine`` — through the entry points a user calls, on the paper's
four matrix structures at n = 2**20 rows with d = 128 fp32 right-hand
sides, and checks every result against a float64 reference built from
the COO arrays on a seeded sample of rows.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --scale 22      # the paper's row count
    python chip_smoke.py --four-chips    # only the sharded tier, 4 chips

On one chip every phase must run the Pallas backend compiled for the
chip.  There is no CPU fallback: without a TPU the script exits nonzero
before printing any result.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``; the timings on earlier
lines are smoke timings (host wall clock around ``block_until_ready``),
not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

#: ``repro.core.patterns.paper_suite`` entries, one per paper structure.
OPERATORS = ("er_{s}_10", "band_{s}_5", "fem_{s}_t32", "powerlaw_{s}_22")
FOUR_CHIP_OPERATORS = ("powerlaw_{s}_22", "band_{s}_5")
D = 128
REUSE = 64
SAMPLE_ROWS = 2048
AUTO_CALLS = 3
ENGINE_STREAMS = 4
ENGINE_PER_STREAM = 6
ENGINE_WIDTHS = (32, 64, 128)
#: Formats whose layouts are packed nonzero chunks (csr_to_row_tiles,
#: csr_to_slab_bins, pack_rowsplit_chunks).
CSR_FAMILY = ("csr", "ell", "ell_coo", "binned", "rowsplit")
MAX_LAYOUT_RATIO = 2.0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, misplaced or interpreted result."""


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Runs each phase, logs a failure with its traceback, and carries on
    so one run reports every failing phase."""

    def __init__(self):
        self.failed = []

    def run(self, label: str, fn, *args, **kwargs):
        try:
            return fn(*args, **kwargs)
        except Exception:              # a boundary that must keep running
            self.failed.append(label)
            log(f"FAILED {label}\n{traceback.format_exc()}")
            return None


class Timer:
    """Wall-clock seconds of a ``with`` block."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0


class Reference:
    """float64 reference of ``A @ B`` on a seeded sample of rows.

    Built from the COO arrays only, never from a packed layout.  The
    bound is the differential suite's accumulation contract:
    ``|C - ref| <= 4 * eps * (|A| @ |B|)`` elementwise.
    """

    def __init__(self, m, seed: int):
        rng = np.random.default_rng(seed)
        self.rows = np.sort(rng.choice(m.n, size=min(SAMPLE_ROWS, m.n),
                                       replace=False))
        sel = np.isin(m.rows, self.rows)
        self.slot = np.searchsorted(self.rows, m.rows[sel])
        cols = np.asarray(m.cols[sel], dtype=np.int64)
        self.vals = np.asarray(m.vals[sel], dtype=np.float64)
        self.needed = np.unique(cols)
        self.col_of = np.searchsorted(self.needed, cols)

    def scaled_error(self, c_rows: np.ndarray, b_needed: np.ndarray,
                     eps: float) -> float:
        """Largest ``|C - ref| / (eps * |A| @ |B|)`` on the sample.

        Raises:
            SmokeFailure: where any entry exceeds the 4-eps bound.
        """
        g = np.asarray(b_needed, dtype=np.float64)[self.col_of]
        shape = (self.rows.shape[0], g.shape[1])
        ref, mag = np.zeros(shape), np.zeros(shape)
        np.add.at(ref, self.slot, self.vals[:, None] * g)
        np.add.at(mag, self.slot, np.abs(self.vals[:, None] * g))
        err = np.abs(np.asarray(c_rows, dtype=np.float64) - ref)
        if np.any(err > 4.0 * eps * mag):
            worst = np.unravel_index(np.argmax(err - 4.0 * eps * mag),
                                     err.shape)
            raise SmokeFailure(
                f"row {self.rows[worst[0]]} col {worst[1]}: error "
                f"{err[worst]:.3e} exceeds 4*eps*(|A|@|B|) = "
                f"{4.0 * eps * mag[worst]:.3e}")
        live = mag > 0
        return float((err[live] / (eps * mag[live])).max()) if live.any() \
            else 0.0


def make_b(seed: int, n: int, d: int):
    """A seeded ``[n, d]`` fp32 right-hand side made on the device."""
    return jax.random.normal(jax.random.key(seed), (n, d), jnp.float32)


def check_on_tpu(out, count: int) -> list:
    """Ids of the devices holding ``out``; all TPUs, ``count`` of them."""
    devices = out.devices()
    if any(dev.platform != "tpu" for dev in devices):
        raise SmokeFailure(f"output on {devices}, not on the TPU")
    if len(devices) != count:
        raise SmokeFailure(f"output on {len(devices)} devices, expected "
                           f"{count}: {sorted(d.id for d in devices)}")
    return sorted(dev.id for dev in devices)


def check_compiled(run, b) -> None:
    """Fail unless ``run`` lowers to a Mosaic kernel (no interpret mode)."""
    from repro.kernels import registry
    if registry.default_interpret():
        raise SmokeFailure("Pallas would run in interpret mode here")
    if "tpu_custom_call" not in jax.jit(run).lower(b).as_text():
        raise SmokeFailure("the plan's kernel is not a compiled Pallas "
                           "(tpu_custom_call) kernel")


def layout_ratio(disp, m, plan) -> float:
    """Packed-layout bytes over the nonzeros' value + index bytes."""
    from repro.sparse import as_precision
    leaves = jax.tree_util.tree_leaves(disp.layout(m, plan))
    packed = sum(x.nbytes for x in leaves if hasattr(x, "nbytes"))
    prec = as_precision(plan.precision)
    return packed / max(m.nnz * (prec.sizeof_val + prec.sizeof_idx), 1)


def run_phase(label: str, name: str, m, ref: Reference, sp, disp, *,
              calls: int, seed: int, plan_s: float, pack_s: float) -> None:
    """Execute ``calls`` distinct right-hand sides through ``sp``, check
    each, and print one line for the phase."""
    from repro.sparse import as_precision
    plan = sp.dispatch
    if plan.backend != "pallas":
        raise SmokeFailure(f"{label} {name}: backend {plan.backend!r}, "
                           f"expected 'pallas'")
    prec = as_precision(plan.precision)
    worst, walls = 0.0, []
    for i in range(calls):
        b = make_b(seed + i, m.n, D)
        if i == 0:
            check_compiled(disp.executor(m, plan), b)
        with Timer() as t:
            c = jax.block_until_ready(sp.execute(b))
        walls.append(t.s)
        check_on_tpu(c, 1)
        c_rows = np.asarray(c[jnp.asarray(ref.rows)]).astype(np.float64)
        b_needed = np.asarray(b[jnp.asarray(ref.needed)])
        worst = max(worst, ref.scaled_error(c_rows, b_needed, prec.eps))
    ratio = layout_ratio(disp, m, plan)
    if plan.chosen in CSR_FAMILY and ratio >= MAX_LAYOUT_RATIO:
        raise SmokeFailure(f"{label} {name}: {plan.chosen} layout is "
                           f"{ratio:.2f}x its nonzero bytes")
    warm = min(walls[1:]) if len(walls) > 1 else float("nan")
    log(f"phase={label} op={name} format={plan.chosen} "
        f"precision={plan.precision} max_scaled_err={worst:.3f} "
        f"layout_bytes_ratio={ratio:.3f} plan_s={plan_s:.3f}"
        f" pack_s={pack_s:.3f} first_call_s={walls[0]:.3f}"
        f" warm_call_s={warm:.4f} (smoke timings)")


def auto_phase(name: str, m, ref: Reference, disp, *, seed: int):
    """Plan, pack and replay the dispatcher's own pick."""
    from repro import sparse
    with Timer() as t_plan:
        dplan = disp.plan(m, D, reuse=REUSE)
    with Timer() as t_pack:
        sp = sparse.plan(m, sparse.BSpec(d=D, reuse=REUSE), dispatcher=disp)
    log(dplan.summary())
    run_phase("auto", name, m, ref, sp, disp, calls=AUTO_CALLS, seed=seed,
              plan_s=t_plan.s, pack_s=t_pack.s)
    return dplan, sp


def forced_phase(name: str, m, ref: Reference, disp, fmt: str, *,
                 seed: int) -> None:
    """Plan, pack and replay one forced format."""
    from repro import sparse
    with Timer() as t_plan:
        disp.plan(m, D, reuse=REUSE, strategy=fmt)
    with Timer() as t_pack:
        sp = sparse.plan(m, sparse.BSpec(d=D, reuse=REUSE), strategy=fmt,
                         dispatcher=disp)
    run_phase(f"forced:{fmt}", name, m, ref, sp, disp, calls=2, seed=seed,
              plan_s=t_plan.s, pack_s=t_pack.s)


def single_chip(scale: int, disp, phases: Phases) -> None:
    """Auto + every eligible forced Pallas format, bf16, and the engine."""
    from repro.core.patterns import paper_suite
    suite = paper_suite(scale)
    engine_op = None
    for k, pattern in enumerate(OPERATORS):
        name = pattern.format(s=scale)
        with Timer() as t:
            m = suite[name]()
        log(f"operator={name} n={m.n} nnz={m.nnz} generate_s={t.s:.3f}")
        ref = Reference(m, seed=100 + k)
        out = phases.run(f"auto {name}", auto_phase, name, m, ref, disp,
                         seed=1000 * k)
        if out is None:
            continue
        dplan, sp = out
        for f in sorted({c.format for c in dplan.candidates if c.eligible
                         and c.format != dplan.chosen}):
            phases.run(f"forced:{f} {name}", forced_phase, name, m, ref,
                       disp, f, seed=1000 * k + 10)
        if name.startswith("powerlaw"):
            phases.run(f"bf16 {name}", bf16_phase, name, m, ref, disp,
                       seed=1000 * k + 20)
            engine_op = (name, m, ref, sp)
    if engine_op is None:
        phases.failed.append("engine (no powerlaw plan)")
    else:
        phases.run("engine", engine_phase, *engine_op)


def bf16_phase(name: str, m, ref: Reference, disp, *, seed: int) -> None:
    """Forced ``precision="bf16"``: bf16 values with int16 indices where
    the packed column extent allows, else the legality gate's reason and
    bf16 values with int32 indices."""
    from repro import sparse
    token = "bf16"
    try:
        disp.plan(m, D, reuse=REUSE, precision=token)
    except ValueError as e:
        gate = disp.plan(m, D, reuse=REUSE).precision_skips.get(
            ("csr", "bf16i16"))
        log(f"precision=bf16 (bf16i16) refused on {name}: {e} [csr: {gate}]")
        token = "bf16i32"
    with Timer() as t_plan:
        disp.plan(m, D, reuse=REUSE, precision=token)
    with Timer() as t_pack:
        sp = sparse.plan(m, sparse.BSpec(d=D, reuse=REUSE, precision=token),
                         dispatcher=disp)
    run_phase(f"precision:{token}", name, m, ref, sp, disp, calls=2,
              seed=seed, plan_s=t_plan.s, pack_s=t_pack.s)


def engine_phase(name: str, m, ref: Reference, sp) -> None:
    """Serve mixed-width requests over several streams through one
    registered plan; every ticket must come back within the bound."""
    from repro import sparse
    engine = sparse.ServingEngine(max_queue=64, policy="wait")
    engine.register(name, sp)
    engine.warmup(name)
    engine.start()
    tickets = []
    try:
        for i in range(ENGINE_STREAMS * ENGINE_PER_STREAM):
            d = ENGINE_WIDTHS[i % len(ENGINE_WIDTHS)]
            b = np.random.default_rng(5000 + i).standard_normal(
                (m.n, d), dtype=np.float32)
            tickets.append((engine.submit(name, b), b))
        worst = 0.0
        for ticket, b in tickets:
            c = ticket.result(timeout=600)
            worst = max(worst, ref.scaled_error(
                c[ref.rows], b[ref.needed], np.finfo(np.float32).eps))
    finally:
        engine.stop()
    stats = engine.stats()
    log(f"phase=engine op={name} format={sp.chosen} requests={len(tickets)}"
        f" streams={ENGINE_STREAMS} widths={ENGINE_WIDTHS} "
        f"served={stats['served']} batches={stats['batches']} "
        f"max_scaled_err={worst:.3f} p50_us={stats['p50_us']:.0f} "
        f"p99_us={stats['p99_us']:.0f} (smoke timings)")


def four_chips(scale: int, disp, phases: Phases) -> None:
    """The sharded tier over a 4-chip mesh, every eligible B-strategy,
    against the float64 reference and the single-chip result."""
    from repro.core.patterns import paper_suite
    from repro.launch.mesh import make_shard_mesh
    mesh = make_shard_mesh(4)
    suite = paper_suite(scale)
    for k, pattern in enumerate(FOUR_CHIP_OPERATORS):
        name = pattern.format(s=scale)
        phases.run(f"sharded {name}", sharded_phase, name, suite[name](),
                   mesh, disp, seed=k)


def sharded_phase(name: str, m, mesh, disp, *, seed: int) -> None:
    """Every eligible B-strategy of one operator on the mesh."""
    from repro import sparse
    eps = float(np.finfo(np.float32).eps)
    ref = Reference(m, seed=200 + seed)
    b = make_b(7000 + seed, m.n, D)
    rows = jnp.asarray(ref.rows)
    b_needed = np.asarray(b[jnp.asarray(ref.needed)])
    single = sparse.plan(m, sparse.BSpec(d=D, reuse=REUSE),
                         dispatcher=disp)
    c1 = np.asarray(jax.block_until_ready(single.execute(b))[rows],
                    dtype=np.float64)
    ref.scaled_error(c1, b_needed, eps)
    probe = sparse.plan(m, sparse.BSpec(d=D, reuse=REUSE), mesh=mesh,
                        dispatcher=disp)
    log(probe.summary())
    for ev in probe.strategy_evals:
        if not ev.eligible:
            continue
        sp = sparse.plan(m, sparse.BSpec(d=D, reuse=REUSE), mesh=mesh,
                         b_strategy=ev.strategy, dispatcher=disp)
        with Timer() as t_first:
            c = jax.block_until_ready(sp.execute(b))
        with Timer() as t_warm:
            c = jax.block_until_ready(sp.execute(b))
        ids = check_on_tpu(c, 4)
        c_rows = np.asarray(c[rows], dtype=np.float64)
        worst = ref.scaled_error(c_rows, b_needed, eps)
        # Both results are within 4 eps of the reference.
        gap = float(np.abs(c_rows - c1).max())
        log(f"phase=sharded op={name} format={sp.chosen} "
            f"b_strategy={ev.strategy} devices={ids} "
            f"max_scaled_err={worst:.3f} max_abs_gap_vs_single={gap:.3e}"
            f" first_call_s={t_first.s:.3f} warm_call_s={t_warm.s:.4f}"
            f" (smoke timings)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=20,
                    help="log2 of the matrix dimension (default 20)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded tier on a 4-chip mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import sparse
    from repro.launch.compile_cache import configure
    log(f"compile_cache={configure()}")
    log(f"device_kind={devices[0].device_kind} count={len(devices)}")
    # The analytic roofline on default ceilings: no calibration or
    # dispatch-tree files from outside the checkout.
    disp = sparse.Dispatcher(calibration=False, tree=False)
    phases = Phases()
    if args.four_chips:
        four_chips(args.scale, disp, phases)
    else:
        single_chip(args.scale, disp, phases)
    if phases.failed:
        print(f"chip_smoke: {len(phases.failed)} phase(s) failed: "
              f"{phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

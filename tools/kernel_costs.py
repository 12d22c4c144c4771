"""Measure what one step of each Pallas SpMM kernel costs on the chip.

    python tools/kernel_costs.py [--configs lj_powerlaw fem_audikw]
                                 [--formats bcsr ...]

For each configuration of the chip benchmark (its operator, values from
seed 1, d = 128, float32) the default dispatcher plans ``auto`` once; then
every Pallas format that plan finds eligible (or those of ``--formats``)
runs alone, forced, on one device-made B: one warm-up call, then the
median of ``--calls`` calls, each waited for.  Each line of output gives
the format, its call time, what the plan predicted for it, and the steps
its layout issues, over which the call time is divided:

* ``csr`` / ``ell`` / ``ell_coo`` (one kernel, one layout): packed slots,
  one B-row DMA each -> ``KernelCosts.row_dma_s``;
* ``bcsr``: stored blocks, after their bytes at HBM bandwidth ->
  ``block_step_s``;
* ``binned`` / ``rowsplit``: packed slots, one B-row load from VMEM each
  -> ``row_load_s``.

The lines are printed and written to ``chiprun_out/kernel_costs.json``.
Needs a TPU; exits 1 without one.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "chipbench"))
sys.path.insert(0, str(ROOT / "src"))

D = 128


def _steps(fmt: str, layout) -> int:
    if fmt == "bcsr":
        return int(layout["arrays"][0].shape[0])
    if fmt == "binned":
        return int(layout["arrays"][2][-1]) * 128
    if fmt == "rowsplit":
        return int(layout["arrays"][0].shape[0]) * 128
    return layout["chunks"] * 128


def _time(run, b, calls: int) -> float:
    import jax
    jax.block_until_ready(run(b))
    times = []
    for _ in range(calls):
        t = time.perf_counter()
        jax.block_until_ready(run(b))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


def measure(name: str, calls: int, formats=None) -> list:
    import jax
    import jax.numpy as jnp
    from yard import names, operator
    from repro import sparse
    from repro.core.hardware import device_hardware
    from repro.core.patterns import COOMatrix
    from repro.kernels import registry

    cfg = names.config(names.benchmark(), name)
    rows, cols = operator.structure(cfg)
    m = COOMatrix(n=cfg["n"], rows=rows, cols=cols,
                  vals=operator.values(rows.shape[0], 1),
                  pattern=cfg["generator"]["kind"])
    disp = sparse.Dispatcher(backend="pallas", calibration=False,
                             tree=False)
    t = time.perf_counter()
    plan = disp.plan(m, D, reuse=cfg["reuse"], precision=cfg["precision"])
    plan_s = time.perf_counter() - t
    print(plan.summary(), flush=True)
    hw = device_hardware()
    b = jax.random.normal(jax.random.key(0), (m.n, D), jnp.float32)
    flops = 2.0 * m.nnz * D
    out, layouts = [], {}
    for c in plan.candidates:
        if not c.eligible or c.precision != "f32i32" or (
                formats and c.format not in formats):
            continue
        spec = registry.get(c.format, "pallas")
        ctx = registry.KernelContext(plan_d=D)
        key = spec.layout_cache_key
        if key not in layouts:
            layouts.clear()                 # one layout on the device
            t = time.perf_counter()
            layouts[key] = jax.block_until_ready(spec.prepare(m, ctx))
            prepare_s = time.perf_counter() - t
        layout = layouts[key]
        call_s = _time(lambda x: spec.run(layout, x, ctx), b, calls)
        steps = _steps(c.format, layout)
        issue_s = call_s
        if c.format == "bcsr":
            issue_s -= layout["arrays"][0].nbytes / hw.hbm_bandwidth
        row = {"config": name, "format": c.format, "auto": plan.chosen,
               "n": m.n, "nnz": m.nnz, "call_s": call_s,
               "predicted_s": flops / (c.predicted_gflops * 1e9),
               "steps": steps, "step_s": issue_s / steps,
               "prepare_s": prepare_s, "plan_s": plan_s}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--configs", nargs="+",
                    default=["lj_powerlaw", "fem_audikw"])
    ap.add_argument("--formats", nargs="+",
                    help="time only these formats (default: every eligible)")
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    rows = [r for name in args.configs for r in measure(name, args.calls, args.formats)]
    dest = ROOT / "chiprun_out"
    dest.mkdir(exist_ok=True)
    (dest / "kernel_costs.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

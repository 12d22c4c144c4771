"""Runs one cell of the chip benchmark once.

    python3 chipbench/run.py --workload lj.replay --seed 7 --seconds 10 --trace 0

The cell, its configuration and traffic are read from ``BENCHMARK.json``
and the files they name (``chipbench/yard/names.py``).  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and
``checks`` last); the numbers compared for ``correct`` are also the last
lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 1
and prints no result.  ``--rehearse LOG2N`` runs the same path on the CPU
at ``2**LOG2N`` rows (Pallas in interpret mode); its result says ``cpu``
and is no measurement.  ``--control`` puts the bfloat16 control in the
program's place (see ``chipbench/yard/reference.py``); it must come out
not correct.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from yard import names, peaks  # noqa: E402
from yard.run import Run  # noqa: E402

OUT = HERE / "out"


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _finite(x):
    """JSON has no infinity: an infinite reading prints as 1e308."""
    return x if math.isfinite(x) else 1e308


class Hooks:
    """What a driver calls around its window: host spans on the profiler's
    clock, and the window itself (traced when ``--trace 1``)."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir

    def annotate(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if self.trace_dir is None:
            yield
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, metavar="LOG2N",
                    help="CPU rehearsal at 2**LOG2N rows; no measurement")
    ap.add_argument("--control", action="store_true",
                    help="judge the bfloat16 control instead of the program")
    ap.add_argument("--rate", type=float,
                    help="override the traffic's rate_per_s (rate sweeps)")
    args = ap.parse_args(argv)

    bench = names.benchmark()
    cell = names.cell(bench, args.workload)
    cfg = names.config(bench, cell["config"])
    traffic = names.traffic(cell["traffic"])
    if args.rate is not None:
        traffic = {**traffic, "rate_per_s": args.rate}
    if args.rehearse:
        cfg = {**cfg, "n": 2 ** args.rehearse}

    # No calibration or dispatch-tree file from outside the checkout
    # steers the dispatcher: its store points at an empty directory.
    calib = OUT / "calibration"
    shutil.rmtree(calib, ignore_errors=True)
    calib.mkdir(parents=True)
    os.environ["REPRO_CALIBRATION_DIR"] = str(calib)

    run = Run(cfg=cfg, traffic=traffic, seed=args.seed,
              seconds=args.seconds, peak={}, t_start=T_START,
              control=args.control)
    with run.part("import"):
        import jax
        from repro.launch import compile_cache
    with run.part("device"):
        devices = jax.devices()
    if args.rehearse:
        if devices[0].platform != "cpu":
            log(f"--rehearse is for the CPU; JAX found "
                f"{devices[0].platform!r}")
            return 1
        run.peak = peaks.REHEARSAL
        # The chip's backend, run by the Pallas interpreter.
        from repro import sparse
        sparse.default_dispatcher().backend = "pallas"
    else:
        if devices[0].platform != "tpu":
            log(f"needs a TPU; JAX found {devices[0].platform!r}")
            return 1
        if len(devices) < cell["chips"]:
            log(f"needs {cell['chips']} chips, found {len(devices)}")
            return 1
        run.peak = peaks.peak(devices[0].device_kind)
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    trace_dir = None
    if args.trace:
        trace_dir = OUT / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    driver = names.load("drivers", traffic["driver"])
    driver.run(run, devices[0], Hooks(trace_dir))

    breakdown = None
    if args.trace:
        from yard import trace
        events = trace.collect(trace.latest_xplane(str(trace_dir)))
        if sum(events["lines"].values()) < 200_000:
            with open(trace_dir / "events.json", "w") as f:
                json.dump(events, f)
        run.device_trace = trace.reduce(events)
        breakdown = {"device_ops": run.device_trace["top_ops"],
                     "idle_gaps": run.device_trace["idle_gaps"]}

    for part, s in run.setup_parts.items():
        log(f"setup {part}_s={s:.4f}")
    log(f"setup_s={run.setup_s:.4f} chosen={run.chosen} nnz={run.nnz} "
        f"layout_bytes={run.layout_bytes} window_s={run.window_s:.4f} "
        f"attempted={run.attempted} failed={run.failed}")

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in names.cell_metrics(bench, args.workload, section):
        value = names.load("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": _finite(float(value)),
                                  "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    if run.device_trace is not None:
        device["busy_s"] = run.device_trace["busy_s"]
        device["window_s"] = run.device_trace["window_s"]
    correct = run.correct()
    checks = {name: {"value": _finite(float(v)), "limit": lim, "op": op}
              for name, (v, lim, op) in run.checks.items()}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (must be {c['op']} "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

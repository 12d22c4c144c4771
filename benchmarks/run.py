"""Benchmark harness — one section per paper table/figure.

  stream      beta measurement (paper Section IV-B)
  calibrate   (--calibrate) on-host ceiling calibration: fit per-format
              (peak_fraction, d_half) from a microbenchmark sweep and
              persist per HardwareSpec fingerprint, so later dispatch
              predictions use measured ceilings instead of the baked-in
              DEFAULT_EFFICIENCY constants
  table5      SpMM GFLOP/s across formats x matrices x d, via the
              structure-aware dispatcher (plus one strategy="auto" row per
              cell)
  fig2        attained vs sparsity-aware roofline + paper-claims check
  serve       streamed vs per-call dispatch across the four structures
              (the sparse.plan serving path; rows appended to the SpMM CSV)
  shard       sharded vs single-device steady-state replay (the
              sparse.plan(mesh=...) tier); rows appended to the SpMM CSV
              with the chosen B-strategy in the impl column.  Run under
              XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU.
  engine      continuous-batching engine vs per-request sync replay
              (repro.sparse.engine): per-request p50/p99 latency and
              goodput per structure, written to its own latency CSV
              (engine_smoke.csv / engine_table.csv — latency columns,
              not the GFLOP/s schema).  ``--engine-smoke`` runs it alone
              and enforces the coalescing-beats-sync goodput claim.
  kernels     Pallas kernel wall-time (interpret mode; correctness-scale)
  roofline    per-(arch x shape x mesh) three-term table from the dry-run
              records in experiments/dryrun (if present)

Prints ``name,us_per_call,derived`` CSV rows plus the full SpMM CSV to
benchmarks/out/.  ``--smoke`` runs the SpMM + streamed-serving suites at
tiny scale with few repeats — the CI per-PR dispatch-policy and
plan-once-beats-percall regression checks; the produced CSV (including
the streamed rows) is uploaded as a workflow artifact.  ``--smoke-bf16``
re-runs the tiny suite at reduced storage precision (bf16 values) and
soft-reports the bf16-vs-fp32 comparison — the CI nightly bf16 lane.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time


def _emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.2f},{derived}")


def bench_stream() -> float:
    from benchmarks.stream import measure_bandwidth
    t0 = time.perf_counter()
    bw = measure_bandwidth(n_bytes=128 * 2 ** 20, repeats=3)
    _emit("stream.copy", (time.perf_counter() - t0) * 1e6,
          f"{bw['copy'] / 1e9:.2f}GB/s")
    _emit("stream.triad", (time.perf_counter() - t0) * 1e6,
          f"{bw['triad'] / 1e9:.2f}GB/s")
    return bw["triad"]


def bench_calibrate(beta: float) -> None:
    from benchmarks.spmm_suite import planning_hardware
    from repro.core.calibrate import CalibrationStore, calibrate
    hw = planning_hardware(beta)
    store = CalibrationStore()
    t0 = time.perf_counter()
    cal = calibrate(hw, backend="jax", store=store)
    _emit("calibrate.total", (time.perf_counter() - t0) * 1e6,
          f"saved={store.path_for(hw)}")
    for e in cal.entries:
        _emit(f"calibrate.{e.format}", 0.0,
              f"peak_fraction={e.peak_fraction:.4f};d_half={e.d_half:.1f};"
              f"sustained={e.sustained_gflops:.2f}GF/s")


def bench_spmm(beta: float, *, scale: int = 16, d_values=None,
               repeats=None, csv_name: str = "table5_spmm.csv",
               dispatch_claims_only: bool = False) -> None:
    from benchmarks.spmm_suite import (
        dispatch_claims_check, paper_claims_check, run_suite,
        scale_free_claims_check, to_csv)
    # scale=16 (n=65,536): B and C at d=64 are 16 MB each, so the working
    # set exceeds this host's LLC — the paper's out-of-cache regime
    # (Section IV-A "matrices were selected to exceed on-chip caches").
    # The regime-comparison claims only hold out-of-cache, so smoke runs
    # (tiny, in-cache) check the dispatch claims alone.
    results = run_suite(beta, scale=scale, d_values=d_values,
                        repeats=repeats)
    os.makedirs("benchmarks/out", exist_ok=True)
    with open(os.path.join("benchmarks/out", csv_name), "w") as f:
        f.write(to_csv(results))
    for r in results:
        if r.d in (1, 64):
            _emit(f"table5.{r.matrix}.{r.impl}.d{r.d}",
                  2.0 * r.nnz * r.d / max(r.gflops, 1e-9) / 1e3,
                  f"{r.gflops:.2f}GF/s;roof={r.roofline_fraction:.2f};"
                  f"chosen={r.chosen}")
    claims = (dispatch_claims_check(results) if dispatch_claims_only
              else paper_claims_check(results))
    failed = [k for k, v in claims.items() if not v]
    for k, v in claims.items():
        _emit(f"fig2.claim.{k}", 0.0, "PASS" if v else "FAIL")
    # Soft-report (like the shard speedup target): the measured
    # binned-vs-CSR ordering needs a bandwidth-bound host; CI boxes are
    # instruction-bound, so this prints but never fails the build.
    for k, v in scale_free_claims_check(results).items():
        _emit(f"fig2.claim.{k}", 0.0, "PASS" if v else "FAIL")
    if dispatch_claims_only and failed:
        raise SystemExit(f"dispatch claims failed: {failed}")


def bench_spmm_bf16(beta: float, *, scale: int = 11, d_values=(16, 64),
                    repeats: int = 3,
                    csv_name: str = "smoke_spmm_bf16.csv") -> None:
    """bf16 smoke lane: the tiny suite re-run at reduced storage precision.

    CPU CI emulates bf16 (XLA upcasts to fp32 on host), so measured
    GFLOP/s carry no claim weight here; the lane exercises the
    reduced-precision dispatch path end-to-end nightly and gives the
    bf16-keyed cells their own trend baseline (``tools/perf_trend.py``
    keys cells on the dtype column, so these rows never diff against
    fp32 ones).  The bf16-keeps-up-with-fp32 comparison is soft-reported
    over the combined fp32 + bf16 results, mirroring the scale-free
    ordering soft report.  The jax backend carries bf16 with int32
    indices (XLA gathers), so the lane pins ``precision="bf16i32"``.
    """
    from benchmarks.spmm_suite import (
        precision_claims_check, run_suite, to_csv)
    base = run_suite(beta, scale=scale, d_values=d_values, repeats=repeats)
    reduced = run_suite(beta, scale=scale, d_values=d_values,
                        repeats=repeats, precision="bf16i32")
    os.makedirs("benchmarks/out", exist_ok=True)
    with open(os.path.join("benchmarks/out", csv_name), "w") as f:
        f.write(to_csv(reduced))
    for r in reduced:
        if r.d == max(d_values):
            _emit(f"bf16.{r.matrix}.{r.impl}.d{r.d}",
                  2.0 * r.nnz * r.d / max(r.gflops, 1e-9) / 1e3,
                  f"{r.gflops:.2f}GF/s;dtype={r.dtype};chosen={r.chosen}")
    for k, v in precision_claims_check(base + reduced).items():
        _emit(f"fig2.claim.{k}", 0.0, "PASS" if v else "FAIL")


def bench_stream_suite(beta: float, *, scale: int, d_values, reuses,
                       repeats: int, csv_name: str,
                       enforce: bool = False) -> None:
    from benchmarks.spmm_suite import CSV_HEADER
    from benchmarks.stream import (
        run_stream_suite, stream_claims_check, to_csv_rows)
    cells = run_stream_suite(beta, scale=scale, d_values=d_values,
                             reuses=reuses, repeats=repeats)
    path = os.path.join("benchmarks/out", csv_name)
    os.makedirs("benchmarks/out", exist_ok=True)
    # Appended to the SpMM CSV: one artifact per run, streamed rows keyed
    # by their impl column (stream_r8 / percall_r8 / ...).  Start from the
    # shared header when this suite runs first / alone.
    fresh = not os.path.exists(path)
    with open(path, "a") as f:
        f.write((CSV_HEADER if fresh else "") + "\n"
                + "\n".join(to_csv_rows(cells)))
    for c in cells:
        if c.reuse >= 8:
            # us_per_call column: amortized per-RHS time (total includes
            # that mode's planning/conversion); total stays in derived.
            _emit(f"serve.{c.matrix}.{c.mode}.d{c.d}.r{c.reuse}",
                  c.total_s * 1e6 / c.reuse,
                  f"{c.gflops:.2f}GF/s;total={c.total_s * 1e3:.1f}ms;"
                  f"chosen={c.chosen}")
    claims = stream_claims_check(cells)
    failed = [k for k, v in claims.items() if not v]
    for k, v in claims.items():
        _emit(f"serve.claim.{k}", 0.0, "PASS" if v else "FAIL")
    if enforce and failed:
        raise SystemExit(f"streamed-dispatch claims failed: {failed}")


def bench_shard_suite(beta: float, *, scale: int, d_values,
                      repeats: int, csv_name: str) -> None:
    from benchmarks.spmm_suite import CSV_HEADER
    from benchmarks.stream import (
        run_shard_suite, shard_claims_check, shard_csv_rows)
    cells = run_shard_suite(beta, scale=scale, d_values=d_values,
                            repeats=repeats)
    path = os.path.join("benchmarks/out", csv_name)
    os.makedirs("benchmarks/out", exist_ok=True)
    fresh = not os.path.exists(path)
    with open(path, "a") as f:
        f.write((CSV_HEADER if fresh else "") + "\n"
                + "\n".join(shard_csv_rows(cells)))
    for c in cells:
        _emit(f"shard.{c.matrix}.{c.impl}.d{c.d}",
              c.steady_s * 1e6,
              f"{c.gflops:.2f}GF/s;devices={c.devices};"
              f"speedup={c.speedup:.2f};chosen={c.chosen}")
    # Soft-report: the >=1.5x target needs real cores behind the virtual
    # devices (see shard_claims_check); the CSV rows carry the measured
    # speedups either way, and tools/perf_trend.py tracks them per-cell.
    for k, v in shard_claims_check(cells).items():
        _emit(f"shard.claim.{k}", 0.0, "PASS" if v else "FAIL")


def bench_engine_suite(beta: float, *, scale: int, d: int, streams: int,
                       per_stream: int, repeats: int, csv_name: str,
                       enforce: bool = False) -> None:
    from benchmarks.stream import (
        ENGINE_CSV_HEADER, engine_claims_check, engine_csv_rows,
        run_engine_suite)
    cells = run_engine_suite(beta, scale=scale, d=d, streams=streams,
                             per_stream=per_stream, repeats=repeats)
    os.makedirs("benchmarks/out", exist_ok=True)
    # The engine lane gets its own CSV: latency/goodput columns, not the
    # GFLOP/s schema the other lanes share.  tools/perf_trend.py trends
    # it with --metric goodput_rps.
    with open(os.path.join("benchmarks/out", csv_name), "w") as f:
        f.write(ENGINE_CSV_HEADER + "\n" + "\n".join(engine_csv_rows(cells)))
    for c in cells:
        _emit(f"engine.{c.matrix}.{c.impl}.d{c.d}", c.p50_us,
              f"p99={c.p99_us:.0f}us;goodput={c.goodput_rps:.1f}rps;"
              f"batches={c.batches}")
    claims = engine_claims_check(cells)
    failed = [k for k, v in claims.items() if not v]
    for k, v in claims.items():
        _emit(f"engine.claim.{k}", 0.0, "PASS" if v else "FAIL")
    if enforce and failed:
        raise SystemExit(f"serving-engine claims failed: {failed}")


def bench_kernels() -> None:
    import jax.numpy as jnp
    import numpy as np
    import jax
    from repro import kernels, sparse
    from repro.core import blocked as gen_blocked
    from repro.core import erdos_renyi
    from repro.kernels import registry
    m = gen_blocked(512, t=32, num_blocks=120, nnz_per_block=60, seed=0)
    b = jnp.asarray(np.random.default_rng(0).normal(
        size=(512, 64)).astype(np.float32))
    # Registry path (the ops.py wrappers are deprecated): bind prepares
    # the layout once, then timing measures the kernel replay alone.
    ctx = registry.KernelContext(bcsr_block=32, row_tile=8, chunk=128)
    run_bcsr = registry.get("bcsr", "pallas").bind(m, ctx)
    jax.block_until_ready(run_bcsr(b))
    t0 = time.perf_counter()
    jax.block_until_ready(run_bcsr(b))
    us = (time.perf_counter() - t0) * 1e6
    roof = kernels.bcsr_kernel_roofline(sparse.coo_to_bcsr(m, 32), 64)
    _emit("kernels.bcsr_spmm.interp", us,
          f"ai={roof.ai:.2f};mxu_util={roof.mxu_utilization:.2f}")
    mc = erdos_renyi(512, 8, seed=1)
    run_csr = registry.get("csr", "pallas").bind(mc, ctx)
    jax.block_until_ready(run_csr(b))
    t0 = time.perf_counter()
    jax.block_until_ready(run_csr(b))
    us = (time.perf_counter() - t0) * 1e6
    roof = kernels.csr_kernel_roofline(sparse.coo_to_csr(mc), 64)
    _emit("kernels.csr_spmm.interp", us,
          f"ai={roof.ai:.2f};mxu_util={roof.mxu_utilization:.2f}")
    g = kernels.grouped_matmul_roofline(4096, 4096, 1536, 128)
    _emit("kernels.grouped_matmul.model", 0.0,
          f"ai={g.ai:.1f};attainable={g.attainable_flops_per_s/1e12:.0f}TF")


def bench_roofline_table() -> None:
    from repro.core.analyzer import analyze_record
    paths = sorted(glob.glob("experiments/dryrun/*.json"))
    if not paths:
        _emit("roofline.table", 0.0, "SKIP-no-dryrun-records")
        return
    for p in paths:
        rec = analyze_record(json.load(open(p)))
        r = rec["roofline"]
        _emit(f"roofline.{rec['arch']}.{rec['shape']}.{rec['mesh']}",
              r["step_time_lower_bound_s"] * 1e6,
              f"dom={r['dominant']};mfu_ceil={r['mfu_upper_bound']:.3f}")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny-scale SpMM suite only (CI per-PR check); "
                             "writes benchmarks/out/smoke_spmm.csv")
    parser.add_argument("--engine-smoke", action="store_true",
                        help="engine-vs-sync serving lane only (CI engine "
                             "smoke job); writes benchmarks/out/"
                             "engine_smoke.csv and enforces the "
                             "coalescing-beats-sync goodput claim")
    parser.add_argument("--smoke-bf16", action="store_true",
                        help="tiny-scale suite at reduced storage "
                             "precision (CI nightly bf16 lane); writes "
                             "benchmarks/out/smoke_spmm_bf16.csv and "
                             "soft-reports the bf16-vs-fp32 comparison")
    parser.add_argument("--calibrate", action="store_true",
                        help="fit + persist on-host per-format compute "
                             "ceilings before (or instead of) the suites; "
                             "subsequent dispatcher predictions use them")
    args = parser.parse_args()
    from repro.launch.compile_cache import configure
    configure()
    print("name,us_per_call,derived")
    beta = bench_stream()
    if args.calibrate:
        bench_calibrate(beta)
        if not args.smoke:
            return
    if args.smoke_bf16:
        bench_spmm_bf16(beta)
        return
    if args.engine_smoke:
        bench_engine_suite(beta, scale=10, d=8, streams=4, per_stream=8,
                           repeats=3, csv_name="engine_smoke.csv",
                           enforce=True)
        return
    if args.smoke:
        bench_spmm(beta, scale=11, d_values=(1, 16, 64), repeats=3,
                   csv_name="smoke_spmm.csv", dispatch_claims_only=True)
        bench_stream_suite(beta, scale=10, d_values=(16, 64),
                           reuses=(1, 8), repeats=2,
                           csv_name="smoke_spmm.csv", enforce=True)
        bench_shard_suite(beta, scale=10, d_values=(64,), repeats=3,
                          csv_name="smoke_spmm.csv")
        return
    bench_spmm(beta)
    bench_stream_suite(beta, scale=12, d_values=(16, 64),
                       reuses=(1, 8, 64), repeats=2,
                       csv_name="table5_spmm.csv")
    bench_shard_suite(beta, scale=12, d_values=(16, 64), repeats=3,
                      csv_name="table5_spmm.csv")
    bench_engine_suite(beta, scale=12, d=8, streams=4, per_stream=16,
                       repeats=3, csv_name="engine_table.csv")
    bench_kernels()
    bench_roofline_table()


if __name__ == "__main__":
    main()

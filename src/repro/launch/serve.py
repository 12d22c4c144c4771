"""Serving launcher: batched LM decode, plus the streamed-SpMM serving path.

LM serving (prefill + greedy decode with a KV cache):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b \
        --reduced --batch 4 --prompt-len 32 --gen 16

Streamed SpMM serving (``--spmm-stream``): hold one sparse operator for
the whole process, plan once through ``sparse.plan`` with the expected
request count as the reuse horizon, and serve every per-step right-hand
side through the bound kernel (``docs/serving.md``):

    PYTHONPATH=src python -m repro.launch.serve --spmm-stream \
        --spmm-structure moe-block --spmm-n 4096 --spmm-d 64 \
        --spmm-steps 64

``--spmm-shards N`` serves the same stream through the sharded tier
(``repro.sparse.shard``): the plan partitions the operator across an
N-device mesh and replays under ``shard_map``; the printed summary adds
the B-distribution strategy audit (``docs/sharding.md``):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --spmm-stream \
        --spmm-shards -1 --spmm-structure moe-block

``--engine`` serves the same operator through the continuous-batching
engine (``repro.sparse.engine``): a synthetic open-loop arrival process
plays ``--engine-streams`` concurrent request streams with mixed
d-widths into the bounded queue, the worker thread coalesces compatible
requests into shared ``execute_wide`` calls, and the report adds
per-request p50/p99 latency and goodput next to an engine-vs-sync
comparison (``docs/serving_engine.md``):

    PYTHONPATH=src python -m repro.launch.serve --engine \
        --spmm-structure moe-block --spmm-n 4096 --spmm-d 64 \
        --engine-streams 4 --engine-requests 64 --engine-rate 2000

``--calibrate`` runs the on-host compute-ceiling calibration
(``repro.core.calibrate``) at startup and persists it, so the serving
plan predicts from measured ``(peak_fraction, d_half)`` ceilings.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.patterns import serving_suite


def generate(cfg, params, prompts: np.ndarray, gen: int):
    """Greedy decode ``gen`` tokens after prefilling ``prompts`` [B,S]."""
    from repro.models import model as M
    B, S = prompts.shape
    cache = M.init_cache(cfg, B, S + gen)
    # Prefill by stepping (teacher forcing) — a production server would
    # batch-prefill; the dry-run prefill cells cover that path.
    tok = jnp.asarray(prompts[:, 0])
    step = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    for t in range(S - 1):
        _, cache = step(params, cache, jnp.asarray(prompts[:, t]),
                        jnp.int32(t))
    tok = jnp.asarray(prompts[:, -1])
    out = []
    for t in range(gen):
        logits, cache = step(params, cache, tok, jnp.int32(S - 1 + t))
        tok = jnp.argmax(
            logits[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
    return np.stack(out, axis=1)


#: CLI choices derive from the shared registry so they can't drift from it.
STREAM_STRUCTURES = tuple(serving_suite(64))


def build_stream_matrix(structure: str, n: int):
    """Build the served sparse operator for one of the paper structures.

    ``moe-block`` is the serving-path case the repo targets: the MoE
    expert-dispatch matrix — dense t x t blocks on the diagonal, one per
    expert token bucket (repro.models.moe routes tokens into exactly this
    shape; see examples/moe_block_sparse.py).  The rest are the paper's
    Table III regimes at serving scale.  All four come from the shared
    registry ``repro.core.patterns.serving_suite``, which
    ``benchmarks/stream.py`` measures.
    """
    suite = serving_suite(n)
    if structure not in suite:
        raise ValueError(f"unknown structure {structure!r}; choose from "
                         f"{STREAM_STRUCTURES}")
    return suite[structure]()


def run_startup_calibration() -> None:
    """Calibrate the per-format compute ceilings for the serving host.

    Runs the short ``repro.core.calibrate`` sweep against the hardware
    spec the default dispatcher resolves to, persists the result to the
    default :class:`~repro.core.calibrate.CalibrationStore`, and
    refreshes the dispatcher so every subsequent plan (including the
    ``--spmm-stream`` serving plan) predicts from measured ceilings
    (``ceiling_source="calibrated"``) instead of the baked-in defaults.
    """
    from repro import sparse
    from repro.core.calibrate import CalibrationStore, calibrate

    disp = sparse.default_dispatcher()
    backend = disp._resolve_backend()
    hw = disp._resolve_hardware()
    t0 = time.perf_counter()
    store = CalibrationStore()
    cal = calibrate(hw, backend=backend, store=store)
    disp.refresh_calibration()
    print(f"startup calibration ({backend} kernels on {hw.name}) took "
          f"{time.perf_counter() - t0:.1f}s -> {store.path_for(hw, backend)}")
    print(cal.summary())


def serve_spmm_stream(args) -> None:
    """Serve ``--spmm-steps`` right-hand sides through one persistent plan."""
    from repro import sparse
    m = build_stream_matrix(args.spmm_structure, args.spmm_n)
    rng = np.random.default_rng(1)

    def next_batch():
        return jnp.asarray(
            rng.normal(size=(m.n, args.spmm_d)).astype(np.float32))

    mesh = None
    shards = getattr(args, "spmm_shards", 0)    # absent on hand-built args
    if shards:
        from repro.launch.mesh import make_shard_mesh
        mesh = make_shard_mesh(None if shards < 0 else shards)

    t0 = time.perf_counter()
    plan = sparse.plan(m, sparse.BSpec(d=args.spmm_d, reuse=args.spmm_steps),
                       mesh=mesh)
    jax.block_until_ready(plan.execute(next_batch()))   # bind + compile
    startup_s = time.perf_counter() - t0
    plan.reset_stats()     # the warm-up is startup, not a served request

    lat = []
    for _ in range(args.spmm_steps):
        b = next_batch()
        t1 = time.perf_counter()
        jax.block_until_ready(plan.execute(b))
        lat.append(time.perf_counter() - t1)
    lat_us = np.asarray(lat) * 1e6
    flops = 2.0 * m.nnz * args.spmm_d

    # ShardedPlan.summary() adds the B-strategy audit under the format
    # decision table; the single-device plan prints the table alone.
    print(plan.summary() if mesh is not None else plan.dispatch.summary())
    single = sparse.plan_spmm(m, args.spmm_d, reuse=1)
    note = ("same as single-shot" if single.chosen == plan.chosen else
            f"single-shot would pick {single.chosen}")
    print(f"serving {args.spmm_structure} [{m.n}x{m.n}, nnz={m.nnz}] "
          f"d={args.spmm_d}: planned for reuse={args.spmm_steps} "
          f"-> {plan.chosen} ({note})")
    print(f"startup (classify+plan+convert+compile): {startup_s * 1e3:.1f} ms")
    print(f"steady-state: p50={np.percentile(lat_us, 50):.0f}us "
          f"p99={np.percentile(lat_us, 99):.0f}us "
          f"-> {flops / np.median(lat_us) / 1e3:.2f} GFLOP/s")

    if args.spmm_compare:
        # Replay the exact same stream: reseed so the draws repeat the
        # streamed run (one warm-up batch, then the served batches).
        rng = np.random.default_rng(1)
        # Warm the single-shot format's kernel first: it can differ from
        # the streamed choice, and its one-time jit compile would
        # otherwise land inside the first timed iteration.
        jax.block_until_ready(
            sparse.Dispatcher(backend=plan.dispatch.backend)
            .spmm(m, next_batch(), reuse=1))
        # Time only the dispatch+execute, like the streamed loop above —
        # host-side RHS generation is excluded from both sides.
        percall_s = 0.0
        for _ in range(args.spmm_steps):
            b = next_batch()
            t2 = time.perf_counter()
            jax.block_until_ready(
                sparse.Dispatcher(backend=plan.dispatch.backend)
                .spmm(m, b, reuse=1))
            percall_s += time.perf_counter() - t2
        streamed_s = float(np.sum(lat))
        print(f"per-call dispatch (fresh dispatcher per request, no "
              f"caches) of the same stream: {percall_s * 1e3:.1f} ms vs "
              f"streamed {streamed_s * 1e3:.1f} ms "
              f"({percall_s / max(streamed_s, 1e-12):.1f}x; "
              f"a warm-cache per-call baseline sits between — see "
              f"benchmarks/stream.py percall_cached)")
    print(f"stats: {plan.stats()}")


def serve_spmm_engine(args) -> None:
    """Serve an open-loop arrival process through the serving engine.

    ``--engine-streams`` synthetic clients each submit
    ``--engine-requests`` right-hand sides with exponential
    inter-arrival gaps (open loop: arrivals don't wait for completions,
    so the queue actually exercises coalescing and backpressure).
    Stream widths alternate ``d`` and ``d // 2`` to show mixed-width
    coalescing.  After the engine drains, the same request sequence is
    replayed through synchronous per-request ``plan.execute`` calls and
    both sides report p50/p99 per-request latency and goodput
    (``docs/serving_engine.md`` walks through one of these transcripts).
    """
    import threading

    from repro import sparse

    m = build_stream_matrix(args.spmm_structure, args.spmm_n)
    streams = max(args.engine_streams, 1)
    per_stream = max(args.engine_requests // streams, 1)
    rate = max(args.engine_rate, 1e-9)      # requests/s per stream

    def width(stream: int) -> int:
        return args.spmm_d if stream % 2 == 0 else max(args.spmm_d // 2, 1)

    # Pre-draw every operand so generation cost stays out of both timings.
    rng = np.random.default_rng(1)
    reqs = [[jnp.asarray(rng.normal(size=(m.n, width(s)))
                         .astype(np.float32)) for _ in range(per_stream)]
            for s in range(streams)]
    gaps = [[rng.exponential(1.0 / rate) for _ in range(per_stream)]
            for _ in range(streams)]
    total = streams * per_stream

    t0 = time.perf_counter()
    plan = sparse.plan(m, sparse.BSpec(d=args.spmm_d, reuse=total))
    jax.block_until_ready(plan.execute(reqs[0][0]))   # bind + compile
    plan.reset_stats()

    engine = sparse.ServingEngine(
        max_queue=args.engine_queue, policy=args.engine_policy)
    engine.register("spmm", plan)
    # Prime every coalesced launch width the run can reach, so jit
    # compiles land in startup instead of inside request latencies.
    worst_case_cols = sum(b.shape[1] for stream in reqs for b in stream)
    warmed = engine.warmup("spmm", max_cols=worst_case_cols)
    startup_s = time.perf_counter() - t0
    engine.start()

    def client(stream: int, tickets: list) -> None:
        for gap, b in zip(gaps[stream], reqs[stream]):
            time.sleep(gap)
            try:
                tickets.append(engine.submit("spmm", b))
            except sparse.ShedError:
                pass                        # counted in engine.stats()

    tickets: list = []
    per_client: list = [[] for _ in range(streams)]
    threads = [threading.Thread(target=client, args=(s, per_client[s]))
               for s in range(streams)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for lst in per_client:
        tickets.extend(lst)
    for t in tickets:
        t.result(timeout=120.0)
    engine.stop()
    stats = engine.stats()

    # Sync baseline: per-request replay of the identical sequence on the
    # same plan, one block_until_ready per request.  Warm each distinct
    # request width first — the engine got its launch widths warmed at
    # startup, so the baseline gets the same courtesy.
    for w in sorted({b.shape[1] for stream in reqs for b in stream}):
        jax.block_until_ready(
            plan.execute_wide(jnp.zeros((m.n, w), jnp.float32)))
    plan.reset_stats()
    sync_lat = []
    t_sync0 = time.perf_counter()
    for s in range(streams):
        for b in reqs[s]:
            t1 = time.perf_counter()
            jax.block_until_ready(plan.execute_wide(b))
            sync_lat.append(time.perf_counter() - t1)
    sync_span = time.perf_counter() - t_sync0
    sync_us = np.asarray(sync_lat) * 1e6
    sync_goodput = len(sync_lat) / max(sync_span, 1e-12)

    print(plan.dispatch.summary())
    print(f"engine serving {args.spmm_structure} [{m.n}x{m.n}, "
          f"nnz={m.nnz}]: {streams} streams x {per_stream} requests, "
          f"widths d={args.spmm_d}/{max(args.spmm_d // 2, 1)}, "
          f"open-loop rate {rate:.0f} req/s/stream, "
          f"queue={args.engine_queue} policy={args.engine_policy}")
    print(f"startup (classify+plan+convert+compile, {warmed} launch "
          f"widths warmed): {startup_s * 1e3:.1f} ms")
    print(engine.summary())
    print(f"sync per-request replay of the same {len(sync_lat)} requests: "
          f"p50={np.percentile(sync_us, 50):.0f}us "
          f"p99={np.percentile(sync_us, 99):.0f}us "
          f"goodput={sync_goodput:.1f} req/s")
    if stats["goodput_rps"] > 0:
        print(f"engine vs sync goodput: {stats['goodput_rps']:.1f} vs "
              f"{sync_goodput:.1f} req/s "
              f"({stats['goodput_rps'] / max(sync_goodput, 1e-12):.2f}x)")


def main():
    """Parse arguments and run either the LM or the streamed-SpMM server."""
    from repro.launch.compile_cache import configure
    configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--spmm-stream", action="store_true",
                    help="serve SpMM through a persistent sparse.plan "
                         "instead of an LM decode loop")
    ap.add_argument("--spmm-structure", choices=STREAM_STRUCTURES,
                    default="moe-block")
    ap.add_argument("--spmm-n", type=int, default=4096)
    ap.add_argument("--spmm-d", type=int, default=64)
    ap.add_argument("--spmm-steps", type=int, default=64,
                    help="requests to serve = the plan's reuse horizon")
    ap.add_argument("--spmm-compare", action="store_true",
                    help="also time per-call dispatch of the same stream")
    ap.add_argument("--spmm-shards", type=int, default=0,
                    help="serve through the sharded tier on this many "
                         "devices (-1 = all visible); on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first")
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(repro.sparse.engine): open-loop concurrent "
                         "clients, bounded queue, coalesced execute_wide "
                         "batches, p50/p99 + goodput report vs a sync "
                         "per-request baseline")
    ap.add_argument("--engine-streams", type=int, default=4,
                    help="concurrent synthetic client streams")
    ap.add_argument("--engine-requests", type=int, default=64,
                    help="total requests across all streams")
    ap.add_argument("--engine-rate", type=float, default=2000.0,
                    help="open-loop arrival rate per stream (requests/s)")
    ap.add_argument("--engine-queue", type=int, default=256,
                    help="bounded admission-queue depth")
    ap.add_argument("--engine-policy", choices=("wait", "shed"),
                    default="wait",
                    help="backpressure when the queue is full: block the "
                         "submitter ('wait') or reject ('shed')")
    ap.add_argument("--calibrate", action="store_true",
                    help="run the on-host ceiling calibration at startup; "
                         "the serving plan then predicts from measured "
                         "(peak_fraction, d_half) instead of defaults")
    args = ap.parse_args()

    if args.calibrate:
        run_startup_calibration()
    if args.engine:
        serve_spmm_engine(args)
        return
    if args.spmm_stream:
        serve_spmm_stream(args)
        return
    if not args.arch:
        ap.error("--arch is required unless --spmm-stream or --engine "
                 "is set")

    from repro.configs.base import get_config
    from repro.models import model as M
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, cfg.vocab_size - 1,
                           size=(args.batch, args.prompt_len)).astype(
        np.int32)
    t0 = time.perf_counter()
    out = generate(cfg, params, prompts, args.gen)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", out[0][:10])


if __name__ == "__main__":
    main()

"""Open-loop serving: requests arrive on a schedule into one
``ServingEngine`` that holds the operator's plan.

Traffic parameters: ``plan_d`` (the width the plan is made for),
``widths`` (request widths, drawn in equal shares), ``rate_per_s``
(offered requests per second), ``ramp_s`` (seconds of the same traffic
offered before the window opens), ``pool`` (distinct host operands per
width), ``max_queue`` (engine admission queue), ``late_limit_s`` (how
long past the window's close an answer is waited for).

Every seed gets the same work: ``round(rate_per_s * seconds)`` requests,
the widths in equal shares, and the gaps between arrivals the quantiles
of an exponential distribution at the rate (a Poisson process), both
shuffled by the seed.  The last request is due at ``--seconds``.  The
ramp is the same schedule over ``ramp_s``, so the queue is in its steady
state when the window opens; ramp requests are served and checked but
not timed.  A request's latency runs from when it was due to when its
result is on the host; a request that fails or never comes counts as
answered a minute past the close (``yard.readers.latency_s``).
"""
from __future__ import annotations

import queue
import sys
import threading
import time

import numpy as np

from yard.readers import latency_s
from yard.reference import sample_rows
from yard.run import Run, make_operator, memory_peak, plan_and_pack
from yard.work import compulsory

OPERATOR = "op"


def schedule(traffic: dict, seconds: float, seed: int):
    """``(due offsets in seconds, widths)`` of ``seconds`` of requests."""
    count = max(1, int(round(traffic["rate_per_s"] * seconds)))
    rng = np.random.default_rng([seed, 5])
    q = (np.arange(count) + 0.5) / count
    gaps = rng.permutation(-np.log1p(-q))
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    widths = np.resize(np.asarray(traffic["widths"]), count)
    return due, rng.permutation(widths)


def _pool(seed: int, widths, count: int, n: int):
    """``{k: [count host arrays [n, k]]}`` made on the device by one
    jitted call and copied to the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(widths) * count)
        return [jax.random.normal(keys[i * count + j], (n, k), jnp.float32)
                for i, k in enumerate(widths) for j in range(count)]

    flat = jax.device_get(make(jax.random.key(seed)))
    return {k: flat[i * count:(i + 1) * count]
            for i, k in enumerate(widths)}


def run(run: Run, device, hooks) -> None:
    annotate = hooks.annotate
    from repro import sparse
    t = run.traffic
    with run.part("generate"):
        op = make_operator(run)
    sp = plan_and_pack(run, op, t["plan_d"])
    with run.part("warmup"):
        engine = sparse.ServingEngine(
            max_queue=t["max_queue"], policy="wait",
            clock=time.perf_counter, batch_log_depth=1 << 20)
        engine.register(OPERATOR, sp)
        engine.warmup(OPERATOR)
    widths = sorted(set(t["widths"]))
    with run.part("inputs"):
        pool = _pool(int(np.random.default_rng([run.seed, 3]).integers(
            2 ** 31)), widths, t["pool"], op.coo.n)
    ramp_due, ramp_widths = schedule(t, t["ramp_s"], run.seed)
    due, req_widths = schedule(t, run.seconds, run.seed)
    offsets = np.concatenate([ramp_due, t["ramp_s"] + due])
    rows = sample_rows(op.coo.n, run.seed)
    engine.start()
    run.setup_s = time.perf_counter() - run.t_start

    count = len(offsets)
    reqs = [{"due": None, "submitted": None, "done": None,
             "cols": int(k), "pool": i % t["pool"], "error": None,
             "c_rows": None}
            for i, k in enumerate(np.concatenate([ramp_widths, req_widths]))]
    handed = queue.Queue()

    def generate():
        for i, r in enumerate(reqs):
            delay = r["due"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            with annotate("chipbench.submit"):
                r["submitted"] = time.perf_counter()
                try:
                    ticket = engine.submit(OPERATOR,
                                           pool[r["cols"]][r["pool"]])
                except Exception as e:  # a refused request counts as failed
                    r["error"] = repr(e)
                    ticket = None
            handed.put((i, ticket))

    gen = threading.Thread(target=generate, name="chipbench-generator")
    try:
        with hooks.window():
            t_ramp = time.perf_counter()
            run.t0 = t_ramp + t["ramp_s"]
            run.t_end = run.t0 + run.seconds
            for r, offset in zip(reqs, offsets):
                r["due"] = t_ramp + float(offset)
            gen.start()
            give_up = run.t_end + t["late_limit_s"]
            for _ in range(count):
                i, ticket = handed.get()
                r = reqs[i]
                if ticket is None:
                    continue
                with annotate("chipbench.result"):
                    try:
                        res = ticket.result(timeout=max(
                            give_up - time.perf_counter(), 0.0))
                    except TimeoutError as e:
                        r["error"] = repr(e)
                        continue
                    except Exception as e:  # served with an error
                        r["error"] = repr(e)
                        continue
                    r["done"] = time.perf_counter()
                    r["batch_done"] = ticket.done_s
                    r["dtype_ok"] = res.dtype == np.float32 and \
                        res.shape == (op.coo.n, r["cols"])
                    r["c_rows"] = np.array(res[rows]) if r["dtype_ok"] \
                        else None
                del ticket, res
    finally:
        gen.join()
        run.memory_peak_bytes = memory_peak(device)
        engine.stop(drain=True)

    run.answers = [(r["done"], r["cols"]) for r in reqs
                   if r["done"] is not None]
    run.requests = reqs[len(ramp_due):]
    lat = [latency_s(run, r) for r in run.requests]
    half = len(lat) // 2
    done = sorted({r["batch_done"] for r in reqs if r["done"] is not None})
    print(f"chipbench: serve requests={len(run.requests)} ramp="
          f"{len(ramp_due)} batches={len(engine.batch_log)}"
          f" batch_cycle_s={np.mean(np.diff(done)):.4f}"
          f" p95_first_half_s={np.percentile(lat[:half], 95):.4f}"
          f" p95_second_half_s={np.percentile(lat[half:], 95):.4f}"
          f" max_s={max(lat):.4f}", file=sys.stderr, flush=True)
    run.attempted = len(run.requests)
    run.failed = sum(r["done"] is None for r in run.requests)
    for rec in engine.batch_log:
        cols = sum(rec.widths)
        run.batches.append((cols, rec.cols, rec.queued_s))
        run.work.append(compulsory(run.nnz, run.nonempty_cols,
                                   op.coo.n, cols))

    # Correctness, after the window: every answered request's sample rows.
    ref = op.reference(run.seed)
    worst, checked = 0.0, 0
    for r in reqs:
        if r["done"] is None:
            continue
        b_needed = pool[r["cols"]][r["pool"]][ref.needed]
        c_rows = ref.control(b_needed) if run.control else r["c_rows"]
        worst = max(worst, float("inf") if c_rows is None
                    else ref.scaled_error(c_rows, b_needed))
        checked += 1
    run.check("max_scaled_err", worst, run.cfg["limits"]["max_scaled_err"])
    run.check("unanswered", sum(r["done"] is None for r in reqs), 0)
    run.check("answers_checked", checked, 1, ">=")

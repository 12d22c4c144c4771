"""Closed-loop replay: ``StreamPlan.execute`` over a pool of distinct
device-made right-hand sides, at most ``in_flight`` calls outstanding.

Traffic parameters: ``d`` (width of every B), ``pool`` (distinct B held
on the device), ``in_flight`` (calls outstanding), ``retained`` (outputs
kept for the correctness check, a reservoir sample drawn from the seed).

The window starts at the first launch and ends when the last call
launched before ``--seconds`` had passed is complete, so every call in
it is counted whole.  Set-up runs one call per distinct input shape
before it, so nothing compiles inside the window.
"""
from __future__ import annotations

import time

import numpy as np

from yard.run import Run, make_operator, memory_peak, plan_and_pack
from yard.work import compulsory


def _pool(seed: int, count: int, n: int, d: int):
    """``count`` distinct ``[n, d]`` float32 B made on the device by one
    jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        keys = jax.random.split(key, count)
        return [jax.random.normal(k, (n, d), jnp.float32) for k in keys]

    return jax.block_until_ready(make(jax.random.key(seed)))


def run(run: Run, device, hooks) -> None:
    import jax
    import jax.numpy as jnp
    t = run.traffic
    d, pool_n, in_flight = t["d"], t["pool"], t["in_flight"]
    with run.part("generate"):
        op = make_operator(run)
    sp = plan_and_pack(run, op, d)
    with run.part("inputs"):
        pool = _pool(int(np.random.default_rng([run.seed, 3]).integers(
            2 ** 31)), pool_n, op.coo.n, d)
    with run.part("warmup"):
        jax.block_until_ready(sp.execute(pool[0]))
    run.setup_s = time.perf_counter() - run.t_start

    annotate = hooks.annotate
    rng = np.random.default_rng([run.seed, 4])
    keep = t["retained"]
    kept = []                         # reservoir of (call index, C)
    outstanding = []                  # (index, launched, C)
    i = 0
    with hooks.window():
        run.t0 = time.perf_counter()
        deadline = run.t0 + run.seconds
        while True:
            if time.perf_counter() < deadline:
                with annotate("chipbench.execute"):
                    launched = time.perf_counter()
                    outstanding.append((i, launched,
                                        sp.execute(pool[i % pool_n])))
                i += 1
                if len(outstanding) < in_flight:
                    continue
            elif not outstanding:
                break
            j, launched, c = outstanding.pop(0)
            with annotate("chipbench.wait"):
                c.block_until_ready()
            run.calls.append((launched, time.perf_counter()))
            # Reservoir sampling: every call is equally likely kept.
            if len(kept) < keep:
                kept.append((j, c))
            else:
                slot = rng.integers(j + 1)
                if slot < keep:
                    kept[slot] = (j, c)
            del c
    run.t_end = run.calls[-1][1]
    run.attempted = len(run.calls)
    run.work = [compulsory(run.nnz, run.nonempty_cols, op.coo.n, d)] * len(
        run.calls)
    run.memory_peak_bytes = memory_peak(device)

    # Correctness, after the window: each kept C on the sample rows.
    ref = op.reference(run.seed)
    rows = jnp.asarray(ref.rows)
    needed = jnp.asarray(ref.needed)
    worst = 0.0
    for j, c in kept:
        if c.dtype != jnp.float32 or c.shape != (op.coo.n, d):
            worst = float("inf")
            continue
        b_needed = np.asarray(jnp.take(pool[j % pool_n], needed, axis=0))
        c_rows = ref.control(b_needed) if run.control else \
            np.asarray(jnp.take(c, rows, axis=0))
        worst = max(worst, ref.scaled_error(c_rows, b_needed))
    run.check("max_scaled_err", worst, run.cfg["limits"]["max_scaled_err"])
    run.check("outputs_checked", len(kept), 1, ">=")

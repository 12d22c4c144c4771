"""``correct`` comes out false for the control and for each fault the
cells can have, and true for the program as it is.

Each case drives a whole run through ``run.main`` at 2**10 rows on the
CPU (``--rehearse``, which skips the look for a chip), with the timed
path broken underneath where the case says so.
"""
import contextlib
import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

import run as bench
from repro.sparse import stream

LOG2N = 10


def _run(workload, *extra, seconds="0.6"):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.main(["--workload", workload, "--seed", "4000000007",
                         "--seconds", seconds, "--trace", "0",
                         "--rehearse", str(LOG2N), *extra])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _scaled(out):
    return out * (1 + 2.0 ** -10)


def _half_columns(out):
    half = out.shape[1] // 2
    return out.at[:, :half].set(0.0)


def _shift_columns(out):
    return jnp.roll(out, 1, axis=1)


FAULTS = {
    "answer_altered": _scaled,
    "half_of_batch_left_out": _half_columns,
    "answers_swapped": _shift_columns,
}


@pytest.mark.parametrize("workload", ["lj.replay", "lj.serve"])
def test_program_is_correct(workload):
    res = _run(workload, *(["--rate", "6"] if "serve" in workload else []))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("workload", ["lj.replay", "lj.serve"])
def test_control_is_not_correct(workload):
    res = _run(workload, "--control",
               *(["--rate", "6"] if "serve" in workload else []))
    assert res["correct"] is False
    assert res["checks"]["max_scaled_err"]["value"] > \
        res["checks"]["max_scaled_err"]["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["lj.replay", "lj.serve"])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    # Replay times StreamPlan.execute; the engine launches through
    # execute_wide.  Break the one the cell drives, where C is produced.
    name = "execute_wide" if "serve" in workload else "execute"
    real = getattr(stream.StreamPlan, name)

    def broken(self, *args, **kwargs):
        return FAULTS[fault](real(self, *args, **kwargs))

    monkeypatch.setattr(stream.StreamPlan, name, broken)
    res = _run(workload, *(["--rate", "6"] if "serve" in workload else []))
    assert res["correct"] is False, res["checks"]


def test_unanswered_request_is_not_correct(monkeypatch):
    from repro.sparse import engine as eng
    real = eng.ServingEngine.submit
    seen = []

    def dropping(self, operator, b, **kwargs):
        ticket = real(self, operator, b, **kwargs)
        seen.append(ticket)
        if len(seen) == 2:           # this request's answer never comes
            ticket._event = type(ticket._event)()
            ticket._event.wait = lambda timeout=None: False
        return ticket

    monkeypatch.setattr(eng.ServingEngine, "submit", dropping)
    monkeypatch.setattr(bench.names, "traffic", lambda name: {
        **json.load(open(bench.HERE / "traffic" / f"{name}.json")),
        "late_limit_s": 1})
    res = _run("lj.serve", "--rate", "6")
    assert res["correct"] is False
    assert res["checks"]["unanswered"]["value"] >= 1
    assert np.isfinite(res["metrics"]["p95_latency_ms"]["value"])

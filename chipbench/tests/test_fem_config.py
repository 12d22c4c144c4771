"""The ``fem_audikw`` configuration: its generator, the ``bcsr_block_us``
reader, and ``fem.replay``'s program and control judged on the CPU."""
import contextlib
import io
import json
import types

import numpy as np
import pytest

import run as bench
from repro import obs
from yard import names

GEN = names.load("generators", "hex_elasticity")
CFG = names.config(names.benchmark(), "fem_audikw")


def test_generator_counts_and_symmetry():
    params = {**CFG["generator"], "nodes": [4, 3, 5]}
    n = 3 * 4 * 3 * 5
    rows, cols = GEN.generate(n, params, 0)
    lin = rows * n + cols
    assert rows.size == 9 * 10 * 7 * 13                 # 9 * prod(3n - 2)
    assert np.unique(lin).size == rows.size             # no duplicates
    assert np.array_equal(np.sort(lin), np.sort(cols * n + rows))
    # Dense 3 x 3 node blocks: every coupled pair of nodes holds all 9.
    _, per_pair = np.unique((rows // 3) * n + cols // 3, return_counts=True)
    assert (per_pair == 9).all()


def test_configuration_states_its_delivered_counts():
    nx, ny, nz = CFG["generator"]["nodes"]
    assert CFG["n"] == CFG["delivered"]["n"] == 3 * nx * ny * nz
    assert CFG["delivered"]["nnz"] == \
        9 * (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    assert CFG["reduced"] == {}


def test_a_rehearsal_gets_the_largest_cube_that_fits():
    rows, cols = GEN.generate(4096, CFG["generator"], 0)
    assert rows.size == 9 * 31 ** 3                     # 11^3 nodes
    assert max(rows.max(), cols.max()) < 3 * 11 ** 3


def _run_with(calls=10, device_s=1.5):
    return types.SimpleNamespace(
        t0=100.0, t_end=150.0, calls=[(0.0, 0.0)] * calls,
        device_trace={"spmm_device_s": device_s})


def _execute(i, start, **attrs):
    return obs.Span(i, None, "repro.execute", start, start + 1e-3, "t",
                    {"format": "bcsr", **attrs})


@pytest.fixture
def log(monkeypatch):
    state = {"spans": []}
    monkeypatch.setattr(obs, "spans", lambda: list(state["spans"]))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    return state


def test_bcsr_block_us_divides_device_time_by_grid_steps(log):
    log["spans"] = [_execute(0, 90.0, blocks=284_193, block_t=64,
                             segments=3)]
    log["spans"] += [_execute(i, 100.0 + i, blocks=284_193, block_t=64,
                              segments=3) for i in range(1, 11)]
    read = names.load("metrics", "bcsr_block_us").read
    assert read(_run_with()) == pytest.approx(1e6 * 1.5 / (10 * 284_193))


def test_bcsr_block_us_reads_none_without_the_attr(log):
    read = names.load("metrics", "bcsr_block_us").read
    log["spans"] = [_execute(i, 100.0 + i, chunks=5, cold_chunks=1)
                    for i in range(10)]
    assert read(_run_with()) is None
    log["spans"] = []
    assert read(_run_with()) is None


def _rehearse(*extra):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.main(["--workload", "fem.replay", "--seed", "4000000009",
                         "--seconds", "0.6", "--trace", "0",
                         "--rehearse", "12", *extra])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_program_is_correct():
    res = _rehearse()
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_is_not_correct():
    res = _rehearse("--control")
    assert res["correct"] is False
    assert res["checks"]["max_scaled_err"]["value"] > \
        res["checks"]["max_scaled_err"]["limit"]

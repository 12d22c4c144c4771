"""The program's spans (repro.obs): nesting, the bounded log, compiles,
and the spans the dispatcher, the plan and the serving engine emit."""
import collections
import glob
import importlib.util
import sys
import threading
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, sparse
from repro.core import banded, blocked
from repro.sparse.dispatch import Dispatcher

CHIPBENCH = Path(__file__).resolve().parent.parent / "chipbench"
ENGINE_STAGES = ("draft", "stage", "dispatch", "wait", "fetch", "complete")


@pytest.fixture(autouse=True)
def _empty_log():
    obs.reset()
    yield
    obs.reset()


def _named(prefix):
    return [s for s in obs.spans() if s.name.startswith(prefix)]


def _metric(name):
    """A benchmark metric reader, loaded the way the benchmark loads it."""
    if str(CHIPBENCH) not in sys.path:
        sys.path.insert(0, str(CHIPBENCH))
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", CHIPBENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def test_parents_nest_and_threads_keep_their_own_stacks():
    opened = threading.Event()
    release = threading.Event()

    def other():
        with obs.span("repro.test.c"):
            with obs.span("repro.test.d"):
                opened.set()
                release.wait(5)

    with obs.span("repro.test.a", k=1) as attrs:
        attrs["late"] = 2
        t = threading.Thread(target=other)
        t.start()
        opened.wait(5)
        with obs.span("repro.test.b"):
            release.set()
        t.join()
    by = {s.name: s for s in obs.spans()}
    assert by["repro.test.a"].parent is None
    assert by["repro.test.b"].parent == by["repro.test.a"].id
    assert by["repro.test.c"].parent is None
    assert by["repro.test.d"].parent == by["repro.test.c"].id
    assert by["repro.test.c"].thread != by["repro.test.a"].thread
    assert by["repro.test.a"].attrs == {"k": 1, "late": 2}
    a, b = by["repro.test.a"], by["repro.test.b"]
    assert a.start <= b.start <= b.end <= a.end


def test_a_span_closes_and_pops_on_error():
    with pytest.raises(RuntimeError):
        with obs.span("repro.test.fails"):
            raise RuntimeError("boom")
    with obs.span("repro.test.after"):
        pass
    by = {s.name: s for s in obs.spans()}
    assert by["repro.test.after"].parent is None
    assert "repro.test.fails" in by


def test_the_bounded_log_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(obs, "_log", collections.deque(maxlen=3))
    for i in range(5):
        with obs.span("repro.test.n", i=i):
            pass
    assert obs.dropped() == 2
    assert [s.attrs["i"] for s in obs.spans()] == [2, 3, 4]
    obs.reset()
    assert obs.dropped() == 0 and obs.spans() == []


def test_a_compile_inside_a_span_is_its_child():
    x = jnp.arange(7.0).block_until_ready()
    obs.reset()
    with obs.span("repro.test.outer"):
        jax.jit(lambda v: v * 3.25 + 0.8125)(x).block_until_ready()
    outer = _named("repro.test.outer")[0]
    compiles = _named("repro.compile")
    assert compiles
    assert all(c.parent == outer.id for c in compiles)
    assert all(outer.start - 1e-3 <= c.start <= c.end <= outer.end
               for c in compiles)


def test_spans_reach_the_profilers_host_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("repro.test.traced", batch=3):
            with obs.span("repro.test.inner"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                                "*.xplane.pb")))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events}
    assert {"repro.test.traced", "repro.test.inner"} <= names


def test_plan_scores_each_candidate_on_a_miss_and_nothing_on_a_hit():
    m = banded(512, 3, fill=0.8, seed=1)
    disp = Dispatcher()
    plan = disp.plan(m, 16)
    classify = _named("repro.dispatch.classify")
    score = _named("repro.dispatch.score")
    cands = _named("repro.dispatch.candidate")
    assert len(classify) == 1 and len(score) == 1
    assert classify[0].end <= score[0].start
    assert [(c.attrs["format"], c.attrs["precision"]) for c in cands] == \
        [(c.format, c.precision) for c in plan.candidates]
    assert all(c.parent == score[0].id for c in cands)
    obs.reset()
    assert disp.plan(m, 16) is plan
    assert _named("repro.dispatch.") == []


def test_bind_prepares_and_execute_is_one_span():
    m = banded(512, 3, fill=0.8, seed=2)
    sp = sparse.plan(m, sparse.BSpec(d=8, reuse=16))
    bind = _named("repro.plan.bind")
    prepare = _named("repro.dispatch.prepare")
    assert len(bind) == 1 and len(prepare) == 1
    assert prepare[0].parent == bind[0].id
    assert prepare[0].attrs == {"format": sp.chosen,
                                "precision": sp.precision}
    b = jnp.ones((512, 8), jnp.float32)
    obs.reset()
    sp.execute(b)
    sp.execute_async(b)
    sp.execute_wide(jnp.ones((512, 24), jnp.float32), block_d=8)
    ex = _named("repro.execute")
    assert len(ex) == 3
    assert all(s.attrs == {"format": sp.chosen} for s in ex)


def test_engine_stages_each_batch_once_in_order(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(obs, "clock", clock)
    m = blocked(256, t=32, num_blocks=8, nnz_per_block=64, seed=3)
    eng = sparse.ServingEngine(clock=clock, max_batch_cols=8)
    eng.register("spmm", sparse.plan(m, sparse.BSpec(d=8, reuse=1024)))
    b = jnp.asarray(np.random.default_rng(0).normal(
        size=(256, 8)).astype(np.float32))
    tickets = [eng.submit("spmm", b) for _ in range(4)]
    submits = _named("repro.engine.submit")
    assert [s.attrs["request"] for s in submits] == [t.id for t in tickets]
    obs.reset()
    for _ in range(4):
        assert eng.step() == 1
        clock.tick(0.25)
    assert eng.step() == 0
    log = sorted(_named("repro.engine."), key=lambda s: s.id)
    for seq in range(4):
        mine = [s.name for s in log if s.attrs.get("batch") == seq]
        assert mine == [f"repro.engine.{n}" for n in ENGINE_STAGES]
    assert [r.seq for r in eng.batch_log] == [0, 1, 2, 3]
    assert [t.batch_seq for t in tickets] == [0, 1, 2, 3]
    run = types.SimpleNamespace(t0=-1.0, t_end=10.0)
    assert _metric("engine_cycle_ms").read(run) == pytest.approx(250.0)
    assert _metric("engine_stage_ms").read(run) == 0.0
    assert _metric("engine_fetch_ms").read(run) == 0.0


def test_worker_idles_in_a_span_only_while_there_is_no_work():
    m = blocked(256, t=32, num_blocks=8, nnz_per_block=64, seed=4)
    eng = sparse.ServingEngine(clock=time.perf_counter)
    eng.register("spmm", sparse.plan(m, sparse.BSpec(d=8, reuse=1024)))
    eng.start()
    try:
        time.sleep(0.05)
        b = jnp.ones((256, 8), jnp.float32)
        eng.submit("spmm", b).result(timeout=30)
    finally:
        eng.stop(drain=True)
    idle = _named("repro.engine.idle")
    dispatch = _named("repro.engine.dispatch")
    assert idle and len(dispatch) == 1
    d = dispatch[0]
    assert not any(s.start < d.end and s.end > d.start for s in idle)
    assert all(s.thread == d.thread == "serving-engine" for s in idle)

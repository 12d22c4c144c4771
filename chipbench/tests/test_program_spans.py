"""The readers of the program's own spans (``yard/spans.py`` and the six
metrics on it) on a hand-made span log, and an idle gap named by a
program span nested in a benchmark span."""
import sys
import types

import pytest

import repro
from repro import obs
from yard import names, trace

T0, T_END = 100.0, 150.0
SETUP = ("classify_s", "score_s", "prepare_s")
WINDOW = ("engine_stage_ms", "engine_cycle_ms", "engine_fetch_ms")


def _span(i, name, start, end, **attrs):
    return obs.Span(i, None, name, start, end, "t", attrs)


def _log():
    """Set-up spans before ``T0``; three engine cycles of 1.1 s in the
    window, an idle second, one more cycle; a batch after the close."""
    spans = [_span(0, "repro.dispatch.classify", 1.0, 4.0),
             _span(1, "repro.dispatch.score", 4.0, 16.0),
             _span(2, "repro.dispatch.prepare", 20.0, 27.5),
             # A warm-up batch before the window counts for nothing.
             _span(3, "repro.engine.stage", 90.0, 95.0),
             _span(4, "repro.engine.dispatch", 95.0, 95.01)]
    i = 5
    starts = [101.0, 102.1, 103.2, 105.3, 155.0]
    for b, t in enumerate(starts):
        spans += [_span(i, "repro.engine.dispatch", t, t + 0.01, batch=b),
                  _span(i + 1, "repro.engine.stage", t + 0.01, t + 0.31,
                        batch=b + 1),
                  _span(i + 2, "repro.engine.fetch", t + 0.7, t + 0.86,
                        batch=b)]
        i += 3
    spans.append(_span(i, "repro.engine.idle", 104.3, 105.2))
    return sorted(spans, key=lambda s: s.end)


def _run():
    return types.SimpleNamespace(t0=T0, t_end=T_END)


@pytest.fixture
def log(monkeypatch):
    state = {"spans": _log(), "dropped": 0}
    monkeypatch.setattr(obs, "spans", lambda: list(state["spans"]))
    monkeypatch.setattr(obs, "dropped", lambda: state["dropped"])
    return state


def _read(name):
    return names.load("metrics", name).read(_run())


def test_setup_metrics_sum_the_spans_before_the_window(log):
    assert _read("classify_s") == pytest.approx(3.0)
    assert _read("score_s") == pytest.approx(12.0)
    assert _read("prepare_s") == pytest.approx(7.5)


def test_engine_metrics_take_the_window_only(log):
    # Four batches start inside [T0, T_END]; the warm-up and the batch
    # after the close are left out.
    assert _read("engine_stage_ms") == pytest.approx(300.0)
    assert _read("engine_fetch_ms") == pytest.approx(160.0)
    # Cycles 101.0 -> 102.1 -> 103.2 count; 103.2 -> 105.3 holds the idle
    # second and does not.
    assert _read("engine_cycle_ms") == pytest.approx(1100.0)


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_a_missing_span_reads_none(log, metric):
    log["spans"] = [s for s in log["spans"]
                    if s.name == "repro.engine.idle"]
    assert _read(metric) is None


@pytest.mark.parametrize("metric", SETUP + WINDOW)
def test_drops_the_reading_needs_read_none(log, metric):
    # Records were dropped and the oldest kept closed inside the window:
    # what was dropped may have started there.
    log["spans"] = [s for s in log["spans"] if s.end > T0 + 2]
    log["dropped"] = 7
    assert _read(metric) is None


def test_drops_before_the_window_spare_the_engine_metrics(log):
    log["spans"] = [s for s in log["spans"] if s.start >= 90.0]
    log["dropped"] = 3
    for metric in SETUP:
        assert _read(metric) is None
    assert _read("engine_stage_ms") == pytest.approx(300.0)
    assert _read("engine_cycle_ms") == pytest.approx(1100.0)


def test_a_program_without_span_log_reads_none(monkeypatch):
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for metric in SETUP + WINDOW:
        assert _read(metric) is None


def test_a_gap_is_named_by_the_program_span_inside_the_benchmarks():
    """The serve cell's result thread holds ``chipbench.result`` open all
    window; a program span open at a gap's midpoint is the inner one."""
    events = {"devices": {"/device:TPU:0": [("k", 0, 100), ("k", 400, 100),
                                            ("k", 800, 100)]},
              "transfers": [], "lines": {},
              "spans": [("chipbench.window", 0, 1000),
                        ("chipbench.result", 0, 1000),
                        ("repro.engine.fetch", 100, 200),
                        ("repro.engine.idle", 550, 200)]}
    red = trace.reduce(events)
    assert red["idle_gaps"] == [["repro.engine.fetch", 300e-9],
                                ["repro.engine.idle", 300e-9],
                                ["chipbench.result", 100e-9]]

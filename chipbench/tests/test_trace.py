"""The trace reduction (``yard.trace.reduce``) on hand-made events with
known answers, and on a small trace recorded on the chip."""
import json
from pathlib import Path

import pytest

from yard import trace

DATA = Path(__file__).resolve().parent / "data"


def _events(ops, spans=(), transfers=(), window=(0, 1000)):
    lo, hi = window
    return {"devices": {"/device:TPU:0": list(ops)},
            "transfers": list(transfers),
            "spans": [("chipbench.window", lo, hi - lo), *spans],
            "lines": {}}


def test_busy_is_the_union_clipped_to_the_window():
    ops = [("a", -50, 100),          # 0..50 inside
           ("b", 20, 60),            # overlaps a: 0..80 in all
           ("c", 200, 100),          # 200..300
           ("d", 950, 200)]          # 950..1000 inside
    red = trace.reduce(_events(ops))
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(230e-9)
    assert red["idle_pct"] == pytest.approx(77.0)
    assert red["spmm_device_s"] == pytest.approx(230e-9)


def test_transfers_are_counted_apart():
    ops = [("fusion", 0, 100), ("TransferToDevice", 100, 100)]
    red = trace.reduce(_events(ops, transfers=[("h2d", 300, 50)]))
    assert red["busy_s"] == pytest.approx(200e-9)
    assert red["spmm_device_s"] == pytest.approx(100e-9)
    assert red["copy_s"] == pytest.approx(50e-9)


def test_top_ops_and_gaps_named_by_innermost_span():
    ops = [("k", 0, 100), ("k", 400, 100), ("m", 600, 50)]
    spans = [("chipbench.wait", 90, 320), ("chipbench.execute", 150, 10),
             ("chipbench.submit", 700, 250)]
    red = trace.reduce(_events(ops, spans=spans))
    assert red["top_ops"] == [["k", 200e-9], ["m", 50e-9]]
    # Gaps: 100..400 (mid 250: only wait is open), 650..1000 (mid 825:
    # submit), 500..600 (mid 550: nothing open).
    assert red["idle_gaps"] == [["chipbench.submit", 350e-9],
                                ["chipbench.wait", 300e-9],
                                ["none", 100e-9]]


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "transfers": [], "lines": {},
                      "spans": [("chipbench.window", 0, 10)]})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {"d": [("k", 0, 1)]}, "transfers": [],
                      "lines": {}, "spans": []})


def test_recorded_chip_trace():
    """lj.replay traced on one TPU v5 lite: 17 csr_spmm launches fill the
    window, and the roofline share is the compulsory bytes' least time
    over their device time, as the run reported it."""
    from yard import peaks, work
    with open(DATA / "lj_replay_events.json") as f:
        events = json.load(f)
    meta = events["meta"]
    red = trace.reduce(events)
    assert red["busy_s"] == pytest.approx(meta["busy_s_reported"])
    assert red["window_s"] == pytest.approx(meta["window_s_reported"])
    assert red["idle_pct"] == pytest.approx(meta["idle_pct_reported"])
    assert red["spmm_device_s"] == pytest.approx(red["busy_s"])
    assert red["copy_s"] == 0
    assert [name for name, _ in red["top_ops"]] == ["csr_spmm.1"]
    flops, nbytes = work.compulsory(meta["nnz"], meta["nonempty_cols"],
                                    meta["n"], meta["d"])
    least, bound = work.least_time(flops, nbytes, peaks.peak("TPU v5 lite"))
    assert bound == "bytes"
    share = 100 * meta["calls"] * least / red["spmm_device_s"]
    assert share == pytest.approx(meta["roofline_pct_reported"])
    assert 0 < share < 100

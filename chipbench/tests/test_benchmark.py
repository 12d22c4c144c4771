"""Every name in BENCHMARK.json finds its file, and each file parses."""
import json
import re

import pytest

from yard import names

BENCH = names.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_resolve(cell):
    cfg = names.config(BENCH, cell["config"])
    traffic = names.traffic(cell["traffic"])
    assert names.load("drivers", traffic["driver"]).run
    assert names.load("generators", cfg["generator"]["kind"]).generate
    assert cfg["limits"]["max_scaled_err"] > 0
    reported = {m["name"] for m in names.cell_metrics(BENCH, cell["name"],
                                                      "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert names.cell_metrics(BENCH, cell["name"], "per_layer")


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert NAME.match(metric["name"])
    assert names.load("metrics", metric["name"]).read
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "moves" in metric:
        moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
        assert moved
        # Every cell the metric reads in reports what it moves.
        assert set(metric["workloads"]) <= set(
            moved[0].get("workloads", cells))


def test_configs_state_their_cuts():
    for c in BENCH["configs"]:
        with open(names.ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["n"] != cfg["source"]["n"]

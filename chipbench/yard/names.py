"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, load driver,
operator generator or metric sits in a file of its own, so a later cell
adds files and entries and edits none:

* configuration: ``BENCHMARK.json`` ``configs[].file``;
* traffic mix:   ``chipbench/traffic/<traffic>.json``;
* load driver:   ``chipbench/drivers/<traffic["driver"]>.py``;
* generator:     ``chipbench/generators/<config["generator"]["kind"]>.py``;
* metric:        ``chipbench/metrics/<metric name>.py``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent        # chipbench/
ROOT = HERE.parent                                    # the checkout


def benchmark() -> dict:
    """The parsed ``BENCHMARK.json`` at the root of the checkout."""
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    """The ``workloads`` entry called ``name``."""
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    """The configuration file of the ``configs`` entry called ``name``."""
    for c in bench["configs"]:
        if c["name"] == name:
            with open(ROOT / c["file"]) as f:
                return {**json.load(f), "name": name}
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def load(kind: str, name: str):
    """Import ``chipbench/<kind>/<name>.py`` as a module.

    Loaded by path, so a name may hold dots (``roofline_pct.replay``).
    """
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell_name: str, section: str) -> list:
    """The ``section`` (``end_to_end`` / ``per_layer``) metrics that
    ``cell_name`` reports: those listing it, or listing no cells."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]

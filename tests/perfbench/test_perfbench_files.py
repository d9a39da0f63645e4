"""Every cell, configuration, traffic file and metric reader that
BENCHMARK.json names is found by name and parses."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = ("hidden", "intermediate", "latent", "state", "projection", "head",
          "expansion", "experts_per_tok")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("perfbench/configs/")
    data = json.loads((ROOT / entry["file"]).read_text())
    assert data["name"] == entry["name"]
    assert data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"]
    assert "assumed" in data
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTHS)


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_cell_resolves(entry):
    from perfbench.cell import Cell

    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    cell = Cell.load(entry["name"], ROOT)
    module = importlib.import_module(
        f"perfbench.families.{cell.traffic['family']}")
    assert hasattr(module, "Family")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert set(cell.traffic["limits"]) and all(
        v > 0 for v in cell.traffic["limits"].values())


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_metric_reader(metric):
    from perfbench.cell import load_reader

    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(load_reader(metric["name"]))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_bounds_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        cells = set(moved.get("workloads",
                              [w["name"] for w in BENCH["workloads"]]))
        assert set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("name", ["roofs.fp_cio", "roofs.ba_cio"])
def test_budgets_come_from_the_configuration(name):
    from perfbench.cell import Cell
    from perfbench.run import settings_from

    cell = Cell.load(name, ROOT)
    settings = settings_from(cell.config, cell.traffic)
    budgets = cell.config["budgets"]
    assert (settings.max_invocations, settings.max_iterations,
            settings.max_time_s) == (budgets["max_invocations"],
                                     budgets["max_iterations"],
                                     budgets["max_time_s"])
    clash = dict(cell.traffic, settings=dict(
        cell.traffic["settings"],
        max_invocations=budgets["max_invocations"] + 1))
    with pytest.raises(ValueError, match="max_invocations"):
        settings_from(cell.config, clash)

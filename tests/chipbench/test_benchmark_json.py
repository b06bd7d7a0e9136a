"""BENCHMARK.json against the files the harness finds by name: every entry has
its file, and what the two say about a metric agrees."""
import importlib
import json
import os

import pytest

import chipbench
from chipbench import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(chipbench.__file__)))


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_has_exactly_the_contracts_keys(bench):
    assert sorted(bench) == sorted(["command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"])
    assert bench["command"][-2:] == ["-m", "chipbench"]
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_cell_and_configuration_has_its_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        data, config = harness.load_cell(cell["name"])
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert data["config"] == cell["config"] and cell["config"] in configs
        assert data["chips"] == cell["chips"] and data["why"] == cell["why"]
        assert len(cell["why"]) <= 200
        entry = configs[cell["config"]]
        assert entry["file"] == f"chipbench/configs/{cell['config']}.json"
        assert entry["source"] == config["source"]
        assert entry["reduced"] == config["reduced"]
    assert {c["config"] for c in bench["workloads"]} == set(configs)
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def _cells_of(metric, bench):
    return set(metric.get("workloads") or [c["name"] for c in bench["workloads"]])


def test_end_to_end_metrics_are_what_the_drivers_report(bench):
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert by_name["setup_s"]["bound"] <= 0.1 and "workloads" not in by_name["setup_s"]
    for cell in bench["workloads"]:
        data, _ = harness.load_cell(cell["name"])
        driver = importlib.import_module(f"chipbench.drivers.{data['driver']}")
        listed = {n: m for n, m in by_name.items()
                  if n != "setup_s" and cell["name"] in _cells_of(m, bench)}
        assert {n: m["unit"] for n, m in listed.items()} == driver.END_TO_END
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_have_a_reader_that_agrees(bench):
    readers = {m.NAME: m for m in harness.layer_metric_modules()}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        reader = readers[m["name"]]
        assert (m["unit"], m["layer"], m["moves"]) == \
            (reader.UNIT, reader.LAYER, reader.MOVES)
        # the metric it moves is reported in every cell where it is
        assert _cells_of(m, bench) <= _cells_of(e2e[m["moves"]], bench)
        for cell in _cells_of(m, bench):     # the cell's kind of run is read
            driver = importlib.import_module(
                "chipbench.drivers." + harness.load_cell(cell)[0]["driver"])
            assert driver.KIND in reader.KINDS
    # what lists gpt1.decode_chat lists gpt1.decode_long: one model, two mixes
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", [])
        assert ("gpt1.decode_chat" in listed) == ("gpt1.decode_long" in listed)
    for cell in bench["workloads"]:      # each cell has a per-layer metric
        assert any(cell["name"] in _cells_of(m, bench)
                   for m in bench["per_layer"])

"""BENCHMARK.json against the contract it is written to, and against the
files the harness will look for: a malformed entry is refused before any
run, so it is caught here, on the CPU, first."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    # 2 + 14 x cells runs, each run_seconds + 60, 180 s a cell to compile,
    # 1200 s spare, must fit 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_command_names_nothing_outside_paths():
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        assert 1 <= len(word) <= 200


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for key in config["reduced"]:       # no width is ever cut
        assert not re.search(r"(_dim|_rank|hidden|inner|embd|head_size)",
                             key)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    assert (ROOT / "benchmark" / "drivers" / f"{mix['driver']}.py").exists()


def test_cells_are_distinct_and_few_take_four_chips():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)


def _cells_of(metric):
    return set(metric.get("workloads",
                          [w["name"] for w in BENCH["workloads"]]))


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_moves_a_reported_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200
    reader = ROOT / "benchmark" / "layer_metrics" / f"{metric['name']}.py"
    assert reader.exists(), f"no reader {reader}"
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    # a per-layer metric is reported only where the metric it moves is
    assert _cells_of(metric) <= _cells_of(moved)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    from benchmark.run import metrics_of_cell

    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for cell in BENCH["workloads"]:
        e2e = {m["name"] for m in metrics_of_cell(BENCH, cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert metrics_of_cell(BENCH, cell, "per_layer")


def test_files_under_paths_have_contract_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in BENCH["paths"]:
        for path in (ROOT / top).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert ok.match(str(path.relative_to(ROOT))), path

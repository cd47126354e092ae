"""Every cell, end to end, on the CPU at its rehearsal sizes: the last line
parses, ``correct`` is true, and no device metric is printed. The four-chip
cell runs on 4 of the virtual CPU devices. And the harness finds a
configuration, a traffic mix and a per-layer metric that were dropped in as
new files, with no edit to a file that was there."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def rehearse(capsys, workload, trace, seed=0):
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "2", "--trace", str(trace),
                       "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_rehearses_end_to_end(capsys, cell):
    line, out = rehearse(capsys, cell["name"], trace=0)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, out[-6:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}            # a CPU run prints no metric
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    want = {m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                       "end_to_end")}
    assert set(line["rehearsal"]["would_report"]) == want


@pytest.mark.parametrize("name", ["train_gpt2_355m_dp4",
                                  "serve_gpt2_124m_batch"])
def test_traced_rehearsal_reads_spans_and_counters(capsys, name):
    line, out = rehearse(capsys, name, trace=1, seed=1)
    assert line["correct"] is True, out[-6:]
    assert line["metrics"] == {} and "breakdown" not in line
    would = set(line["rehearsal"]["would_report"])
    # the host-side readers found their spans, gauges and counters; the
    # device-trace readers found no device plane and returned nothing
    assert {"compile_s", "compile_cache_misses"} <= would
    if name.startswith("train"):
        assert "data_wait_pct" in would
        assert "train_device_idle_pct" not in would
    else:
        assert {"batch_slot_occupancy_pct",
                "batch_prefill_share_pct"} <= would


def test_no_accelerator_and_no_rehearsal_flag_is_an_error(capsys):
    rc = harness.main(["--workload", "train_gpt2_355m_1chip", "--seed", "0",
                       "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert not any(line.startswith("{")
                   for line in captured.out.splitlines())
    assert "no accelerator" in captured.err


def test_new_cell_config_mix_and_metric_are_files_plus_entries(
        capsys, tmp_path, monkeypatch):
    """A later PR's view: nothing that is there is edited."""
    import benchmark.layer_metrics as readers

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    # a configuration: its own file of sizes
    cfg = json.loads((ROOT / "benchmark/configs/gpt2_124m.json").read_text())
    cfg["name"] = "gpt2_124m_page4"
    cfg["rehearsal"]["job"]["page_size"] = 4
    (root / "benchmark/configs/gpt2_124m_page4.json").write_text(
        json.dumps(cfg))
    # a traffic mix: a data file the one generator reads (shared prefixes
    # and bursts need no new code)
    mix = json.loads((ROOT / "benchmark/traffic/batch_closed.json")
                     .read_text())
    mix["shared_prefix"] = {"share": 0.5, "length": 8, "n_prefixes": 2}
    mix["rehearsal"]["shared_prefix"] = mix["shared_prefix"]
    mix["warm_programs"] = "all"     # shared prefixes reach skip and resume
    (root / "benchmark/traffic/shared_prefix_closed.json").write_text(
        json.dumps(mix))
    # a per-layer metric: a reader of its own
    extra = tmp_path / "more_readers"
    extra.mkdir()
    (extra / "queue_depth_mean.py").write_text(
        "from benchmark import stats\n\n\n"
        "def read(run):\n"
        "    samples = [(e['ts'], float(e['value'])) for e in run.events\n"
        "               if e.get('name') == 'serving_queue_depth']\n"
        "    return stats.time_weighted_mean(samples, *run.window)\n")
    monkeypatch.setattr(readers, "__path__",
                        list(readers.__path__) + [str(extra)])
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "gpt2_124m_page4", "source": cfg["source"],
        "file": "benchmark/configs/gpt2_124m_page4.json", "reduced": [],
        "why": "smaller pages"})
    bench["workloads"].append({
        "name": "serve_page4_shared", "config": "gpt2_124m_page4",
        "traffic": "shared_prefix_closed", "chips": 1, "why": "a new cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_out_tokens_per_s":
            m["workloads"].append("serve_page4_shared")
    bench["per_layer"].append({
        "name": "queue_depth_mean", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "serving host",
        "moves": "serve_out_tokens_per_s",
        "workloads": ["serve_page4_shared"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH_DIR", root / "benchmark")

    line, out = rehearse(capsys, "serve_page4_shared", trace=1)
    assert line["correct"] is True, out[-6:]
    assert "queue_depth_mean" in line["rehearsal"]["would_report"]
    assert (root / "benchmark_out" / "serve_page4_shared").is_dir()

"""bench.py contract tests — one process, ONE JSON line on stdout for the
driver, a non-zero exit whenever a selected config failed or no chip was
found (unless the CPU was asked for by name)."""

import ast
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402


def _json_lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


@pytest.fixture
def bench_paths(tmp_path, monkeypatch):
    """The bench writes its history and telemetry stream where these point;
    a test run must leave the package and the checkout untouched."""
    monkeypatch.setattr(bench, "HISTORY_PATH", tmp_path / "hist.jsonl")
    monkeypatch.setattr(bench, "TELEMETRY_PATH", tmp_path / "tele.jsonl")
    return tmp_path


def test_bench_starts_no_subprocess():
    """A process that touched JAX holds the chip: the bench is ONE process
    (the probe children, the watchdog parent and the inner are gone)."""
    tree = ast.parse((REPO / "bench.py").read_text())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names} \
        | {n.module.split(".")[0] for n in ast.walk(tree)
           if isinstance(n, ast.ImportFrom) and n.module}
    assert not imported & {"subprocess", "multiprocessing", "signal",
                           "threading"}
    assert "--_inner" not in (REPO / "bench.py").read_text()


def test_no_chip_is_a_failure_not_a_cpu_row(monkeypatch, capsys):
    """JAX_PLATFORMS unset and no TPU: error JSON, non-zero exit, and the
    backend rule's message — never a CPU number under the device metric."""
    monkeypatch.delenv("JAX_PLATFORMS")
    assert bench.main(["--quick"]) == 1
    (line,) = _json_lines(capsys)
    assert line["value"] == 0.0
    assert "did not ask for the CPU by name" in line["error"]


def test_unknown_only_label_fails_before_the_backend(capsys):
    assert bench.main(["--only", "resnet50,nope"]) == 1
    (line,) = _json_lines(capsys)
    assert "unknown --only labels ['nope']" in line["error"]


def test_cpu_by_name_runs_and_failed_config_is_named(bench_paths,
                                                     monkeypatch, capsys):
    """The single-process path on the CPU backend asked for by name: the
    headline reports, a selected config that raises is named in
    `configs_failed`, the exit code is non-zero, the measured configs are
    still reported — and a CPU run never lands in the device history.
    (`measure_config` itself is exercised by test_experiments' smoke runs;
    a canned row keeps this test about bench's own plumbing.)"""
    from distributed_pytorch_training_tpu.experiments import harness

    def measure_config(name, bf16, **kw):
        if not bf16:
            raise RuntimeError("fp32 arm blew up")
        return {"model": name, "bf16": bf16, "samples_per_sec": 80.0,
                "samples_per_sec_chip": 10.0, "mfu_pct": None,
                "chip_peak_tflops_bf16": None, "tflops_per_sec": 0.1,
                "contracts": {"pass": True, "violations": []}}

    monkeypatch.setattr(harness, "measure_config", measure_config)
    rc = bench.main(["--quick"])
    (line,) = _json_lines(capsys)
    assert rc == 1
    assert line["platform"] == "cpu" and line["value"] == 10.0
    assert line["vs_baseline"] is None
    assert [f["label"] for f in line["configs_failed"]] == ["fp32"]
    assert "fp32 arm blew up" in line["configs_failed"][0]["error"]
    assert [c["model"] for c in line["configs"]] == ["resnet18"]
    assert line["telemetry_path"] == str(bench_paths / "tele.jsonl")
    assert (bench_paths / "tele.jsonl").exists()
    assert not (bench_paths / "hist.jsonl").exists()


def test_history_append_writes_jsonl(bench_paths):
    """Every TPU bench appends its full result dict (provenance for the
    README table) to experiments/results/bench_history.jsonl."""
    bench._record_history({"metric": "m", "value": 1.0, "configs": []})
    bench._record_history({"metric": "m", "value": 2.0, "configs": []})
    rows = [json.loads(l) for l in
            (bench_paths / "hist.jsonl").read_text().splitlines()]
    assert [r["value"] for r in rows] == [1.0, 2.0]
    assert all("timestamp" in r for r in rows)


def test_extra_configs_default_to_bf16_unless_they_say_fp32():
    merged = {label: {"bf16": True, **kw}
              for label, _, kw in bench.EXTRA_CONFIGS}
    assert merged["gpt2_124m_fp32"]["bf16"] is False
    assert all(v["bf16"] for k, v in merged.items() if not k.endswith("_fp32"))

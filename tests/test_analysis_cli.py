"""`analysis check` CLI (analysis/__main__.py): the tier-1 gate — the full
rule suite over the repo source AND the canonical config matrix lowered on
the CPU test mesh must exit 0 (ISSUE 3 acceptance).
"""

import json

from distributed_pytorch_training_tpu.analysis.__main__ import main


def test_analysis_check_json_exits_0_on_repo(capsys, devices):
    """THE acceptance test: every AST rule over the repo plus every HLO
    contract in the matrix (dp / zero1 / grad_sync x wires / accum /
    explicit FSDP / the serving decode step), lowered on the 8-device CPU
    mesh — clean, and every contract really evaluated (a matrix of skips
    would be vacuously green)."""
    assert main(["check", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["schema_version"] == 2
    assert report["ok"] is True and report["findings"] == []
    statuses = report["contracts"]
    assert set(statuses) == {"dp", "dp_accum", "zero1", "zero1_bf16",
                             "zero1_int8_mh",
                             "gsync_fp32", "gsync_bf16", "gsync_int8",
                             "gsync_bf16_accum", "gsync_int8_mh",
                             "gsync_int8_mh_accum", "gsync_int8_mh_fused",
                             "gsync_int8_hier", "gsync_int8_hier_accum",
                             "zero1_int8_hier",
                             "fsdp", "fsdp_accum", "fsdp_int8_mh",
                             "fsdp_tp", "fsdp_tp_int8_mh",
                             "serving_paged", "serving_spec",
                             "control_replan",
                             "elastic_reshard",
                             "elastic_grow"}
    assert all(s == "pass" for s in statuses.values()), statuses
    # both engines actually ran, incl. the fsdp rules (ISSUE 7), the
    # serving decode-loop rule (ISSUE 10), the elastic census pins in
    # BOTH directions (ISSUEs 11 + 12), the 2-D TP x FSDP rules
    # (ISSUE 13), the two-tier hier wire rules (ISSUE 16), and the paged
    # serving pool donation rule (ISSUE 17)
    kinds = {r for r in report["rules_run"]}
    assert "shard-map-shim-only" in kinds and "zero1-collectives" in kinds
    assert "fsdp-layer-gather-bound" in kinds
    assert "no-host-sync-in-decode" in kinds
    assert "elastic-reshard-census" in kinds
    assert "elastic-grow-census" in kinds
    assert "tp-psum-signature" in kinds
    assert "hier-tier-signature" in kinds
    assert "paged-pool-donated" in kinds
    assert "fsdp-gather-rides-data-only" in kinds
    assert "span-names-registered" in kinds
    assert "profiler-session-via-stepprofiler-only" in kinds
    # the speculative verify-path donation rule (ISSUE 19)
    assert "spec-verify-donated" in kinds
    # the concurrency discipline pass (ISSUE 18)
    assert "guarded-by" in kinds
    assert "lock-order-acyclic" in kinds
    assert "no-blocking-under-lock" in kinds
    assert "thread-lifecycle" in kinds
    # the control-plane gate (ISSUE 20)
    assert "control-decisions-gated" in kinds


def test_ast_only_is_fast_and_clean(capsys):
    assert main(["check", "--ast-only"]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out
    assert "contract" not in out  # no HLO matrix ran


def test_rules_selection_and_unknown_rule(capsys):
    assert main(["check", "--ast-only", "--rules",
                 "shard-map-shim-only,axis-name-registry"]) == 0
    assert main(["check", "--rules", "no-such-rule"]) == 2
    assert "no-such-rule" in capsys.readouterr().err


def test_unknown_contract_is_a_usage_error(capsys):
    assert main(["check", "--contracts", "warp-drive"]) == 2
    assert "warp-drive" in capsys.readouterr().err


def test_list_prints_catalog_with_rationales(capsys):
    assert main(["check", "--list"]) == 0
    out = capsys.readouterr().out
    for name in ("shard-map-shim-only", "no-impure-calls-in-traced",
                 "no-host-sync-in-step", "axis-name-registry",
                 "grad-sync-bucket-bound", "compressed-wire",
                 "no-fp32-wire", "zero1-collectives", "zero1-sharded-state",
                 "donated-buffers-elided", "no-host-transfer",
                 "dp-sync-present"):
        assert name in out, name
    assert "why:" in out


def test_console_script_is_declared():
    """The pyproject entry point must keep pointing at main (ISSUE 3
    satellite: `analysis` console script)."""
    from pathlib import Path

    pyproject = (Path(__file__).resolve().parent.parent
                 / "pyproject.toml").read_text()
    assert ('analysis = "distributed_pytorch_training_tpu.analysis.'
            '__main__:main"') in pyproject


def test_findings_drive_nonzero_exit(tmp_path, capsys, monkeypatch):
    """A violation anywhere in the linted set must flip the exit code —
    the CLI's one job."""
    from distributed_pytorch_training_tpu.analysis import ast_rules

    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental import shard_map\n")
    monkeypatch.setattr(ast_rules, "iter_source_files",
                        lambda repo=None: [bad])
    assert main(["check", "--ast-only", "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] is False
    assert report["findings"][0]["rule"] == "shard-map-shim-only"


def test_changed_mode_lints_only_the_git_diff(tmp_path, capsys,
                                              monkeypatch):
    """--changed scopes the PER-FILE rules to the git-changed set but
    keeps whole-repo rules global: a violation in an unchanged file stays
    invisible to the fast loop, a violation in a changed file flips the
    exit code."""
    from distributed_pytorch_training_tpu.analysis import __main__ as cli
    from distributed_pytorch_training_tpu.analysis import ast_rules

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental import shard_map\n")
    monkeypatch.setattr(ast_rules, "iter_source_files",
                        lambda repo=None: [clean, bad])

    monkeypatch.setattr(cli, "_changed_source_files", lambda: [clean])
    assert main(["check", "--ast-only", "--changed", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True

    monkeypatch.setattr(cli, "_changed_source_files", lambda: [bad])
    assert main(["check", "--ast-only", "--changed", "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["findings"][0]["rule"] == "shard-map-shim-only"


def test_changed_mode_falls_back_to_full_set_without_git(capsys,
                                                         monkeypatch):
    """A broken git invocation must widen the lint, never narrow it:
    _changed_source_files -> None means the full repo runs."""
    import subprocess

    from distributed_pytorch_training_tpu.analysis import __main__ as cli

    def _no_git(*a, **kw):
        raise FileNotFoundError("git")

    monkeypatch.setattr(subprocess, "run", _no_git)
    assert cli._changed_source_files() is None
    assert main(["check", "--ast-only", "--changed"]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_changed_source_files_intersects_the_linted_set(monkeypatch):
    """Paths git reports that are OUTSIDE the linted tree (deleted
    files, tests, tooling) must not reach the AST engine."""
    import subprocess

    from distributed_pytorch_training_tpu.analysis import __main__ as cli
    from distributed_pytorch_training_tpu.analysis.ast_rules import (
        REPO_ROOT, iter_source_files,
    )

    real = sorted(iter_source_files())[0].relative_to(REPO_ROOT)

    class _Out:
        def __init__(self, stdout):
            self.stdout = stdout

    def _git(cmd, **kw):
        if "diff" in cmd:
            return _Out(f"{real}\nno/such/file.py\nnot_python.txt\n")
        return _Out("")

    monkeypatch.setattr(subprocess, "run", _git)
    changed = cli._changed_source_files()
    assert changed == [(REPO_ROOT / real).resolve()]

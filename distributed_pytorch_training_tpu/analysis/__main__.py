"""``python -m distributed_pytorch_training_tpu.analysis check`` — run the
parallelism contract checker (HLO engine over the canonical config matrix +
AST lint engine over the repo source) and exit nonzero on any finding.

Also installed as the ``analysis`` console script (pyproject.toml).

Flags:
  --json             machine-readable report on stdout
  --rules a,b        run only the named rules (see --list)
  --ast-only         skip the HLO matrix (no jax / device init — fast lint)
  --contracts a,b    evaluate only the named contracts from the matrix
  --changed          AST rules on git-changed files only (fast local loop);
                     whole-repo rules (the lock-order graph) and the HLO
                     matrix are unaffected — they are global by nature
  --list             print the rule catalog (name, kind, rationale) and exit

Exit codes: 0 clean, 1 findings, 2 usage error.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

# --json report layout version. 1 was the implicit, unversioned layout;
# 2 added this field (consumers should treat a missing field as 1).
REPORT_SCHEMA_VERSION = 2


def _changed_source_files() -> Optional[List[Path]]:
    """Git-changed .py files (vs HEAD, plus untracked), intersected with
    the linted set. None when git is unavailable — the caller falls back
    to the full set: an incremental mode must never lint LESS than a
    broken git invocation would excuse."""
    import subprocess

    from .ast_rules import REPO_ROOT, iter_source_files

    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"],
            cwd=REPO_ROOT, capture_output=True, text=True,
            check=True, timeout=30).stdout
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard"],
            cwd=REPO_ROOT, capture_output=True, text=True,
            check=True, timeout=30).stdout
    except Exception:  # noqa: BLE001 - not a repo / no git binary
        return None
    names = {ln.strip() for ln in (diff + "\n" + untracked).splitlines()
             if ln.strip().endswith(".py")}
    linted = {p.resolve() for p in iter_source_files()}
    out = []
    for n in sorted(names):
        p = (REPO_ROOT / n).resolve()
        if p in linted and p.exists():
            out.append(p)
    return out


def _ensure_test_mesh() -> None:
    """A CPU run asked for by name (``JAX_PLATFORMS=cpu``) gets the 8-device
    virtual mesh — the tests/conftest.py recipe — so the zero1/grad_sync
    contracts engage. Never sets the platform: any other run keeps whatever
    devices jax resolves. Backend init is lazy, so this takes effect as
    long as no ``jax.devices()`` call has happened yet; a caller whose
    backend is already up (the tier-1 in-process test) keeps its devices."""
    from ..runtime import cpu_requested

    if not cpu_requested():
        return
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass  # backend already up: its device count stands


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="analysis",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("command", choices=["check"],
                   help="'check' runs both engines")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule names to run (default: all)")
    p.add_argument("--contracts", default=None,
                   help="comma-separated contract names from the matrix "
                        "(default: all)")
    p.add_argument("--ast-only", action="store_true",
                   help="skip the HLO config matrix (no jax init)")
    p.add_argument("--changed", action="store_true",
                   help="per-file AST rules on git-changed files only; "
                        "global rules and the HLO matrix still run whole")
    p.add_argument("--list", action="store_true", dest="list_rules",
                   help="print the rule catalog and exit")
    args = p.parse_args(argv)

    from .contracts import CONTRACT_MATRIX, get_contract, iter_rules

    try:
        rule_names = ([r.strip() for r in args.rules.split(",") if r.strip()]
                      if args.rules else None)
        rules = iter_rules(names=rule_names)
    except KeyError as e:
        print(f"analysis: {e.args[0]}", file=sys.stderr)
        return 2

    if args.list_rules:
        for r in rules:
            print(f"{r.name} [{r.kind}]\n  {r.description}\n  why: "
                  f"{r.rationale}\n")
        return 0

    ast_rule_names = [r.name for r in rules if r.kind == "ast"]
    global_rule_names = [r.name for r in rules if r.kind == "ast-global"]
    hlo_rule_names = [r.name for r in rules if r.kind == "hlo"]

    findings = []
    contract_status = {}

    if ast_rule_names or global_rule_names:
        from .ast_rules import run_ast_rules

        changed = _changed_source_files() if args.changed else None
        if args.changed and changed is not None:
            # incremental: per-file rules on the changed set only; the
            # whole-repo rules (lock-order graph) still see every file —
            # a cycle is a property of the union, not of one diff
            if ast_rule_names:
                findings += run_ast_rules(files=changed,
                                          rules=ast_rule_names)
            if global_rule_names:
                findings += run_ast_rules(rules=global_rule_names)
        else:
            findings += run_ast_rules(
                rules=ast_rule_names + global_rule_names)

    if hlo_rule_names and not args.ast_only:
        try:
            contracts = ([get_contract(c.strip())
                          for c in args.contracts.split(",") if c.strip()]
                         if args.contracts else CONTRACT_MATRIX)
        except KeyError as e:
            print(f"analysis: {e.args[0]}", file=sys.stderr)
            return 2
        _ensure_test_mesh()
        from .hlo_rules import run_contract_matrix

        hlo_findings, contract_status = run_contract_matrix(
            contracts=contracts, rules=hlo_rule_names)
        findings += hlo_findings

    if args.as_json:
        print(json.dumps({
            "schema_version": REPORT_SCHEMA_VERSION,
            "ok": not findings,
            "n_findings": len(findings),
            "findings": [f.as_dict() for f in findings],
            "contracts": contract_status,
            "rules_run": [r.name for r in rules],
        }, indent=2, sort_keys=True))
    else:
        for name, status in sorted(contract_status.items()):
            print(f"contract {name}: {status}")
        for f in findings:
            print(str(f))
        print(f"analysis check: {len(findings)} finding(s) from "
              f"{len(rules)} rule(s)"
              + (f", {len(contract_status)} contract(s)"
                 if contract_status else ""))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

"""Shim lint: every shard_map in the repo must go through the one wrapper,
`parallel/collectives.py shard_map` — the entry point moved
(jax.experimental.shard_map -> jax.shard_map) and the replication-check
flag was renamed (check_rep -> check_vma) across jax versions, and every
body here needs the check off; this tier-1 test fails the moment a direct
use lands anywhere else.

MIGRATED onto the AST engine (analysis/ast_rules.py `shard-map-shim-only`,
ISSUE 3): the old regex fired on entry-point MENTIONS inside docstrings and
string literals — prose about the rule tripped the rule. The AST rule only
sees real imports, attribute accesses, and call kwargs, so that false-
positive class is gone structurally (pinned below).
"""

from pathlib import Path

from distributed_pytorch_training_tpu.analysis.ast_rules import (
    SHARD_MAP_SHIM, run_ast_rules,
)

REPO = Path(__file__).resolve().parent.parent
SHIM = REPO / "distributed_pytorch_training_tpu" / "parallel" / "collectives.py"


def test_no_direct_shard_map_outside_collectives_shim():
    offenders = run_ast_rules(rules=["shard-map-shim-only"])
    assert not offenders, (
        "direct jax shard_map entry-point use outside the "
        "parallel/collectives.py shim (import `shard_map` from "
        "distributed_pytorch_training_tpu.parallel instead):\n  "
        + "\n  ".join(str(f) for f in offenders))


def test_docstring_mentions_no_longer_false_positive(tmp_path):
    """The known false-positive class of the regex lint (ISSUE 3
    satellite): a file whose docstrings/strings MENTION the raw entry
    points — exactly what the shim and this test's own docstring do —
    must pass; a real import in the same file must still flag."""
    prose = tmp_path / "prose.py"
    prose.write_text(
        '"""Use jax.shard_map via the shim; never\n'
        'from jax.experimental import shard_map directly."""\n'
        'HINT = "jax.experimental.shard_map.shard_map moved"\n')
    assert run_ast_rules(files=[prose],
                         rules=["shard-map-shim-only"]) == []

    real = tmp_path / "real.py"
    real.write_text('"""Innocent docstring."""\n'
                    "from jax.experimental import shard_map\n")
    found = run_ast_rules(files=[real], rules=["shard-map-shim-only"])
    assert len(found) == 1 and found[0].location.endswith(":2")


def test_shim_is_the_wrapper_the_rule_points_at():
    """The lint is only meaningful while the shim really is the one caller
    of the raw entry point, and the rule keeps pointing at this file."""
    assert "jax.shard_map(" in SHIM.read_text()
    assert SHIM.as_posix().endswith(SHARD_MAP_SHIM)

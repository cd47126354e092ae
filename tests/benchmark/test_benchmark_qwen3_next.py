"""The files the Qwen3-Next cell brought to the benchmark: its rehearsal
(traced, so the host-side readers see the counters), every region name of
`_hybrid_regions` held against the lowered text of the tiny model's train
step, the family's layer check telling a bf16 state in the delta rule from
float32, the closed-form FLOPs against a hand count at the published sizes, the
parameter count of the cut without allocating, and the configuration file
against the catalog's row."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.flops import qwen3_next as flops
from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import (
    GROUPED_PRODUCT, HYBRID_TRAIN_STEP,
)
from distributed_pytorch_training_tpu.models import get_model

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "train_qwen3_next_s8192_1chip"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/qwen3_next_80b_a3b.json").read_text())
HYBRID_METRICS = {
    "hybrid_gdn_rule_ms", "hybrid_gdn_proj_ms", "hybrid_gated_attn_ms",
    "hybrid_moe_route_ms", "hybrid_moe_dispatch_ms", "hybrid_moe_experts_ms",
    "hybrid_head_loss_ms", "hybrid_unscoped_ms"}


def test_traced_rehearsal_is_correct_and_reads_the_counters(capsys):
    rc = harness.main(["--workload", CELL, "--seed", "3000000019",
                       "--seconds", "2", "--trace", "1", "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, out[-8:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and "breakdown" not in line
    would = set(line["rehearsal"]["would_report"])
    # the host-side readers found their spans and counters; the device-trace
    # readers found no device plane and left their metrics out
    assert {"compile_s", "compile_cache_misses", "data_wait_pct",
            "moe_expert_load_max_over_mean",
            "moe_held_assignment_share_pct"} <= would
    assert not (would & HYBRID_METRICS)
    counts = line["rehearsal"]["counts"]
    assert counts["moe_dropped_assignments"] == 0
    assert counts["moe_held_assignments"] > 0
    # the window is the mix's `window_steps` (4 here), or the clock's cut
    assert 0 < counts["steps"] <= 4
    check = next(json.loads(o[5:]) for o in out
                 if o.startswith("note ") and "vs_reference" in o)
    limits = check["tolerances"]
    assert check["logits_rel_diff"] <= limits["logits_rel_tol"]
    assert check["logits_rel_p50"] <= limits["logits_rel_p50_tol"]
    assert check["loss_rel_diff"] <= limits["loss_rel_tol"]
    assert check["rule_rel_diff"] <= limits["rule_rel_tol"]
    # the timed step's gradient, read from AdamW's first moment, along
    # itself: what the reference's loss does there, and no leaf without one
    assert check["step_grad_rel_diff"] <= limits["step_grad_rel_tol"]
    assert counts["step_dead_leaves"] == 0 == len(check["step"]["dead_leaves"])
    assert len(check["step"]["leaf_norms"]) > 40
    by_boundary = next(json.loads(o[5:]) for o in out
                       if o.startswith("note ") and "by_boundary" in o)
    held = by_boundary["counters_per_step_by_boundary"]["moe_held_assignments"]
    assert len(held) >= 2 and all(h > 0 for h in held)


def test_the_cell_reports_every_metric_the_issue_names():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    names = {m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                        "per_layer")}
    assert HYBRID_METRICS | {
        "train_optimizer_ms",   # the experts' AdamW does not fuse here
        "moe_expert_load_max_over_mean", "moe_held_assignment_share_pct",
        "data_wait_pct", "train_mfu_pct", "train_device_idle_pct",
        "flash_fwd_ms", "flash_bwd_ms", "flash_attention_roofline",
        "compile_s", "compile_cache_misses", "peak_hbm_gb"} == names
    e2e = {m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                      "end_to_end")}
    assert e2e == {"train_tokens_per_s_chip", "setup_s"}


# -- region names against the program ------------------------------------------

@pytest.fixture(scope="module")
def train_paths():
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.parallel.sharding import shard_batch
    from distributed_pytorch_training_tpu.training.loop import (
        TrainConfig, Trainer,
    )
    from distributed_pytorch_training_tpu.training.optim import (
        make_optimizer, make_schedule,
    )
    from distributed_pytorch_training_tpu.training.tasks import (
        LanguageModelingTask,
    )

    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    model = get_model("qwen3_next_80b_a3b", dtype=jnp.bfloat16, remat=True,
                      **CONFIG["rehearsal"]["model_overrides"])
    trainer = Trainer(LanguageModelingTask(compute_dtype=jnp.bfloat16), mesh,
                      TrainConfig(per_device_batch=2, bf16=True),
                      rules=type(model).partition_rules())
    state = trainer.init_state(
        model, np.zeros((1, 64), np.int32),
        make_optimizer("adamw", make_schedule("constant", 3e-4)),
        jax.random.PRNGKey(0))
    batch = shard_batch({"input_ids": np.zeros((2, 64), np.int32),
                         "weight": np.ones(2, np.float32)}, mesh)
    lowered = trainer._train_step.lower(state, batch, jax.random.PRNGKey(0))
    return set(re.findall(r'loc\("(jit\([^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("region", [r for r in HYBRID_TRAIN_STEP[1]
                                    if r not in GROUPED_PRODUCT])
def test_train_step_carries_region(train_paths, region):
    found = {_regions.region_of(p, HYBRID_TRAIN_STEP[1]) for p in train_paths}
    assert region in found


def test_grouped_products_are_read_by_the_names_xla_gives_them(train_paths):
    """On the chip a `ragged_dot` keeps no scope path: its custom calls'
    whole path is the operation's own name (two paths recorded from the
    traced run of PR 27). The program's side of it: the grouped products are
    `ragged_dot`s under `moe_experts`."""
    for path in ("ragged-dot-none:", "ragged-dot-metadata:"):
        assert _regions.region_of(path, HYBRID_TRAIN_STEP[1]) \
            in GROUPED_PRODUCT
    assert any("moe_experts/ragged_dot" in p for p in train_paths)
    # unscoped is what is left, `optimizer` is `train_optimizer_ms`'s
    from benchmark.layer_metrics import hybrid_moe_experts_ms as experts
    from benchmark.layer_metrics import hybrid_unscoped_ms as unscoped
    split = dict.fromkeys((*HYBRID_TRAIN_STEP[1], _regions.UNSCOPED,
                           _regions.COLLECTIVE), 1.0)
    run = type("Run", (), {"facts": {"region_splits": {
        "paths": {}, (HYBRID_TRAIN_STEP[0], HYBRID_TRAIN_STEP[1]): split}},
        "trace_data": type("T", (), {"devices": {"d": None}})()})()
    assert experts.read(run) == 4.0 and unscoped.read(run) == 2.0


def test_layer_check_tells_a_bf16_state_in_the_rule_from_float32(monkeypatch):
    """`benchmark/checks/qwen3_next.py` at the rehearsal's head sizes, 512
    positions: the program's rule is within the limit of the reference's
    position-by-position rule, and the same rule on inputs rounded to bf16
    is past it."""
    from benchmark.checks import qwen3_next as checks
    from distributed_pytorch_training_tpu.ops import gated_delta_rule as gdr

    config = {"published": CONFIG["rehearsal"]["published"]}
    limit = CONFIG["correct"]["rule_rel_tol"]
    assert checks.layer_checks(config, {"seq_len": 512}, 3000000019)[
        "rule_rel_diff"] < limit / 10
    exact = gdr._chunked_rule
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    monkeypatch.setattr(gdr, "_chunked_rule", lambda q, k, v, g, beta: exact(
        rounded(q), rounded(k), rounded(v), g, beta))
    assert checks.layer_checks(config, {"seq_len": 512}, 3000000019)[
        "rule_rel_diff"] > 2 * limit


def test_the_rule_lies_inside_the_mixer_and_experts_inside_the_layer(
        train_paths):
    assert any(_regions.region_of(p, ("gdn_rule",)) == "gdn_rule"
               and _regions.region_of(p, ("gdn",)) == "gdn"
               for p in train_paths)
    # backward paths carry the same names (transforms are taken off)
    assert any("transpose" in p and _regions.region_of(
        p, HYBRID_TRAIN_STEP[1]) == "moe_experts" for p in train_paths)


# -- the closed forms ------------------------------------------------------------

def test_flops_against_a_hand_count_at_the_published_sizes():
    """By hand, from the source's numbers (M = 1e6 weights):
    Gated DeltaNet layer: 2048 x 12288 + 2048 x 64 + 4096 x 2048 = 33.685504M;
    attention layer: 2048 x 8192 + 2 x 2048 x 512 + 4096 x 2048 = 27.262976M;
    each layer's sparse block: router 1.048576M + shared 3.145728M + its gate
    0.002048M + routed 10 x 32/512 x 3.145728M = 1.96608M: 6.162432M;
    head 18992 x 2048 = 38.895616M. Three + one + four + head: 191.864832M.
    Delta rule: 3 layers x 32 heads x 3 products x 2 x 128 x 128 = 9.437184
    MFLOP a token forward. Attention: 6 x 8192 x 4096 = 201.326592 MFLOP."""
    assert flops.matmul_weights_per_token(CONFIG) == pytest.approx(
        3 * 33.685504e6 + 27.262976e6 + 4 * 6.162432e6 + 38.895616e6)
    assert flops.delta_rule_flops_per_token(CONFIG) == pytest.approx(
        9.437184e6)
    assert flops.train_flops_per_token(CONFIG, 8192) == pytest.approx(
        6 * 191.864832e6 + 3 * 9.437184e6 + 201.326592e6)
    assert flops.train_shape(CONFIG, 1, 8192, "flash") == dict(
        batch=1, seq_len=8192, heads=16, head_dim=256, layers=1,
        attention="flash")
    assert flops.moe_assignments_per_token(CONFIG) == 40
    # uncut: every expert held, the whole vocabulary, 48 layers
    whole = {"published": CONFIG["published"]}
    assert flops.matmul_weights_per_token(whole) == pytest.approx(
        36 * 33.685504e6 + 12 * 27.262976e6
        + 48 * (1.048576e6 + 3.145728e6 + 0.002048e6 + 10 * 3.145728e6)
        + 151936 * 2048)


def test_parameter_count_of_the_cut_without_allocating():
    """625.7M by the issue's arithmetic (vocabulary rows unpadded); the
    program pads 18,992 rows to 19,072: 626.0M, inside 1%."""
    model = get_model(CONFIG["registry_model"], **CONFIG["model_overrides"])
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert abs(count - 625.7e6) / 625.7e6 < 0.01
    assert shapes["layer0"]["moe"]["gate"].shape == (32, 2048, 512)
    assert shapes["layer0"]["moe"]["router"].shape == (2048, 512)
    assert shapes["head"]["kernel"].shape == (2048, 19072)
    assert [("gated_attn" in shapes[f"layer{i}"]) for i in range(4)] == [
        False, False, False, True]


def test_configuration_file_holds_the_catalog_row():
    """Every number of the source's config stands in the file under the same
    key, at the top level and in `published`; only a key `reduced` lists
    differs; no width is among them."""
    published = CONFIG["published"]
    assert published["num_hidden_layers"] == 48
    assert published["num_experts"] == 512
    assert published["vocab_size"] == 151936
    differing = {k for k, v in published.items() if CONFIG[k] != v}
    assert differing <= set(CONFIG["reduced"])
    assert CONFIG["model_overrides"] == {
        k: CONFIG[k] for k in CONFIG["reduced"]} == {
        "depth": 4, "num_experts_held": 32, "vocab_size": 18992}
    # the floors: a whole period, >= 8 experts, >= an eighth of the rows
    assert CONFIG["depth"] % published["full_attention_interval"] == 0
    assert CONFIG["num_experts_held"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    # the registry's defaults ARE the published sizes
    model = get_model(CONFIG["registry_model"])
    for ours, theirs in {
            "hidden_dim": "hidden_size", "depth": "num_hidden_layers",
            "num_heads": "num_attention_heads",
            "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "num_experts": "num_experts", "vocab_size": "vocab_size",
            "num_experts_per_tok": "num_experts_per_tok",
            "moe_intermediate_size": "moe_intermediate_size",
            "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
            "linear_num_value_heads": "linear_num_value_heads",
            "linear_key_head_dim": "linear_key_head_dim"}.items():
        assert getattr(model, ours) == published[theirs], ours

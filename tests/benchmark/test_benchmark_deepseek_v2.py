"""The files the DeepSeek-V2 cell brought to the benchmark: its traced
rehearsal (so the host-side readers see spans, gauges and the engine's step
counters), every region name of `_dsv2_regions` held against the lowered text
of the tiny model's decode and prefill programs on both reads, the closed
forms against a hand count at the published sizes, the parameter count of the
cut without allocating, the configuration file against the catalog's row,
the new readers on a run that has nothing for them (the parent's program),
and the family's layer check telling a rounded cache from a whole one."""

import json
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import run as harness
from benchmark.checks import deepseek_v2 as checks
from benchmark.flops import deepseek_v2 as flops
from benchmark.layer_metrics import _dsv2_regions, _regions
from distributed_pytorch_training_tpu.models import get_model

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "serve_deepseek_v2_long_prompt_batch"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/deepseek_v2_236b_a21b.json").read_text())
NEW_METRICS = {
    "dsv2_decode_step_ms", "dsv2_decode_mla_attn_ms",
    "dsv2_decode_mla_proj_ms", "dsv2_decode_moe_ms", "dsv2_decode_other_ms",
    "dsv2_prefill_ms_per_ktoken", "dsv2_prefill_mla_attn_share_pct",
    "dsv2_prefill_flash_ms", "mla_decode_roofline",
    "dsv2_moe_held_share_pct", "dsv2_device_idle_pct",
    "dsv2_slot_occupancy_pct", "dsv2_prefill_share_pct"}


def test_traced_rehearsal_is_correct_and_reads_spans_and_counters(capsys):
    rc = harness.main(["--workload", CELL, "--seed", "3000000019",
                       "--seconds", "2", "--trace", "1", "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, out[-8:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and "breakdown" not in line
    would = set(line["rehearsal"]["would_report"])
    assert {"compile_s", "compile_cache_misses", "dsv2_moe_held_share_pct",
            "dsv2_slot_occupancy_pct", "dsv2_prefill_share_pct"} <= would
    # the device-trace readers found no device plane and left theirs out
    assert not would & {"dsv2_decode_step_ms", "mla_decode_roofline",
                        "dsv2_prefill_ms_per_ktoken"}
    counts = line["rehearsal"]["counts"]
    assert counts["moe_dropped_assignments"] == 0
    limits = CONFIG["correct"]
    for name, tol in limits.items():
        if name.endswith("_tol"):
            assert counts[name[:-4]] <= tol, name


def test_the_cell_reports_every_metric_the_issue_names():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "long_prompt_closed"
    names = {m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                        "per_layer")}
    assert NEW_METRICS | {"batch_decode_step_ms"} >= names - {
        "compile_s", "compile_cache_misses", "peak_hbm_gb"}
    assert NEW_METRICS <= names
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tokens_per_s"
    e2e = {m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                      "end_to_end")}
    assert e2e == {"serve_out_tokens_per_s", "setup_s"}
    assert [m["name"] for m in BENCH["per_layer"][-13:]] == [
        m["name"] for m in BENCH["per_layer"] if m["name"] in NEW_METRICS]


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = json.loads(
        (ROOT / "benchmark/traffic/long_prompt_closed.json").read_text())
    assert mix["driver"] == "serve_lm"
    assert (mix["loop"], mix["clients_per_row"]) == ("closed", 2)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert mix["output_len"] == {"dist": "uniform", "min": 384, "max": 640}
    assert mix["shared_prefix"] == {"share": 0.0}
    assert mix["plan_requests_per_s"] == 20 and mix["rows"] % 16 == 0
    assert (mix["ramp_s"], mix["grace_s"], mix["trace_seconds"]) == (
        30.0, 0.0, 3.0)
    assert mix["warm_programs"] == ["paged_decode", "paged_prefill"]


# -- region names against the programs -----------------------------------------

@pytest.fixture(scope="module", params=["gather", "kernel"])
def program_paths(request):
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.serving import continuous
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )

    real = continuous.paged_attention_backend_supported
    continuous.paged_attention_backend_supported = \
        lambda: request.param == "kernel"
    try:
        model = get_model("deepseek_v2_236b_a21b", dtype=jnp.bfloat16,
                          **CONFIG["rehearsal"]["model_overrides"])
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        params = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), params)
        engine = continuous.SlotEngine(
            model, build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]),
            PagedServeConfig(buckets=(16,), rows=4, max_new_tokens=8,
                             page_size=8, serve_dtype="bf16"), params)
        assert engine.kv_path == request.param
        texts = {"decode": engine.lower_paged_decode().as_text(
            debug_info=True), "prefill": engine.lower_paged_prefill(
                16).as_text(debug_info=True)}
    finally:
        continuous.paged_attention_backend_supported = real
    return request.param, {
        name: set(re.findall(r'loc\("(jit\([^"]*)"', text))
        for name, text in texts.items()}


def test_every_region_name_is_in_the_programs(program_paths):
    read, paths = program_paths
    decode = {_regions.region_of(p, _dsv2_regions.DSV2_DECODE[1])
              for p in paths["decode"]}
    prefill = {_regions.region_of(p, _dsv2_regions.DSV2_PREFILL[1])
               for p in paths["prefill"]}
    shared = {"mla_attn", "mla_proj", "moe_route", "moe_dispatch",
              "moe_experts", "shared_expert", "dense_mlp", "kv_scatter",
              "embed", "final_norm", "head", "sample"}
    assert shared | {"bookkeeping", "model"} <= decode
    assert shared <= prefill
    # the decode kernel by its own name on the kernel read, the views'
    # gather on the other; the CPU's prefill runs the XLA attention
    assert (_dsv2_regions.MLA_KERNEL in decode) == (read == "kernel")
    assert ("kv_gather" in decode) == (read == "gather")
    assert any(p.startswith("jit(decode)/") for p in paths["decode"])
    assert any(p.startswith("jit(prefill)/") for p in paths["prefill"])
    # every named region is one of the four readers' groups, once
    groups = (_dsv2_regions.ATTN, _dsv2_regions.PROJ, _dsv2_regions.MOE,
              _dsv2_regions.OTHER)
    flat = [r for g in groups for r in g]
    assert len(flat) == len(set(flat))
    assert set(flat) == set(_dsv2_regions.DSV2_DECODE[1]) | {
        _regions.UNSCOPED, _regions.COLLECTIVE}


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent's program under this benchmark: no trace, no such span,
    counter or fact. Every new reader says None and raises nothing."""
    import importlib

    run = types.SimpleNamespace(
        trace_data=None, events=[], facts={}, window=(0.0, 1.0), peaks=None,
        config=CONFIG, out_dir=ROOT, note=lambda **_: None)
    for name in sorted(NEW_METRICS):
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.read(run) is None, name


# -- the closed forms ----------------------------------------------------------

def test_parameters_and_flops_by_hand():
    sizes = CONFIG["published"]
    attention = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 \
        + 512 * 128 * 256 + 128 * 128 * 5120
    assert flops.attention_weights(sizes) == attention == 149_225_472
    expert = 3 * 5120 * 1536
    dense_layer = attention + 3 * 5120 * 12288
    expert_layer = attention + 5120 * 160 + 2 * expert + 20 * expert
    want = dense_layer + 5 * expert_layer + 2 * 12800 * 5120
    assert flops.parameters(CONFIG) == want == 3_814_490_112
    # what the program would hold, without allocating (norms besides)
    model = get_model("deepseek_v2_236b_a21b", dtype=jnp.bfloat16,
                      **CONFIG["model_overrides"])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves = jax.tree_util.tree_leaves(shapes)
    held = sum(leaf.size for leaf in leaves)
    norms = 6 * (2 * 5120 + 1536 + 512) + 5120
    assert held == want + norms
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype(jnp.bfloat16)}
    routed = 6 * 20 / 160 * expert
    per_token = 6 * attention + 3 * 5120 * 12288 \
        + 5 * (5120 * 160 + 2 * expert + routed) + 12800 * 5120
    assert flops.matmul_weights_per_token(CONFIG) == pytest.approx(per_token)
    assert flops.prefill_flops_per_token(CONFIG, 3072) == pytest.approx(
        2 * per_token + 6 * 128 * 320 * 3072)
    assert flops.moe_assignments_per_token(CONFIG) == 30
    cost = flops.mla_decode_call_cost(sizes, 128, 128 * 3300.0)
    assert cost["flops"] == pytest.approx(2 * 128 * 1088 * 128 * 3300)
    assert cost["bytes"] == pytest.approx(
        (128 * 3300 * 576 + 128 * 128 * 1088) * 2)
    assert cost["flops"] / cost["bytes"] == pytest.approx(225.27, rel=1e-3)


def test_configuration_file_against_the_catalogs_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "DeepSeek-V2")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["published"] == row["config"]
    assert CONFIG["reduced"] == ["depth", "num_experts_held", "vocab_size"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    cut = CONFIG["model_overrides"]
    assert (cut["depth"], cut["num_experts_held"], cut["vocab_size"]) == (
        CONFIG["depth"], CONFIG["num_experts_held"], CONFIG["vocab_size"]) \
        == (6, 20, 12800)
    # the floors: four expert layers after the dense one, 8 experts, 1/8
    assert cut["depth"] - row["config"]["first_k_dense_replace"] >= 4
    assert cut["num_experts_held"] >= 8
    assert cut["vocab_size"] * 8 >= row["config"]["vocab_size"]
    job = CONFIG["job"]
    assert job["buckets"] == [2560, 3072, 3584, 4096]
    assert job["max_new_tokens"] == 640 and job["prefix_skip"] is False
    assert job["page_size"] in (16, 32, 64)


def test_layer_check_tells_a_rounded_cache_from_a_whole_one(monkeypatch):
    """`kernel_rel_diff` at the rehearsal's sizes: the program's absorbed
    read against the expanded float32 form is rounding apart; with the
    cached rows rounded to float8_e4m3 on the way in it is far over the
    limit."""
    config = harness._merge(CONFIG, CONFIG["rehearsal"])
    traffic = {"rows": 4}
    whole = checks.layer_checks(config, traffic, seed=5)
    assert whole["kernel_read"] == "gather"
    assert whole["kernel_rel_diff"] <= CONFIG["correct"]["kernel_rel_diff_tol"]
    from distributed_pytorch_training_tpu.models import layers

    real = layers.scatter_paged_window

    def rounded(pool, table, positions, c, pe, active):
        low = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)  # noqa: E731
        return real(pool, table, positions, low(c), low(pe), active)

    monkeypatch.setattr(layers, "scatter_paged_window", rounded)
    low = checks.layer_checks(config, traffic, seed=5)
    assert low["kernel_rel_diff"] > CONFIG["correct"]["kernel_rel_diff_tol"]
    assert low["kernel_rel_diff"] > 5 * whole["kernel_rel_diff"]

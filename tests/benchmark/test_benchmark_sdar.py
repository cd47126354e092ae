"""The files the SDAR cell brought to the benchmark: its traced rehearsal (so
the host-side readers see spans, counters and the engine's step counters),
the cell's entries and its traffic letter for letter against ISSUE 42, every
region name of `_sdar_regions` held against the lowered text of the tiny
model's block step and prefill on both reads, the closed forms against a
hand count at the published sizes, the parameter count of the cut without
allocating, the configuration file against the catalog's row, the new readers
on a run that has nothing for them (the parent's program), the driver's replay
of a trajectory, and the family's layer check telling a rounded cache from a
whole one."""

import importlib
import json
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.checks import sdar as checks
from benchmark.drivers import serve_block_diffusion as driver
from benchmark.flops import sdar as flops
from benchmark.layer_metrics import _regions, _sdar_regions
from distributed_pytorch_training_tpu.models import get_model

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "serve_sdar_block_diffusion_batch"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/sdar_30b_a3b_chat.json").read_text())
MIX = json.loads(
    (ROOT / "benchmark/traffic/block_diffusion_closed.json").read_text())
NEW_METRICS = [
    "sdar_block_step_ms", "sdar_block_attn_ms", "sdar_block_moe_ms",
    "sdar_block_head_unmask_ms", "sdar_block_other_ms",
    "sdar_window_attention_roofline", "sdar_positions_per_token",
    "sdar_moe_load_max_over_mean", "sdar_prefill_share_pct",
    "sdar_device_idle_pct", "sdar_slot_occupancy_pct"]


@pytest.fixture(scope="module")
def rehearsal():
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", CELL, "--seed", "3000000019",
                           "--seconds", "2", "--trace", "1", "--rehearsal"])
    return rc, out.getvalue().strip().splitlines()


def test_traced_rehearsal_is_correct_and_reads_spans_and_counters(rehearsal):
    rc, out = rehearsal
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, out[-8:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and "breakdown" not in line
    would = set(line["rehearsal"]["would_report"])
    assert {"compile_s", "compile_cache_misses", "sdar_positions_per_token",
            "sdar_moe_load_max_over_mean", "sdar_slot_occupancy_pct",
            "sdar_prefill_share_pct"} <= would
    # the device-trace readers found no device plane and left theirs out
    assert not would & {"sdar_block_step_ms", "sdar_block_moe_ms",
                        "sdar_window_attention_roofline",
                        "sdar_device_idle_pct"}
    counts = line["rehearsal"]["counts"]
    assert counts["moe_dropped_assignments"] == 0
    for name, tol in CONFIG["correct"].items():
        if name.endswith("_tol"):
            assert counts[name[:-4]] <= tol, name


@pytest.mark.parametrize("reading", [
    "token_gap", "choice_gap_mean", "logits_rel_p50", "logits_rel",
    "kernel_rel_diff"])
def test_every_reading_has_its_limit_its_reason_and_its_two_readings(
        rehearsal, reading):
    limits = CONFIG["correct"]
    assert 0 < limits[f"{reading}_tol"] < 1
    why = limits["why"][f"{reading}_tol"]
    assert "Found" in why and "float8" in why
    check = next(json.loads(line[5:]) for line in rehearsal[1]
                 if line.startswith('note {"check": "limits"'))
    assert check["within"][reading] is True
    # the readings no limit lies between are noted, with the reason
    assert {"token_gap_p50", "choice_gap", "choice_gap_p50"} <= set(
        check["found"])
    assert "no limit" in limits["why"]["choice_gap"]
    assert "no limit" in limits["why"]["token_gap_tol"]


def test_the_rehearsal_counts_what_the_scheduler_forwarded(rehearsal):
    notes = [json.loads(line[5:]) for line in rehearsal[1]
             if line.startswith("note ")]
    served = next(n for n in notes if n.get("check") == "server_vs_reference")
    assert {n % 4 for n in served["prompt_lens"]} == {0, 1, 2, 3}
    assert any(w % 4 for w in served["new_tokens"])
    assert served["token_counts_ok"] is True
    counters = next(n["step_counters"] for n in notes
                    if "step_counters" in n)
    assert counters["steps"] > 0 and counters["moe_dropped_assignments"] == 0
    # every row's window routes: rows x B positions x top-k x layers a step
    assert counters["moe_held_assignments"] == counters["steps"] * 4 * 4 * 8


def test_the_cells_entries_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "sdar_30b_a3b_chat", "block_diffusion_closed")
    assert len(cell["why"]) <= 200
    for said in ("176 rows", "U(128,512)", "U(512,1024)", "B 4", "T 4",
                 "tokens an expert"):
        assert said in cell["why"], said
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "sdar_30b_a3b_chat")
    assert entry == {
        "name": "sdar_30b_a3b_chat", "source": CONFIG["source"],
        "file": "benchmark/configs/sdar_30b_a3b_chat.json",
        "reduced": ["depth"], "why": entry["why"]}
    assert len(entry["why"]) <= 200
    names = [m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                        "per_layer")]
    assert set(names) == set(NEW_METRICS) | {
        "compile_s", "compile_cache_misses", "peak_hbm_gb"}
    # appended after every entry the benchmark had, in the issue's order
    # (and no pin on the list's END: the next PR appends after these)
    listed = [m["name"] for m in BENCH["per_layer"]]
    first = listed.index(NEW_METRICS[0])
    assert listed[first:first + len(NEW_METRICS)] == NEW_METRICS
    assert first > listed.index("dsv2_prefill_share_pct")
    for m in BENCH["per_layer"][first:first + len(NEW_METRICS)]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_out_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["sdar_window_attention_roofline"]["unit"] == "%"
    assert by_name["sdar_positions_per_token"]["source"] \
        == "program_counter"
    assert by_name["sdar_positions_per_token"]["better"] == "lower"
    e2e = {m["name"] for m in harness.metrics_of_cell(BENCH, cell,
                                                      "end_to_end")}
    assert e2e == {"serve_out_tokens_per_s", "setup_s"}
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_out_tokens_per_s")
    assert CELL in tokens["workloads"] and tokens["bound"] == 0.06


def test_the_traffic_is_the_issues_letter_for_letter():
    assert MIX["driver"] == "serve_block_diffusion"
    assert (MIX["loop"], MIX["clients_per_row"]) == ("closed", 2)
    assert MIX["prompt_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert MIX["output_len"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert MIX["shared_prefix"] == {"share": 0.0}
    assert (MIX["block_length"], MIX["denoising_steps"],
            MIX["temperature"]) == (4, 4, 0.0)
    assert MIX["rows"] % 16 == 0 and 160 <= MIX["rows"] <= 176
    assert (MIX["ramp_s"], MIX["grace_s"], MIX["trace_seconds"]) == (
        30.0, 0.0, 3.0)
    assert MIX["warm_programs"] == ["paged_decode", "paged_prefill"]
    assert isinstance(MIX["plan_seed"], int)
    # the plan: 2.0 x the 5.1 requests a second the cell completes (PERF.md
    # section 6), which outlasts ramp + window + a 30 s stop_trace (a traced
    # run had sent 528 when its window closed)
    assert MIX["plan_requests_per_s"] == 10
    assert MIX["plan_requests_per_s"] * (30 + 30 + 2) + 2 * MIX["rows"] \
        >= 528 + 5.1 * 2 * 30
    job = CONFIG["job"]
    assert job["block_length"] == MIX["block_length"]
    assert job["page_size"] % job["block_length"] == 0
    assert all(b % job["block_length"] == 0 for b in job["buckets"])


# -- region names against the programs -----------------------------------------

@pytest.fixture(scope="module", params=["gather", "kernel"])
def program_paths(request):
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.serving import continuous
    from distributed_pytorch_training_tpu.serving.block_diffusion import (
        BlockDiffusionEngine,
    )
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )

    real = continuous.paged_attention_backend_supported
    continuous.paged_attention_backend_supported = \
        lambda: request.param == "kernel"
    try:
        model = get_model("sdar_30b_a3b_chat", dtype=jnp.bfloat16,
                          **CONFIG["rehearsal"]["model_overrides"])
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        params = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), params)
        engine = BlockDiffusionEngine(
            model, build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]),
            PagedServeConfig(buckets=(16,), rows=4, max_new_tokens=8,
                             page_size=8, serve_dtype="bf16"), params)
        assert engine.kv_path == request.param
        texts = {"step": engine.lower_paged_decode().as_text(
            debug_info=True), "prefill": engine.lower_paged_prefill(
                16).as_text(debug_info=True)}
    finally:
        continuous.paged_attention_backend_supported = real
    return request.param, {
        name: set(re.findall(r'loc\("(jit\([^"]*)"', text))
        for name, text in texts.items()}


def test_every_region_name_is_in_the_programs(program_paths):
    read, paths = program_paths
    step = {_regions.region_of(p, _sdar_regions.SDAR_BLOCK_STEP[1])
            for p in paths["step"]}
    prefill = {_regions.region_of(p, _sdar_regions.SDAR_PREFILL[1])
               for p in paths["prefill"]}
    shared = {"attn", "attn_proj", "moe_route", "moe_dispatch",
              "moe_experts", "kv_scatter", "embed"}
    assert shared | {"final_norm", "head", "unmask", "bookkeeping",
                     "model"} <= step
    assert shared <= prefill
    # nothing is sampled from a prefill: its head is dead code
    assert not {"head", "unmask"} & prefill
    # the window kernel by its own name on the kernel read, the views'
    # gather on the other
    assert (_sdar_regions.WINDOW_KERNEL in step) == (read == "kernel")
    assert ("kv_gather" in step) == (read == "gather")
    assert any(p.startswith("jit(block_step)/") for p in paths["step"])
    assert any(p.startswith("jit(prefill)/") for p in paths["prefill"])
    # every named region is one of the four readers' groups, once
    groups = (_sdar_regions.ATTN, _sdar_regions.MOE,
              _sdar_regions.HEAD_UNMASK, _sdar_regions.OTHER)
    flat = [r for g in groups for r in g]
    assert len(flat) == len(set(flat))
    assert set(flat) == set(_sdar_regions.SDAR_BLOCK_STEP[1]) | {
        _regions.UNSCOPED, _regions.COLLECTIVE}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_returns_nothing_where_there_is_nothing_to_read(name):
    """The parent's program under this benchmark: no trace, no such span,
    counter or fact. Every new reader says None and raises nothing."""
    run = types.SimpleNamespace(
        trace_data=None, events=[], facts={}, window=(0.0, 1.0), peaks=None,
        config=CONFIG, out_dir=ROOT, note=lambda **_: None)
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert reader.read(run) is None


def test_the_counter_readers_read_the_schedulers_counters():
    def counter(name, value):
        return {"kind": "counter", "name": name, "value": value}

    run = types.SimpleNamespace(
        events=[counter("serving_block_positions_forwarded", 400),
                counter("serving_block_positions_forwarded", 120),
                counter("serving_block_tokens_committed", 100),
                counter("serving_block_steps", 13)],
        facts={"step_counters": {"steps": 13.0,
                                 "moe_expert_load_max_over_mean": 20.8}})
    ppt = importlib.import_module(
        "benchmark.layer_metrics.sdar_positions_per_token")
    load = importlib.import_module(
        "benchmark.layer_metrics.sdar_moe_load_max_over_mean")
    assert ppt.read(run) == pytest.approx(5.2)
    assert load.read(run) == pytest.approx(1.6)


# -- the closed forms ----------------------------------------------------------

def test_parameters_and_costs_by_hand():
    sizes = CONFIG["published"]
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert flops.attention_weights(sizes) == attention == 18_874_368
    layer = attention + 2048 * 128 + 128 * 3 * 2048 * 768
    assert flops.layer_weights(sizes) == layer == 623_116_288
    want = 6 * layer + 2 * 151936 * 2048
    assert flops.parameters(CONFIG) == want == 4_361_027_584
    # what the program would hold, without allocating (norms besides)
    model = get_model("sdar_30b_a3b_chat", dtype=jnp.bfloat16,
                      **CONFIG["model_overrides"])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves = jax.tree_util.tree_leaves(shapes)
    norms = 6 * (2 * 2048 + 2 * 128) + 2048
    assert sum(leaf.size for leaf in leaves) == want + norms
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype(jnp.bfloat16)}
    per_position = 6 * (attention + 2048 * 128 + 8 * 3 * 2048 * 768) \
        + 151936 * 2048
    assert flops.matmul_weights_per_token(CONFIG) == per_position
    assert flops.moe_assignments_per_token(CONFIG) == 48
    # 176 rows of 700 committed positions, a window of 4
    cost = flops.window_attention_call_cost(sizes, 176, 4, 176 * 700.0)
    assert cost["flops"] == pytest.approx(
        4 * 32 * 128 * (4 * 176 * 700 + 176 * 16))
    assert cost["bytes"] == pytest.approx(
        (2 * 176 * 700 * 512 + 176 * 4 * 2 * 4096 + 176 * 4 * 2 * 512) * 2)
    assert cost["flops"] / cost["bytes"] == pytest.approx(31.0, rel=0.02)


def test_configuration_file_against_the_catalogs_row():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "SDAR-30B-A3B-Chat")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["published"] == row["config"]
    assert CONFIG["reduced"] == ["depth"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key
    cut = CONFIG["model_overrides"]
    assert set(cut) == {"depth", "router_init_std"}
    assert cut["depth"] == CONFIG["depth"] == 6
    # the floors: a period of one layer and at least four of them, every
    # expert, the whole vocabulary
    assert cut["depth"] >= 4 and row["config"]["decoder_sparse_step"] == 1
    model = get_model("sdar_30b_a3b_chat", **cut)
    assert (model.num_experts, model.num_experts_per_tok,
            model.vocab_size) == (128, 8, 151936)
    for key in ("block_length", "mask_token_id", "logits", "prompt",
                "schedule", "weights", "router_init_std", "balance_loss"):
        assert key in CONFIG["assumed"], key
    job = CONFIG["job"]
    assert job["buckets"] == [128, 256, 384, 512] and len(job["buckets"]) <= 4
    assert job["max_new_tokens"] == 1024 and job["prefix_skip"] is False
    assert (job["page_size"], job["kv_dtype"], job["serve_dtype"]) == (
        64, "fp32", "bf16")
    assert (job["block_length"], job["mask_token_id"]) == (
        model.block_length, model.mask_token_id)
    assert "8 pipeline stages" in CONFIG["deployment"]


# -- the driver's own pieces ---------------------------------------------------

def test_check_requests_cover_every_remainder_and_a_cut_block():
    prompts, wants = driver.check_requests(
        np.random.default_rng(0), (128, 256, 384, 512), 4, 151936)
    lens = [len(p) for p in prompts]
    assert len(prompts) == 8 and min(lens) >= 64 and max(lens) <= 512
    assert {n % 4 for n in lens} == {0, 1, 2, 3}
    assert all(6 <= w <= 10 for w in wants)
    assert any(w % 4 for w in wants)
    ends = [(n % 4 + w) % 4 for n, w in zip(lens, wants)]
    assert ends[:6] == [0] * 6 and all(ends[6:])


def test_replay_rebuilds_the_windows_state_step_by_step():
    prompt = np.arange(1, 7, dtype=np.int32)            # 6 = 4 + 2
    tokens = np.array([11, 12, 21, 22, 23, 24, 31], np.int32)
    steps = np.array([1, 0, 3, 0, 2, 1, 0])
    got = driver.replay(prompt, tokens, steps, 4)
    # block at 4: the prompt's 5, 6 stand; 12 falls at step 0, 11 at step 1
    assert [g[0] for g in got] == [4, 4, 8, 8, 8, 8, 12]
    np.testing.assert_array_equal(got[0][1], [5, 6, 11, 12])
    np.testing.assert_array_equal(got[0][2], [False, False, True, True])
    np.testing.assert_array_equal(got[0][3], [False, False, False, True])
    np.testing.assert_array_equal(got[1][2], [False, False, True, False])
    np.testing.assert_array_equal(got[1][3], [False, False, True, False])
    # block at 8: four steps, one position each
    np.testing.assert_array_equal(got[4][2], [True, False, True, False])
    np.testing.assert_array_equal(got[5][3], [True, False, False, False])
    # block at 12 is cut to one token: its first step alone, all masked
    np.testing.assert_array_equal(got[6][1], [31, 0, 0, 0])
    np.testing.assert_array_equal(got[6][2], [True] * 4)
    np.testing.assert_array_equal(got[6][3], [True, False, False, False])


def test_layer_check_tells_a_rounded_cache_from_a_whole_one(monkeypatch):
    """`kernel_rel_diff` at the rehearsal's sizes: the program's window read
    against the expanded float32 form is rounding apart; with the cached
    rows rounded to float8_e4m3 on the way in it is far over the limit."""
    config = harness._merge(CONFIG, CONFIG["rehearsal"])
    traffic = {"rows": 4}
    whole = checks.layer_checks(config, traffic, seed=5)
    assert whole["kernel_read"] == "gather"
    assert whole["kernel_rel_diff"] <= CONFIG["correct"]["kernel_rel_diff_tol"]
    from distributed_pytorch_training_tpu.models import layers

    real = layers.scatter_paged_window

    def rounded(pool, table, positions, k, v, active):
        low = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)  # noqa: E731
        return real(pool, table, positions, low(k), low(v), active)

    monkeypatch.setattr(layers, "scatter_paged_window", rounded)
    low = checks.layer_checks(config, traffic, seed=5)
    assert low["kernel_rel_diff"] > CONFIG["correct"]["kernel_rel_diff_tol"]
    assert low["kernel_rel_diff"] > 5 * whole["kernel_rel_diff"]


def test_the_fit_tool_sizes_the_engine_the_model_asks_for(monkeypatch):
    from benchmark.tools import fit_check_block_diffusion, fit_check_serve_lm
    from distributed_pytorch_training_tpu.serving import (
        block_diffusion, continuous,
    )

    seen = {}
    monkeypatch.setattr(
        fit_check_serve_lm, "main",
        lambda argv: seen.update(cls=continuous.SlotEngine, argv=argv) or 0)
    assert fit_check_block_diffusion.main(["--workload", CELL]) == 0
    assert seen["cls"] is block_diffusion.BlockDiffusionEngine
    assert continuous.SlotEngine is not block_diffusion.BlockDiffusionEngine

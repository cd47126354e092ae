"""`ops.grouped_product` (the all-held expert layer's grouped products as a
Pallas grouped matmul, ISSUE 43), in interpreter mode on the CPU:

* the kernel against `lax.ragged_dot` in float32 and in bf16 over group
  sizes that hold 0, 1, sizes off every sublane tile, one group with every
  row, a last group that ends at the last row, and rows past the last group;
* a layer's three products (gate and up, gated SiLU, down) against the same
  arithmetic group by group;
* `HeldExpertsMoe` with every expert held and the kernel forced on, against
  the same module on the XLA form and against the uncut reference layer,
  ``moe_dropped_assignments`` 0;
* which form a module takes (`HeldExpertsMoe.expert_path`): the kernel only
  for the all-held pass, on the backend's say-so, at shapes of whole tiles;
* lowered for a TPU, the block step holds the kernel's ``tpu_custom_call``
  under a ``moe_experts`` path and no ``ragged_dot``; a share's layer holds
  ``ragged_dot`` and no kernel; the ``compile`` span of ``paged_decode``
  carries ``expert_path``.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar as reference
from distributed_pytorch_training_tpu import telemetry
from distributed_pytorch_training_tpu.models import moe
from distributed_pytorch_training_tpu.serving.build import build_slot_engine
from distributed_pytorch_training_tpu.serving.router import InProcessReplica
from distributed_pytorch_training_tpu.training.tasks import step_counters

gp = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.grouped_product")

M, K, N = 96, 32, 40
# name -> group sizes over M rows (a sum under M leaves rows past the last
# group, which neither form defines)
SIZES = {
    "empty_one_and_odd": [0, 1, 13, 0, 27, 5, 50],
    "off_every_tile": [7, 9, 15, 17, 23, 25],
    "one_group_holds_all": [0, 0, M, 0],
    "first_group_holds_all": [M, 0, 0],
    "ends_at_the_last_row": [31, 1, 0, 64],
    "rows_past_the_last_group": [5, 0, 30, 11],
    "nothing_held": [0, 0, 0],
    "a_group_a_row": [1] * M,
}
# max|diff| over outputs of O(sqrt(K)): float32 differs by the order of the
# sums alone; in bf16 both forms accumulate in float32 and round once
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 0.0}
# `lax.ragged_dot` in a lowered program's text (the text with debug info
# also holds this file's function names)
XLA_PRODUCT = "chlo.ragged_dot"


def operands(dtype, groups, k=K, n=N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (M, k), dtype),
            jax.random.normal(ks[1], (groups, k, n), dtype))


def held(sizes):
    """The rows a group covers, as a mask over M."""
    return np.arange(M) < sum(sizes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_kernel_matches_ragged_dot(case, dtype):
    sizes = SIZES[case]
    rows, weights = operands(dtype, len(sizes))
    s = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gp.grouped_product(rows, weights, s), np.float32)
    want = np.asarray(jax.lax.ragged_dot(rows, weights, s), np.float32)
    assert got.shape == (M, N) and got.dtype == want.dtype
    covered = held(sizes)
    np.testing.assert_allclose(got[covered], want[covered], rtol=0,
                               atol=TOL[dtype])


@pytest.mark.parametrize("case", ["empty_one_and_odd", "off_every_tile",
                                  "one_group_holds_all"])
def test_a_layers_products_match_the_arithmetic_group_by_group(case):
    sizes = SIZES[case]
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (M, K))
    gate, up = (0.3 * jax.random.normal(k, (len(sizes), K, N))
                for k in ks[1:3])
    down = 0.3 * jax.random.normal(ks[3], (len(sizes), N, K))
    s = jnp.asarray(sizes, jnp.int32)
    mid = jax.nn.silu(gp.grouped_product(x, gate, s)) \
        * gp.grouped_product(x, up, s)
    got = np.asarray(gp.grouped_product(mid, down, s))
    at = 0
    for g, size in enumerate(sizes):
        a = np.asarray(x[at:at + size], np.float64)
        h = a @ np.asarray(gate[g], np.float64)
        want = (h / (1 + np.exp(-h)) * (a @ np.asarray(up[g], np.float64))
                ) @ np.asarray(down[g], np.float64)
        np.testing.assert_allclose(got[at:at + size], want, atol=2e-5)
        at += size


def kernel_backend(monkeypatch):
    """A backend on which the all-held pass takes the kernel: the ONE
    predicate, patched where the module asks it; the kernel itself runs in
    interpreter mode, as every Pallas kernel does on the CPU."""
    monkeypatch.setattr(moe, "grouped_product_backend_supported",
                        lambda: True)


SDAR_SIZES = dict(num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                  rms_norm_eps=1e-6, rope_theta=1e6, num_experts=16,
                  num_experts_per_tok=4, norm_topk_prob=True, vocab_size=200)


@pytest.mark.parametrize("tokens", [20, 21, 64], ids=lambda t: f"tokens{t}")
def test_all_held_on_the_kernel_is_the_xla_form_and_the_reference(
        tokens, monkeypatch):
    layer = moe.HeldExpertsMoe(16, 16, 4, 24, norm_topk_prob=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, 64))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    assert layer.expert_path(tokens, 64) == "xla"
    xla, _ = layer.apply({"params": params}, x, mutable=["counters"])
    kernel_backend(monkeypatch)
    assert layer.expert_path(tokens, 64) == "kernel"
    got, sown = layer.apply({"params": params}, x, mutable=["counters"])
    np.testing.assert_allclose(got, xla, atol=1e-6)
    want = reference.experts(params, x.reshape(tokens, 64), SDAR_SIZES)
    np.testing.assert_allclose(got.reshape(tokens, 64), want, atol=1e-6)
    counted = step_counters(sown["counters"])
    assert float(counted["moe_held_assignments"]) == tokens * 4
    assert float(counted["moe_dropped_assignments"]) == 0
    text = jax.jit(lambda p, x: layer.apply({"params": p}, x)).lower(
        params, x).as_text()
    assert XLA_PRODUCT not in text


def test_the_path_is_read_off_what_the_module_can_see(monkeypatch):
    kernel_backend(monkeypatch)
    whole = moe.HeldExpertsMoe(16, 16, 4, 256, dtype=jnp.bfloat16)
    share = moe.HeldExpertsMoe(16, 4, 4, 256, dtype=jnp.bfloat16)
    assert whole.expert_path(64, 128) == "kernel"
    assert share.expert_path(64, 128) == "xla"      # a walk under a cond
    monkeypatch.setattr(gp, "_interpret", lambda: False)
    assert whole.expert_path(64, 128) == "kernel"
    assert whole.expert_path(64, 192) == "xla"      # half a lane tile
    assert whole.expert_path(16, 128) == "xla"      # 64 rows: half a tile
    assert moe.HeldExpertsMoe(16, 16, 4, 200, dtype=jnp.bfloat16
                              ).expert_path(64, 128) == "xla"
    assert moe.HeldExpertsMoe(16, 16, 4, 256, dtype=jnp.float16
                              ).expert_path(64, 128) == "xla"
    monkeypatch.setattr(moe, "grouped_product_backend_supported",
                        gp.grouped_product_backend_supported)
    assert whole.expert_path(64, 128) == "xla"      # this is a CPU


def test_the_tiling_spans_the_contraction_and_what_fits_of_the_width(
        monkeypatch):
    assert gp.grouped_product_tiling(80, 64, 24, jnp.float32) == (16, 64, 24)
    monkeypatch.setattr(gp, "_interpret", lambda: False)
    tiling = gp.grouped_product_tiling
    # the block-diffusion cell's two shapes: one weight tile an expert
    assert tiling(5632, 2048, 768, jnp.bfloat16) == (128, 2048, 768)
    assert tiling(5632, 768, 2048, jnp.bfloat16) == (128, 768, 2048)
    # float32 weights are twice the bytes: half the width a tile
    assert tiling(5632, 2048, 768, jnp.float32) == (128, 2048, 384)
    assert tiling(5632, 5120, 1536, jnp.bfloat16) == (128, 5120, 384)
    assert tiling(5632, 2048, 700, jnp.bfloat16) is None
    # rows in whole tiles of 128: the 8 positions x 8 of a model's init are
    # not worth a kernel instance
    assert tiling(64, 2048, 768, jnp.bfloat16) is None
    assert tiling(5632 + 64, 2048, 768, jnp.bfloat16) is None
    with pytest.raises(ValueError, match="grouped_product_supports"):
        gp.grouped_product(jnp.zeros((8, 100), jnp.bfloat16),
                           jnp.zeros((2, 100, 128), jnp.bfloat16),
                           jnp.array([4, 4], jnp.int32))


# -- lowered for a TPU ---------------------------------------------------------

WIDE = dict(vocab_size=200, hidden_dim=128, depth=2, num_heads=8,
            num_kv_heads=2, head_dim=128, moe_intermediate_size=128,
            num_experts=16, num_experts_per_tok=4, mask_token_id=199)


def on_a_tpu(monkeypatch):
    monkeypatch.setattr(gp, "_interpret", lambda: False)
    kernel_backend(monkeypatch)


def kernel_calls(text):
    """The lines of a lowered program's text (with debug info) that call
    the grouped kernel: Mosaic custom calls named as `megablox.gmm` names
    its kernel."""
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line and 'kernel_name = "kernel"' in line]


def test_the_block_step_lowers_for_a_tpu_on_the_grouped_kernel(monkeypatch):
    on_a_tpu(monkeypatch)
    eng, _ = build_slot_engine(
        jax.devices()[:1], "sdar_30b_a3b_chat", buckets=(16,), rows=8,
        max_new_tokens=8, page_size=16, serve_dtype="bf16",
        model_overrides=WIDE)
    assert eng.expert_path == "kernel"    # 8 rows x W 4 x top 4: one tile
    fn = eng._make_paged_decode()
    avals = (eng._served, eng._pool_avals(), eng._control_avals(),
             eng._row_aval((8, eng.config.pages_per_slot), jnp.int32))
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert XLA_PRODUCT not in text
    # gate and up share one instance, down is the other; both layers call
    # the same two
    assert len(kernel_calls(text)) == 2
    # each call site lies under the layer's scope: the path a trace's
    # `tf_op` carries, by which `sdar_block_moe_ms` counts the kernel
    assert "moe_experts/jit(gmm)" in text


def test_a_share_lowers_to_ragged_dot_and_no_kernel(monkeypatch):
    on_a_tpu(monkeypatch)
    share = moe.HeldExpertsMoe(16, 4, 4, 128, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 64, 128), jnp.bfloat16)
    params = jax.eval_shape(lambda: share.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))["params"])
    text = jax.jit(lambda p, x: share.apply(
        {"params": p}, x, mutable=["counters"])).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert XLA_PRODUCT in text and "tpu_custom_call" not in text


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_compile_span_carries_expert_path(path, monkeypatch):
    if path == "kernel":
        kernel_backend(monkeypatch)
    rec = telemetry.configure()          # ring-only stream
    try:
        eng, _ = build_slot_engine(
            jax.devices()[:1], "sdar_30b_a3b_chat", buckets=(16,), rows=2,
            max_new_tokens=8, page_size=8, seed=0, model_overrides=dict(
                WIDE, hidden_dim=64, head_dim=16, moe_intermediate_size=24))
        replica = InProcessReplica("e0", eng)
        try:
            prompt = np.arange(1, 14, dtype=np.int32)
            res = replica.submit(prompt, max_new_tokens=5).result(timeout=300)
        finally:
            replica.stop()
        events = rec.tail(10_000)
    finally:
        telemetry.reset()
    assert len(res.tokens) == 5
    compiles = {e["program"]: e for e in events
                if e["kind"] == "span" and e["name"] == "compile"}
    assert compiles["paged_decode"]["expert_path"] == path
    assert compiles["paged_decode"]["kv_path"] == "gather"
    assert "expert_path" not in compiles["paged_prefill"]

"""DeepSeek-V2 (models/deepseek_v2.py) against its plain reference
(benchmark/reference/deepseek_v2.py) at the rehearsal's widths, on the CPU:
the expanded forward; prefill then the absorbed decode through pages on both
reads; the kernel against the gather form; group-limited routing by hand and
against the reference; the share test; `SlotEngine` on a latent pool; YaRN in
closed form; what a latent pool refuses; the flash forward with a value width
of its own."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2 as reference
from distributed_pytorch_training_tpu.models import get_model, moe
from distributed_pytorch_training_tpu.models import deepseek_v2 as program
from distributed_pytorch_training_tpu.models.layers import (
    PagedLatent, gather_paged_kv, init_paged_latent, paged_kv_bytes,
    scatter_paged_rows, scatter_paged_window,
)
from distributed_pytorch_training_tpu.models.registry import (
    is_lm_model, lm_vocab,
)
from distributed_pytorch_training_tpu.ops.flash_attention import (
    flash_attention,
)
from distributed_pytorch_training_tpu.ops.mla_paged_attention import (
    mla_paged_attention,
)
from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
from distributed_pytorch_training_tpu.serving import continuous
from distributed_pytorch_training_tpu.serving.batching import RequestQueue
from distributed_pytorch_training_tpu.serving.continuous import (
    ContinuousScheduler, SlotEngine,
)
from distributed_pytorch_training_tpu.serving.paged import PagedServeConfig

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
SIZES = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=24, n_routed_experts=16,
    num_experts_per_tok=4, n_group=4, topk_group=2, norm_topk_prob=False,
    routed_scaling_factor=16, n_shared_experts=2, first_k_dense_replace=1,
    rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=YARN, vocab_size=1600)
WIDTHS = dict(
    hidden_dim=64, num_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=24, n_routed_experts=16,
    num_experts_per_tok=4, n_group=4, topk_group=2)
CUT = dict(depth=3, num_experts_held=4, first_expert=4, vocab_size=200)
SHARE = dict(first_expert=4, num_experts_held=4, vocab_size=200)


@pytest.fixture(scope="module")
def served():
    model = get_model("deepseek_v2_236b_a21b", **WIDTHS, **CUT)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 200)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    want = reference.forward(reference.from_program_params(params), ids,
                             SIZES, SHARE)
    return model, params, ids, np.asarray(want)


def test_forward_matches_the_reference(served):
    model, params, ids, want = served
    got = model.apply({"params": params}, ids)
    assert got.shape == (2, 24, 256)          # 200 rows padded to 128s
    np.testing.assert_allclose(got[..., :200], want, atol=2e-6)
    assert float(got[..., 200:].max()) == float(jnp.finfo(jnp.float32).min)
    by_layer = reference.layer_by_layer(params, ids[0], SIZES, SHARE,
                                        rows=(5, 3))
    np.testing.assert_allclose(by_layer, want[0, 5:8], atol=2e-6)


def test_prefill_then_absorbed_decode_over_views(served):
    """The model alone: the prefill's latent rows into dense views, then four
    S=1 steps of the absorbed form, each against the reference's expanded
    full forward at that position."""
    model, params, ids, want = served
    logits, cache = model.apply({"params": params}, ids[:1, :16],
                                cache=model.init_cache(1, 16))
    np.testing.assert_allclose(logits[0, :, :200], want[0, :16], atol=2e-6)
    assert [leaf.shape for leaf in cache[0]] == [(1, 16, 32), (1, 16, 8)]
    views = [tuple(jnp.zeros((1, 32, leaf.shape[-1])).at[:, :16].set(leaf)
                   for leaf in layer) for layer in cache]
    for pos in range(16, 20):
        logits, views = model.apply(
            {"params": params}, ids[:1, pos:pos + 1], cache=tuple(views),
            cache_positions=jnp.array([pos]))
        np.testing.assert_allclose(logits[0, 0, :200], want[0, pos],
                                   atol=2e-6)


def _engine(model, params, **kw):
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    cfg = PagedServeConfig(buckets=(16, 32), rows=4, max_new_tokens=8,
                           page_size=8, **kw)
    return SlotEngine(model, mesh, cfg, params), cfg


@pytest.mark.parametrize("read", ["gather", "kernel"])
def test_served_through_pages_matches_the_reference(served, read,
                                                    monkeypatch):
    """Prefill, then the absorbed decode through the paged latent pool, on
    the reference read and on the kernel (interpreter), through the
    scheduler: kept logits and every decoded token against the reference's
    expanded full forward fed the same prefix; no compile after warm-up;
    every page back in the pool at the end; the counters counted."""
    model, params, _, _ = served
    if read == "kernel":
        monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                            lambda: True)
    engine, cfg = _engine(model, params)
    assert engine.kv_path == read
    engine.warmup()
    warmed = engine.compiles
    assert warmed == 3                         # decode + two prefills
    queue = RequestQueue(cfg.buckets)
    scheduler = ContinuousScheduler(engine, queue)
    rng = np.random.default_rng(0)
    requests = [queue.submit(rng.integers(0, 200, n).astype(np.int32),
                             max_new_tokens=8, seed=i)
                for i, n in enumerate((5, 16, 23, 32, 9, 17))]
    while scheduler.step() or len(queue):
        pass
    ref_params = reference.from_program_params(params)
    for request in requests:
        result = request.result(timeout=1)
        n = len(request.tokens)
        ids = np.concatenate([request.tokens, result.tokens])[None]
        want = np.asarray(reference.forward(ref_params, jnp.asarray(ids),
                                            SIZES, SHARE))[0]
        np.testing.assert_allclose(result.last_logits[:200], want[n - 1],
                                   atol=2e-6)
        for k, token in enumerate(result.tokens):
            row = want[n - 1 + k]
            assert row.max() - row[token] <= 1e-6
    assert engine.compiles == warmed
    stats = scheduler.pool.stats()
    assert stats["leased"] == 0
    assert stats["free"] + stats["retained"] == cfg.total_pages - 1
    counted = engine.fetch_step_counters()
    assert counted["steps"] > 0 and counted["moe_held_assignments"] > 0
    assert counted["moe_dropped_assignments"] == 0


def test_latent_pool_bytes_at_the_published_widths():
    model = get_model("deepseek_v2_236b_a21b", depth=6, dtype=jnp.bfloat16)
    pool = jax.eval_shape(lambda: model.init_paged_pool(11, 16))
    assert isinstance(pool, PagedLatent)
    assert paged_kv_bytes(pool) == 6 * 11 * 16 * 1152   # 576 numbers of 2 B
    with pytest.raises(ValueError, match="no int8 form"):
        model.init_paged_pool(11, 16, quantized=True)


def test_a_latent_pool_refuses_the_window_programs(served):
    model, params, _, _ = served
    engine, _ = _engine(model, params, prefix_skip=True)
    assert engine.prefix_skip_enabled is False
    for ask in (engine.lower_paged_skip,
                lambda: engine.lower_paged_resume(16)):
        with pytest.raises(ValueError, match="K/V-only"):
            ask()
    from distributed_pytorch_training_tpu.serving.speculative import (
        SpeculativeEngine,
    )
    with pytest.raises(ValueError, match="K/V-only"):
        SpeculativeEngine(model, engine.mesh, engine.config, params, model,
                          params)
    with pytest.raises(ValueError, match="S=1 decode"):
        model.apply({"params": params}, jnp.zeros((1, 2), jnp.int32),
                    cache=model.init_cache(1, 8),
                    cache_positions=jnp.array([3]))


def test_kernel_matches_the_gather_form_over_ragged_rows():
    """Ragged live lengths with a dead row, a row at position 0, rows that
    end on and one past a page boundary, an odd layer of a pair; pages
    scattered through the pool."""
    rows, heads, rank, rope, ps, per_row = 6, 4, 32, 8, 8, 5
    live = np.array([0, 1, 8, 9, 16, 37], np.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    table = jnp.asarray(1 + np.random.default_rng(1).permutation(
        rows * per_row).reshape(rows, per_row).astype(np.int32))
    c_all = jax.random.normal(keys[0], (3, rows, per_row * ps, rank))
    pe_all = jax.random.normal(keys[1], (3, rows, per_row * ps, rope))
    positions = jnp.broadcast_to(jnp.arange(per_row * ps),
                                 (rows, per_row * ps))
    pool = scatter_paged_window(
        init_paged_latent(3, rows * per_row + 1, ps, rank, rope), table,
        positions, c_all, pe_all, positions < live[:, None])
    q_c = jax.random.normal(keys[2], (rows, heads, rank))
    q_pe = jax.random.normal(keys[3], (rows, heads, rope))
    fresh_c = jax.random.normal(keys[4], (rows, rank))
    fresh_pe = jax.random.normal(keys[5], (rows, rope))
    views = gather_paged_kv(pool, table)
    np.testing.assert_array_equal(
        np.asarray(views[1])[:, 5, :37], np.asarray(pe_all)[:, 5, :37])
    for layer in range(3):
        got = mla_paged_attention(
            q_c, q_pe, fresh_c, fresh_pe, pool.c, pool.pe, table,
            jnp.asarray(live), layer=layer, sm_scale=0.2, pages_per_chunk=2)
        want, _ = program._attend_view(
            q_c, q_pe, fresh_c, fresh_pe, tuple(v[layer] for v in views),
            jnp.asarray(live), 0.2, jnp.float32)
        np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(got[0], jnp.broadcast_to(fresh_c[0],
                                                        (heads, rank)))
    # the decode step's row write: an inactive row's write is dropped
    active = jnp.asarray(live > 0)
    wrote = scatter_paged_rows(pool, table, jnp.asarray(live),
                               jnp.stack([fresh_c] * 3),
                               jnp.stack([fresh_pe] * 3), active)
    after = gather_paged_kv(wrote, table)
    np.testing.assert_array_equal(np.asarray(after[1])[2, 5, 37],
                                  np.asarray(fresh_pe)[5])
    np.testing.assert_array_equal(np.asarray(after[0])[1, 0],
                                  np.asarray(views[0])[1, 0])


def test_group_limited_routing_by_hand():
    """8 experts in 4 groups of 2, 2 groups stay, 3 experts a token. Scores
    0.30 0.05 | 0.20 0.20 | 0.20 0.01 | 0.02 0.02: groups 0 (0.30) and, of
    the tie at 0.20 between groups 1 and 2, the lower: group 1. Of experts
    0..3 the three largest are 0 (0.30) and the tie 2, 3 (0.20 each); expert
    1 (0.05) stays out. Weights 2.5 p, not renormalised."""
    sizes = dict(n_group=4, topk_group=2, num_experts_per_tok=3,
                 routed_scaling_factor=2.5, norm_topk_prob=False)
    probs = jnp.array([[0.30, 0.05, 0.20, 0.20, 0.20, 0.01, 0.02, 0.02]])
    np.testing.assert_allclose(
        reference.routing_weights(probs, sizes),
        [[0.75, 0.0, 0.5, 0.5, 0.0, 0.0, 0.0, 0.0]], rtol=1e-6)
    # two experts a token: of the tie between 2 and 3 the lower id
    np.testing.assert_allclose(
        reference.routing_weights(probs, dict(sizes, num_experts_per_tok=2)),
        [[0.75, 0.0, 0.5, 0.0, 0.0, 0.0, 0.0, 0.0]], rtol=1e-6)


@pytest.mark.parametrize("tied", [False, True])
def test_program_routing_matches_the_reference(tied):
    """`HeldExpertsMoe` holding every expert against the reference's routed
    experts: on random scores, and on scores with exact ties between groups
    and between experts (equal router columns), where both must send the
    token to the lower id."""
    d, experts, t = 16, 16, 40
    layer = moe.HeldExpertsMoe(experts, experts, 4, 12, n_group=4,
                               topk_group=2, norm_topk_prob=False,
                               routed_scaling_factor=16.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, d))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params = dict(params, router=params["router"] * 40.0)
    if tied:
        router = np.array(params["router"])
        router[:, 4:8] = router[:, 0:4]        # group 1 ties with group 0
        router[:, 9] = router[:, 8]            # two experts of group 2 tie
        params = dict(params, router=jnp.asarray(router))
    got, sown = layer.apply({"params": params}, x, mutable=["counters"])
    sizes = dict(n_group=4, topk_group=2, num_experts_per_tok=4,
                 routed_scaling_factor=16.0, norm_topk_prob=False)
    want = reference.routed_experts(params, x[0], sizes, 0, experts)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    assert float(sown["counters"]["moe_dropped_assignments"][0]) == 0
    assert float(sown["counters"]["moe_held_assignments"][0]) == 4 * t


def test_the_groups_shares_add_up_to_the_uncut_layer():
    """The share test: every routing group's chip computes its own experts'
    part of an expert layer; the four parts, with the shared experts (which
    every chip computes alike) counted once, are the uncut reference layer."""
    model = get_model("deepseek_v2_236b_a21b", **WIDTHS, depth=2,
                      num_experts_held=16, vocab_size=200)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64))
    whole = model.init(jax.random.PRNGKey(3),
                       jnp.zeros((1, 4), jnp.int32))["params"]["layer1"]
    whole = dict(whole, moe=dict(whole["moe"],
                                 router=whole["moe"]["router"] * 30.0))
    ref = reference.from_program_params(whole)
    want = reference.routed_experts(ref["moe"], x[0], SIZES, 0, 16) \
        + reference.gated_mlp(ref["shared_expert"], x[0])
    total = jnp.zeros_like(x[0])
    for group in range(4):
        first = 4 * group
        chip = moe.HeldExpertsMoe(
            16, 4, 4, 24, first, n_group=4, topk_group=2,
            norm_topk_prob=False, routed_scaling_factor=16.0)
        held = {k: (v if k == "router" else v[first:first + 4])
                for k, v in whole["moe"].items()}
        total = total + chip.apply({"params": held}, x)[0]
    shared = program.GatedMlp(48).apply({"params": whole["shared_expert"]}, x)
    np.testing.assert_allclose(total + shared[0], want, atol=3e-5, rtol=3e-5)


def test_yarn_frequencies_and_softmax_scale_in_closed_form():
    """Published sizes: 64 rotary dims, theta 1e4, factor 40 over 4,096,
    beta 32 / 1. A pair's wavelength is 2 pi 1e4^(2i/64): the correction
    dims are 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> 10 and
    64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23: pairs 0..10 keep their
    frequency, pairs 23..31 have it divided by 40, a ramp over 13 between."""
    freq = program.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40.0, rtol=1e-6)
    ramp = (16 - 10) / 13.0
    np.testing.assert_allclose(
        freq[16], plain[16] * ((1 - ramp) + ramp / 40.0), rtol=1e-6)
    published = dict(SIZES, qk_nope_head_dim=128, qk_rope_head_dim=64)
    np.testing.assert_allclose(reference.yarn_inv_freq(published), freq,
                               rtol=1e-6)
    want = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    model = get_model("deepseek_v2_236b_a21b")
    assert model.softmax_scale == pytest.approx(want, rel=1e-12)
    assert reference.softmax_scale(published) == pytest.approx(want,
                                                               rel=1e-12)
    assert want == pytest.approx(0.114722, rel=1e-5)


def test_registry_answers_by_the_model():
    assert is_lm_model("deepseek_v2_236b_a21b")
    assert is_lm_model("qwen3_next_80b_a3b") and is_lm_model("bert_base")
    assert not is_lm_model("resnet18")
    assert lm_vocab("deepseek_v2_236b_a21b") == 102400
    assert lm_vocab("deepseek_v2_236b_a21b", vocab_size=12800) == 12800
    assert lm_vocab("gpt2_124m") == 50257 and lm_vocab("bert_base") == 30522
    model = get_model("deepseek_v2_236b_a21b", dtype=jnp.bfloat16)
    assert model.param_dtype == jnp.bfloat16    # weights rest as served


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_with_a_value_width_of_its_own(causal):
    """Keys of 24, values of 16 (latent attention: 192 / 128) through the
    forward kernel (interpreter) against the XLA form; its backward is not
    written and says so."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, 64, 3, 24))
    k = jax.random.normal(keys[1], (2, 64, 3, 24))
    v = jax.random.normal(keys[2], (2, 64, 3, 16))
    got = flash_attention(q, k, v, causal, None, 32, 32)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(24.0)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    assert got.shape == (2, 64, 3, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(NotImplementedError, match="forward kernel only"):
        jax.grad(lambda v: flash_attention(q, k, v, causal).sum())(v)


def test_live_cache_tokens_counter_is_the_steps_reads(served, monkeypatch):
    """`serving_live_cache_tokens`: the cached positions the decode steps
    read, by host arithmetic over the running requests: a request of n
    prompt tokens asking for w reads n, n + 1, .. n + w - 2 over its w - 1
    steps."""
    model, params, _, _ = served
    engine, cfg = _engine(model, params)
    seen = {}
    monkeypatch.setattr(
        continuous.telemetry, "counter",
        lambda name, value, **_: seen.__setitem__(
            name, seen.get(name, 0) + value))
    queue = RequestQueue(cfg.buckets)
    scheduler = ContinuousScheduler(engine, queue)
    shapes = [(5, 8), (16, 3), (23, 6)]
    for i, (n, w) in enumerate(shapes):
        queue.submit(np.arange(n, dtype=np.int32), max_new_tokens=w, seed=i)
    while scheduler.step() or len(queue):
        pass
    want = sum(sum(range(n, n + w - 1)) for n, w in shapes)
    assert seen["serving_live_cache_tokens"] == want


class TestLowersForATpu:
    """The lowering a chip would run, Pallas to Mosaic included, from the CPU
    (nothing compiles, nothing runs), at the cell's timed shapes: a block
    shape or an operation the interpreter accepts and the TPU lowering
    refuses fails here, before chip time. (Mosaic's own compile, where a
    576-lane slice of an HBM array was refused, runs in
    `benchmark.tools.fit_check_serve_lm`.)"""

    @pytest.mark.parametrize("page_size", [16, 32, 64])
    def test_the_decode_kernel_at_the_timed_shape(self, monkeypatch,
                                                  page_size):
        from distributed_pytorch_training_tpu.ops import (
            mla_paged_attention as kernel,
        )

        monkeypatch.setattr(kernel, "_interpret", lambda: False)
        rows, heads, rank, rope = 112, 128, 512, 64
        per_row = -(-4736 // page_size)
        bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
        text = jax.jit(lambda *a: mla_paged_attention(
            *a, layer=3, sm_scale=0.1147)).trace(
            bf16(rows, heads, rank), bf16(rows, heads, rope),
            bf16(rows, rank), bf16(rows, rope),
            bf16(6, rows * per_row + 1, page_size, rank),
            bf16(3, rows * per_row + 1, page_size, 2 * rope),
            jax.ShapeDtypeStruct((rows, per_row), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1
        assert "mla_paged_attention" in text
        assert kernel.mla_paged_attention_supports(page_size, rank, rope,
                                                   jnp.bfloat16)
        assert not kernel.mla_paged_attention_supports(8, rank, rope,
                                                       jnp.bfloat16)

    @pytest.mark.parametrize("seq_len", [2560, 3072, 3584, 4096])
    def test_the_prefill_flash_forward_at_192_and_128(self, monkeypatch,
                                                      seq_len):
        import importlib

        monkeypatch.setattr(importlib.import_module(
            "distributed_pytorch_training_tpu.ops.flash_attention"),
            "_interpret", lambda: False)
        qk = jax.ShapeDtypeStruct((1, seq_len, 128, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, seq_len, 128, 128), jnp.bfloat16)
        lowered = jax.jit(lambda q, k, v: flash_attention(q, k, v, True)
                          ).trace(qk, qk, v).lower(
                              lowering_platforms=("tpu",))
        assert lowered.out_info.shape == (1, seq_len, 128, 128)
        text = lowered.as_text()
        assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text

"""SDAR (models/sdar.py) and the block engine (serving/block_diffusion.py)
against the plain reference (benchmark/reference/sdar.py) at a tiny size, on
the CPU, in float32: the forward under the block mask; prefill + block steps
through `BlockDiffusionEngine`, `BlockDiffusionScheduler` and the `Router`
against `reference.generate`, token for token and order for order; the
stream's independence of slot, join order and company; rows in different
phases in one step; the pool untouched before a commit; the window read
against the gather form; the flash forward under the block mask; the expert
layer with every expert held; what such a model's engine refuses; and the
lowered text of the programs the other cells run, unchanged."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar as reference
from distributed_pytorch_training_tpu.models import get_model, moe
from distributed_pytorch_training_tpu.models.layers import (
    dot_product_attention, gather_paged_kv, init_paged_kv,
    scatter_paged_window,
)
from distributed_pytorch_training_tpu.models.registry import (
    is_lm_model, lm_vocab,
)
from distributed_pytorch_training_tpu.models.sdar import (
    attend_window_views, block_causal_mask,
)
from distributed_pytorch_training_tpu.ops.flash_attention import (
    flash_attention, make_flash_attention_fn,
)
from distributed_pytorch_training_tpu.ops.paged_attention import (
    paged_attention, paged_attention_supports,
)
from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
from distributed_pytorch_training_tpu.serving import continuous
from distributed_pytorch_training_tpu.serving.batching import (
    Request, RequestQueue,
)
from distributed_pytorch_training_tpu.serving.block_diffusion import (
    BlockDiffusionEngine, BlockDiffusionScheduler, denoise_steps_of,
)
from distributed_pytorch_training_tpu.serving.build import (
    build_slot_engine, build_spec_engine,
)
from distributed_pytorch_training_tpu.serving.continuous import (
    ContinuousScheduler, SlotEngine,
)
from distributed_pytorch_training_tpu.serving.paged import PagedServeConfig
from distributed_pytorch_training_tpu.serving.router import (
    InProcessReplica, Router,
)
from distributed_pytorch_training_tpu.training.tasks import step_counters

B, MASK = 4, 199
TINY = dict(vocab_size=200, hidden_dim=64, depth=2, num_heads=8,
            num_kv_heads=2, head_dim=16, moe_intermediate_size=24,
            num_experts=16, num_experts_per_tok=4, mask_token_id=MASK)
SIZES = dict(num_attention_heads=8, num_key_value_heads=2, head_dim=16,
             rms_norm_eps=1e-6, rope_theta=1e6, num_experts=16,
             num_experts_per_tok=4, norm_topk_prob=True, vocab_size=200)


def build(rows=4, **kw):
    engine, _ = build_slot_engine(
        jax.devices()[:1], "sdar_30b_a3b_chat", buckets=(16, 32), rows=rows,
        max_new_tokens=12, page_size=8, model_overrides=TINY, seed=0, **kw)
    return engine


@pytest.fixture(scope="module")
def engine():
    return build()


@pytest.fixture(scope="module")
def ref_params(engine):
    return reference.from_program_params(engine._served)


@pytest.fixture(scope="module")
def router(engine):
    replica = InProcessReplica("r0", engine)
    yield Router([replica])
    replica.stop()


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, size=n).astype(
        np.int32)


# -- the model -----------------------------------------------------------------

def test_forward_under_the_block_mask_matches_the_reference(engine,
                                                            ref_params):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 22), 0, MASK)
    bits = np.zeros(22, bool)
    bits[[17, 20, 21]] = True
    got = engine.model.apply({"params": engine._served},
                             jnp.where(bits[None], MASK, ids))
    assert got.shape == (2, 22, 256)          # 200 rows padded to 128s
    for b in (1, 0):
        want = reference.forward(ref_params, ids[b], jnp.asarray(bits),
                                 SIZES, B, MASK)
        np.testing.assert_allclose(got[b, :, :200], want, atol=2e-6)
    assert float(got[..., 200:].max()) == float(jnp.finfo(jnp.float32).min)
    by_layer = reference.layer_by_layer(engine._served, ids[0], bits, SIZES,
                                        B, MASK, rows=(16, 4))
    np.testing.assert_allclose(by_layer, want[16:20], atol=2e-6)


def test_a_later_block_does_not_move_an_earlier_ones_logits(engine):
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, MASK)
    other = ids.at[0, 12:].set(7)
    run = lambda x: engine.model.apply({"params": engine._served}, x)  # noqa: E731
    a, b = run(ids), run(other)
    np.testing.assert_array_equal(a[0, :12], b[0, :12])
    assert float(jnp.abs(a[0, 12:] - b[0, 12:]).max()) > 0
    # inside a block both directions are open: position 12 sees 15
    assert float(jnp.abs(run(ids.at[0, 15].set(7))[0, 12]
                         - a[0, 12]).max()) > 0


def test_registry_answers_by_the_model():
    assert is_lm_model("sdar_30b_a3b_chat")
    assert lm_vocab("sdar_30b_a3b_chat") == 151936
    model = get_model("sdar_30b_a3b_chat")
    assert (model.block_length, model.mask_token_id) == (4, 151669)
    assert model.padded_vocab == 151936


def test_all_experts_held_matches_the_uncut_reference_layer():
    layer = moe.HeldExpertsMoe(16, 16, 4, 24, norm_topk_prob=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 10, 64))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    got, sown = layer.apply({"params": params}, x, mutable=["counters"])
    want = reference.experts(params, x.reshape(20, 64), SIZES)
    np.testing.assert_allclose(got.reshape(20, 64), want, atol=1e-6)
    counted = step_counters(sown["counters"])
    assert float(counted["moe_held_assignments"]) == 20 * 4
    assert float(counted["moe_dropped_assignments"]) == 0
    # one pass over the whole sorted order: no conditional walk
    text = jax.jit(lambda p, x: layer.apply({"params": p}, x)).lower(
        params, x).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    share = moe.HeldExpertsMoe(16, 4, 4, 24, 4)
    text = jax.jit(lambda p, x: share.apply({"params": p}, x)).lower(
        share.init(jax.random.PRNGKey(4), x)["params"], x).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text


# -- served: the engine, the scheduler and the router against generate ---------

CASES = [  # (prompt length, want, denoising steps)
    (12, 7, 4), (13, 9, 4), (15, 6, 2), (16, 5, 1), (3, 8, 4), (21, 12, 4),
    (9, 1, 2), (31, 10, 4), (14, 3, 1)]


@pytest.mark.parametrize("length,want,steps", CASES)
def test_served_tokens_and_unmask_order_are_generates(
        router, ref_params, length, want, steps):
    prompt = prompt_of(length, seed=length)
    res = router.submit(prompt, max_new_tokens=want,
                        denoising_steps=steps).result(timeout=120)
    tokens, order = reference.generate(ref_params, prompt, want, B, steps,
                                       SIZES, MASK)
    np.testing.assert_array_equal(res.tokens, tokens)
    np.testing.assert_array_equal(res.unmask_steps, order)
    assert len(res.tokens) == want
    assert res.last_logits.shape == (256,)


def test_a_prompt_may_hold_the_mask_id(router, ref_params):
    prompt = prompt_of(14, seed=5)
    prompt[[2, 9, 13]] = MASK          # 13 is in the prompt's remainder
    res = router.submit(prompt, max_new_tokens=7).result(timeout=120)
    tokens, order = reference.generate(ref_params, prompt, 7, B, B, SIZES,
                                       MASK)
    np.testing.assert_array_equal(res.tokens, tokens)
    np.testing.assert_array_equal(res.unmask_steps, order)


def test_the_kept_logits_are_the_first_denoise_steps(router, ref_params):
    prompt = prompt_of(18, seed=6)
    res = router.submit(prompt, max_new_tokens=5).result(timeout=120)
    # 18 = 16 + 2: the first window is [16, 20), its last position 19
    ids = np.concatenate([prompt, np.zeros(2, np.int32)])
    bits = np.arange(20) >= 18
    want = reference.forward(ref_params, jnp.asarray(ids), jnp.asarray(bits),
                             SIZES, B, MASK)[19]
    np.testing.assert_allclose(res.last_logits[:200], want, atol=2e-6)


def test_the_stream_is_the_same_whatever_the_slot_order_and_company(
        router):
    mine = prompt_of(19, seed=7)
    alone = router.submit(mine, max_new_tokens=11).result(timeout=120)
    for round_ in range(2):
        others = [router.submit(prompt_of(5 + 6 * i, seed=20 + i + round_),
                                max_new_tokens=3 + 2 * i,
                                denoising_steps=(4, 2, 1)[i % 3])
                  for i in range(3 + 2 * round_)]
        again = router.submit(mine, max_new_tokens=11)
        late = [router.submit(prompt_of(9, seed=40), max_new_tokens=12)]
        res = again.result(timeout=120)
        np.testing.assert_array_equal(res.tokens, alone.tokens)
        np.testing.assert_array_equal(res.unmask_steps, alone.unmask_steps)
        np.testing.assert_array_equal(res.last_logits, alone.last_logits)
        for h in others + late:
            h.result(timeout=120)


def test_requests_the_engine_cannot_serve_are_refused_not_served(router):
    with pytest.raises(ValueError, match="temperature 0"):
        router.submit(prompt_of(8), max_new_tokens=4,
                      temperature=0.7).result(timeout=60)
    with pytest.raises(ValueError, match="does not divide"):
        router.submit(prompt_of(8), max_new_tokens=4,
                      denoising_steps=3).result(timeout=60)
    # and the replica serves on
    assert len(router.submit(prompt_of(8), max_new_tokens=4).result(
        timeout=60).tokens) == 4


# -- the step program, driven by hand ------------------------------------------

def pool_bytes(eng):
    return [np.asarray(x).copy() for x in jax.tree_util.tree_leaves(
        eng._pool)]


def test_nothing_is_written_before_a_commit_and_phases_share_a_step():
    eng = build(rows=3)
    sched = BlockDiffusionScheduler(eng, RequestQueue(eng.config.buckets))
    first = Request(prompt_of(13, seed=1), max_new_tokens=6)
    assert sched._try_admit(first)
    jax.block_until_ready(eng._control["tok"])
    after_prefill = pool_bytes(eng)
    # 13 = 12 + 1: three positions to denoise, one a step, then the commit
    for step in range(3):
        eng.block_step()
        control = jax.device_get(eng._control)
        slot = next(iter(sched.running))
        assert control["steps_done"][slot] == step + 1
        assert int(control["win_masked"][slot].sum()) == 2 - step
        assert control["emitted"][slot] == 0
        for a, b in zip(pool_bytes(eng), after_prefill):
            np.testing.assert_array_equal(a, b)
    # a second request joins mid-block: it denoises while the first commits
    second = Request(prompt_of(8, seed=2), max_new_tokens=4,
                     denoising_steps=2)
    assert sched._try_admit(second)
    other = next(s for s in sched.running if s != slot)
    eng.block_step()
    control = jax.device_get(eng._control)
    assert control["emitted"][slot] == 3 and control["positions"][slot] == 16
    assert control["steps_done"][slot] == 0
    assert control["win_masked"][slot].all()
    assert control["steps_done"][other] == 1
    assert int(control["win_masked"][other].sum()) == 2
    assert control["emitted"][other] == 0
    changed = [not np.array_equal(a, b)
               for a, b in zip(pool_bytes(eng), after_prefill)]
    assert all(changed)
    # the committed block's K/V: what a prefill of the final ids would write
    page_row = eng._page_table[slot]
    k_view = np.asarray(gather_paged_kv(
        eng._pool, jnp.asarray(page_row[None]))[0])[:, 0, 12:16]
    ids = np.concatenate([first.tokens, control["out_buf"][slot][:3]])
    _, cache = eng.model.apply(
        {"params": eng._served}, jnp.asarray(ids[None]), train=False,
        cache=eng.model.init_cache(1, 16))
    want = np.stack([np.asarray(c[0][0, 12:16]) for c in cache])
    np.testing.assert_allclose(k_view, want, atol=1e-6)


def test_the_host_mirror_replays_the_static_schedule(ref_params):
    """`left` moves by a block at a commit and by nothing at a denoise step,
    with no fetch; the first token lands at the first commit's fence."""
    eng = build(rows=2)
    sched = BlockDiffusionScheduler(eng, RequestQueue(eng.config.buckets))
    req = sched.queue.submit(prompt_of(14, seed=3), max_new_tokens=9,
                             denoising_steps=2)
    seen = []
    sched.burst_steps = 1
    while sched.step():
        for st in sched.running.values():
            seen.append((st.left, st.to_commit,
                         req.t_first_token is not None))
    # 14 = 12 + 2: two positions in one step of two, then the commit; later
    # blocks two steps and the commit; 9 = 2 + 4 + 3
    assert [s[0] for s in seen] == [9, 7, 7, 7, 3, 3, 3]
    assert [s[2] for s in seen] == [False] + [True] * 6
    res = req.result(timeout=10)
    tokens, order = reference.generate(ref_params, req.tokens, 9, B, 2,
                                       SIZES, MASK)
    np.testing.assert_array_equal(res.tokens, tokens)
    np.testing.assert_array_equal(res.unmask_steps, order)
    assert denoise_steps_of(3, 2) == 2 and denoise_steps_of(4, 4) == 1


def test_the_block_step_donates_pool_and_control():
    from distributed_pytorch_training_tpu.analysis import hlo_rules

    eng = build(rows=2)
    artifacts = hlo_rules.paged_serving_artifacts(eng)
    # the pool's two buffers and every control leaf alias in place
    artifacts.config["paged_cache_leaves"] = 2 + len(eng._control)
    assert hlo_rules.check_paged_pool_donated(artifacts) == []


# -- which engine, which scheduler, and what they refuse -----------------------

def test_the_model_chooses_its_engine_and_its_scheduler(engine):
    assert type(engine) is BlockDiffusionEngine
    assert engine.scheduler_cls is BlockDiffusionScheduler
    assert engine.kv_path == "gather" and not engine.prefix_skip_enabled
    plain, _ = build_slot_engine(
        jax.devices()[:1], "gpt2_124m", buckets=(8,), rows=2,
        max_new_tokens=4, page_size=8, model_overrides=dict(
            hidden_dim=32, depth=1, num_heads=2, vocab_size=64))
    assert type(plain) is SlotEngine
    assert plain.scheduler_cls is ContinuousScheduler
    with pytest.raises(ValueError, match="BlockDiffusionEngine"):
        BlockDiffusionScheduler(plain, RequestQueue((8,)))
    with pytest.raises(ValueError, match="block_length > 1"):
        BlockDiffusionEngine(plain.model, plain.mesh, plain.config,
                             plain._served)


def test_skip_resume_and_speculation_raise_for_such_a_model(engine):
    for lower in (engine.lower_paged_skip,
                  lambda: engine.lower_paged_resume(16)):
        with pytest.raises(ValueError, match="generates by blocks"):
            lower()
    with pytest.raises(ValueError, match="generates by blocks"):
        build_spec_engine(jax.devices()[:1], "sdar_30b_a3b_chat", "gpt2_124m",
                          buckets=(16,), rows=2, max_new_tokens=8,
                          page_size=8, model_overrides=TINY,
                          draft_overrides=dict(hidden_dim=32, depth=1,
                                               num_heads=2, vocab_size=200))
    with pytest.raises(ValueError, match="whole blocks"):
        build_slot_engine(jax.devices()[:1], "sdar_30b_a3b_chat",
                          buckets=(16,), rows=2, max_new_tokens=8,
                          page_size=6, model_overrides=TINY)


# -- the window read -----------------------------------------------------------

def window_case(w, hq, hkv, d, seed=0):
    rows, ps, per_row = 5, 8, 6
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, *s: jax.random.normal(k, s, jnp.float32)  # noqa: E731
    k_all = normal(keys[0], rows, per_row * ps, hkv, d)
    v_all = normal(keys[1], rows, per_row * ps, hkv, d)
    table = jnp.asarray((1 + np.arange(rows * per_row, dtype=np.int32)
                         ).reshape(rows, per_row))
    positions = jnp.broadcast_to(jnp.arange(per_row * ps),
                                 (rows, per_row * ps))
    pool = scatter_paged_window(
        init_paged_kv(1, rows * per_row + 1, ps, hkv, d), table, positions,
        k_all[None], v_all[None], jnp.ones(positions.shape, bool))
    q = normal(keys[2], rows, w, hq, d)
    k_new, v_new = normal(keys[3], rows, w, hkv, d), \
        normal(keys[4], rows, w, hkv, d)
    # a dead row and a row at position 0 read nothing; ragged ones between
    live = jnp.asarray([0, 0, 8, 28, 44], jnp.int32)
    return pool, table, q, k_new, v_new, live


@pytest.mark.parametrize("w,group", [(1, 1), (1, 8), (4, 1), (4, 8)])
def test_window_kernel_matches_the_gather_form(w, group):
    hkv, d = 2, 16
    hq = hkv * group
    pool, table, q, k_new, v_new, live = window_case(w, hq, hkv, d)
    flat = lambda x: x.reshape(x.shape[0], w, -1)  # noqa: E731
    got = paged_attention(flat(q), flat(k_new), flat(v_new), pool.k, pool.v,
                          table, live, layer=0, num_heads=hq,
                          num_kv_heads=hkv, pages_per_chunk=2)
    views = tuple(v[0] for v in gather_paged_kv(pool, table))
    want, _ = attend_window_views(q, k_new, v_new, views, live, jnp.float32)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=2e-6)
    # a row with nothing cached attends its own window alone
    alone = dot_product_attention(
        q[:1], jnp.repeat(k_new[:1], group, 2), jnp.repeat(v_new[:1], group, 2))
    np.testing.assert_allclose(got.reshape(want.shape)[:1], alone, atol=2e-6)


def test_window_supports_says_what_the_tile_needs(monkeypatch):
    pa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.paged_attention")
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    ok = dict(window=4, num_heads=32, num_kv_heads=4)
    assert paged_attention_supports(64, 512, jnp.bfloat16, **ok)
    assert not paged_attention_supports(64, 256, jnp.bfloat16, **ok)  # d=64
    assert not paged_attention_supports(8, 512, jnp.bfloat16, **ok)
    assert not paged_attention_supports(64, 512, jnp.bfloat16, window=4,
                                        num_heads=30, num_kv_heads=4)
    assert paged_attention_supports(16, 768, jnp.bfloat16)   # the S=1 call


def test_served_on_the_kernel_read_in_interpreter_mode(monkeypatch,
                                                       ref_params):
    monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                        lambda: True)
    eng = build(rows=2)
    assert eng.kv_path == "kernel"
    replica = InProcessReplica("k0", eng)
    try:
        prompt = prompt_of(13, seed=8)
        res = replica.submit(prompt, max_new_tokens=6).result(timeout=300)
    finally:
        replica.stop()
    tokens, order = reference.generate(ref_params, prompt, 6, B, B, SIZES,
                                       MASK)
    np.testing.assert_array_equal(res.tokens, tokens)
    np.testing.assert_array_equal(res.unmask_steps, order)


# -- the flash forward under the block mask ------------------------------------

@pytest.mark.parametrize("seq,block", [(64, None), (64, 16), (2048, None)])
def test_flash_forward_under_the_block_mask(seq, block):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(x, (1, seq, 2, 16)) for x in keys)
    got = flash_attention(q, k, v, True, None, block, block, None, 4)
    want = dot_product_attention(q, k, v, mask=block_causal_mask(seq, 4))
    np.testing.assert_allclose(got, want, atol=2e-6)
    causal = flash_attention(q, k, v, True, None, block, block)
    assert float(jnp.abs(causal - got).max()) > 1e-3


def test_flash_block_mask_is_forward_only_and_checks_its_blocks():
    q = jnp.ones((1, 32, 1, 8))
    with pytest.raises(NotImplementedError, match="causal_block=4"):
        jax.grad(lambda q: flash_attention(
            q, q, q, True, None, None, None, None, 4).sum())(q)
    with pytest.raises(ValueError, match="power of"):
        flash_attention(q, q, q, True, None, None, None, None, 3)
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, q, q, False, None, None, None, None, 4)
    fn = make_flash_attention_fn(causal=True, causal_block=4)
    np.testing.assert_allclose(
        fn(q, q, q), dot_product_attention(
            q, q, q, mask=block_causal_mask(32, 4)), atol=1e-6)


# -- the other cells' programs, unchanged --------------------------------------

# -- lowering cases begin (exec'd as they stand on the parent's tree too) --
def lowered_for_a_tpu(fn, *shapes):
    """The text of ``fn`` lowered for a TPU from here, Mosaic and all,
    without locations (`runtime/dist.py` leaves them out of the compile
    cache's key for the same reason: an edit that moves a line moves
    them)."""
    def program(*args):
        return fn(*args)

    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return jax.jit(program).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)


def without_locations(lower):
    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return lower().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)


def expert_layer_text(layer, tokens, hidden):
    """An expert layer's program over (1, tokens, hidden) in bf16."""
    x = jax.ShapeDtypeStruct((1, tokens, hidden), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype))["params"])

    def program(p, x):
        return layer.apply({"params": p}, x, mutable=["counters"])

    return without_locations(lambda: jax.jit(program).lower(params, x))


def decode_step_text(continuous, get_model, build_mesh, MeshSpec,
                     PagedServeConfig):
    """The paged decode step of a small GPT-2 on the kernel read (the
    kernel in interpreter mode, inlined)."""
    real = continuous.paged_attention_backend_supported
    continuous.paged_attention_backend_supported = lambda: True
    try:
        model = get_model("gpt2_124m", dtype=jnp.bfloat16, hidden_dim=64,
                          depth=2, num_heads=4, vocab_size=300,
                          max_position=64)
        params = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(
                    (1, 8), jnp.int32))["params"]))
        engine = continuous.SlotEngine(
            model, build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]),
            PagedServeConfig(buckets=(16,), rows=4, max_new_tokens=8,
                             page_size=8, serve_dtype="bf16"), params)
        return without_locations(engine.lower_paged_decode)
    finally:
        continuous.paged_attention_backend_supported = real


def lowering_cases(pa, fa, moe_module):
    """name -> lowered text of the calls the other cells' programs make:
    the decode step's read at the GPT-2 124M serve cells' shape
    (`paged_attention`, one query row of 12 heads); a GPT-2 train step's
    flash calls (forward and both backward kernels, 8 x 16 heads of 64 at S
    = 1024); DeepSeek-V2's prefill forward (keys of 192, values of 128); the
    hybrid cell's and the DeepSeek-V2 cell's expert layers (`HeldExpertsMoe`
    with a share, at their published widths)."""
    S = jax.ShapeDtypeStruct
    rows, ps, per_row, width = 64, 16, 66, 768
    pool = S((12, rows * per_row + 1, ps, width), jnp.bfloat16)
    row = S((rows, width), jnp.bfloat16)
    fn = fa.make_flash_attention_fn(causal=True)
    x = S((8, 1024, 16, 64), jnp.bfloat16)
    wide = S((1, 2560, 128, 192), jnp.bfloat16)

    def loss(q, k, v):
        return fn(q, k, v, dtype=jnp.bfloat16).astype(jnp.float32).sum()

    return {
        "decode_read": lowered_for_a_tpu(
            lambda q, kf, vf, kp, vp, table, live: pa.paged_attention(
                q, kf, vf, kp, vp, table, live, layer=3, num_heads=12),
            row, row, row, pool, pool, S((rows, per_row), jnp.int32),
            S((rows,), jnp.int32)),
        "train_flash": lowered_for_a_tpu(
            jax.grad(loss, argnums=(0, 1, 2)), x, x, x),
        "prefill_flash": lowered_for_a_tpu(
            lambda q, k, v: fn(q, k, v, dtype=jnp.bfloat16), wide, wide,
            S((1, 2560, 128, 128), jnp.bfloat16)),
        "hybrid_experts": expert_layer_text(moe_module.HeldExpertsMoe(
            512, 32, 10, 512, 0, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16), 8192, 2048),
        "dsv2_experts": expert_layer_text(moe_module.HeldExpertsMoe(
            160, 20, 6, 1536, 0, n_group=8, topk_group=3,
            norm_topk_prob=False, routed_scaling_factor=16.0,
            router_init_std=0.0035, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16), 112, 5120),
    }
# -- lowering cases end --


# sha256 of `lowering_cases`' texts, taken with this file's own code and this
# installation's jax: `decode_read`, `train_flash`, `prefill_flash` and
# `decode_step` on the PARENT of PR 42 (commit ab68caa), and unchanged since;
# `hybrid_experts` and `dsv2_experts` on PR 50's own tree: PR 47 moved both
# by design (`models.moe.take_rows` / `sum_rows`: the expert layer's rows
# come back by gathers; until then they were ab68caa's too, 1997a4d6... and
# 7dbb09fe...) to 9db5bf21... and 34ef10be..., and PR 50's names on the
# router's sorts (`moe.ROUTE_NAMES`; a name lowers to no operation) moved
# the counter in the private functions' names (`@_where_25` is
# `@_where_26`) and nothing else: with `@name_<n>` read as `@name` both
# texts are PR 47's letter for letter
PARENTS = {
    "decode_read":
        "a5140bec0bdf4c257e4a7bc71d3e4d4a"
        "cee98cc8bf2cec46b8efa5fbde3afbdf",
    "train_flash":
        "345a96debbf0d72cbfe67ac0960fadc8"
        "b6172cf992ca32d1f4bd3ce3ef7224b4",
    "prefill_flash":
        "db4306ee17160cfe5dca3592aae8632b"
        "a86efd5adc36d109e03fc00b9d140cd5",
    "hybrid_experts":
        "336e5b82b421c64c5fdb34594dbb8fb4"
        "56843edd3aa0176d18b76feb16fcfc6c",
    "dsv2_experts":
        "4329756dd403e2de36e661ec20160b9a"
        "6d2d594cb1bb507f631391319fbf057f",
    "decode_step":
        "304b4476f2b5f75b11a8d7e13353d430"
        "360d6ff0187f1f232f222b49e526668b",
}


@pytest.fixture(scope="module")
def lowered(request):
    pa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.paged_attention")
    fa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")
    real = pa._interpret, fa._interpret
    pa._interpret = fa._interpret = lambda: False
    try:
        texts = lowering_cases(pa, fa, moe)
        S = jax.ShapeDtypeStruct
        x = S((8, 1024, 16, 64), jnp.bfloat16)
        said = fa.make_flash_attention_fn(causal=True, causal_block=1)
        texts["said_aloud"] = lowered_for_a_tpu(
            jax.grad(lambda q, k, v: said(
                q, k, v, dtype=jnp.bfloat16).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)), x, x, x)
        texts["blocks"] = lowered_for_a_tpu(
            lambda q, k, v: fa.flash_attention(
                q, k, v, True, None, None, None, None, 4),
            *(S((1, 512, 32, 128), jnp.bfloat16),) * 3)
    finally:
        pa._interpret, fa._interpret = real
    texts["decode_step"] = decode_step_text(
        continuous, get_model, build_mesh, MeshSpec, PagedServeConfig)
    return texts


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_the_other_cells_calls_lower_to_the_parents_text(lowered, name):
    """The GPT-2 serve cells' `paged_attention` at W = 1, the train cells'
    flash kernels at ``causal_block`` 1 and DeepSeek-V2's prefill forward:
    letter for letter what the parent lowered."""
    assert hashlib.sha256(lowered[name].encode()).hexdigest() \
        == PARENTS[name]


def test_causal_block_one_said_aloud_is_the_causal_program(lowered):
    assert lowered["said_aloud"] == lowered["train_flash"]
    assert "tpu_custom_call" in lowered["blocks"]
    assert lowered["blocks"] != lowered["prefill_flash"]


def test_the_block_step_lowers_for_a_tpu_on_the_kernel_read(monkeypatch):
    pa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.paged_attention")
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                        lambda: True)
    wide = dict(TINY, head_dim=128, hidden_dim=128)   # heads of whole tiles
    eng, _ = build_slot_engine(
        jax.devices()[:1], "sdar_30b_a3b_chat", buckets=(16,), rows=4,
        max_new_tokens=8, page_size=16, serve_dtype="bf16",
        model_overrides=wide)
    assert eng.kv_path == "kernel"
    fn = eng._make_paged_decode()
    avals = (eng._served, eng._pool_avals(), eng._control_avals(),
             eng._row_aval((4, eng.config.pages_per_slot), jnp.int32))
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    # one kernel for both layers (the layer is a run-time scalar)
    assert text.count("tpu_custom_call") >= 1
    assert "paged_attention" in text

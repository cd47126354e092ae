"""serving/ — what every engine stands on, the forward engine, and the
CLI's load test (ISSUE 10; one server for a causal LM since ISSUE 51).

Pins, in order:
* the cache-aware GPT-2 forward leaves the no-cache training path
  BYTE-IDENTICAL HLO (lowering test against a pre-cache reference copy);
* prefill logits match the full-context forward BITWISE in fp32; decode
  logits, and mixed-length batches vs solo forwards, within 8 eps of the
  largest logit (`assert_logits_match`: other XLA:CPU programs);
* a causal LM has ONE server: the forward engine refuses it by name of
  its builder, and a token engine carries nothing of the forward engine;
* int8 weight serving reuses the wire-codec grid (bound + grid match) and
  serves through the token engine;
* a manifest-verified checkpoint serves through the token engine, torn
  ones skipped as a resume would skip them;
* the request queue, the forward engine's loop and its drain, on BERT;
* the `no-host-sync-in-decode` AST rule (mutation-tested);
* `measure_serving_continuous` (the CLI's `bench` row), `serving smoke` in
  process, and the slow CLI e2e.

The token engine's own pins (bitwise streams, zero recompiles, the
``serving_paged`` contract) are tests/test_continuous.py's.
"""

import json
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.models import get_model
from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.serving import (
    ContinuousScheduler, InferenceEngine, PagedServeConfig, QuantizedLeaf,
    RequestQueue, ServeConfig, SlotEngine, dequantize_params, drain,
    int8_weight_bytes, quantize_params, serve_forever,
)

VOCAB = 97
TINY = dict(hidden_dim=32, depth=2, num_heads=2, vocab_size=VOCAB)


def tiny_model(**kw):
    cfg = dict(max_position=64, **TINY)
    cfg.update(kw)
    return GPT2LMHead(**cfg)


@pytest.fixture(scope="module")
def tiny(mesh8):
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    return model, params


@pytest.fixture(scope="module")
def bert(mesh8):
    """A tiny BERT: the token model WITHOUT a cache, the forward engine's."""
    model = get_model("bert_base", mlp_dim=64, max_position=64, **TINY)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    return model, params


@pytest.fixture(scope="module")
def engine(mesh8, bert):
    model, params = bert
    eng = InferenceEngine(model, mesh8, ServeConfig(buckets=(8, 16), rows=8),
                          params)
    eng.warmup()
    return eng


def prompts(ns, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in ns]


def paged_cfg(**kw):
    cfg = dict(buckets=(8,), rows=8, max_new_tokens=2, page_size=4)
    cfg.update(kw)
    return PagedServeConfig(**cfg)


def serve_all(eng, seqs, **kw):
    """Every prompt through a fresh scheduler over ``eng``, drained."""
    q = RequestQueue(eng.config.buckets)
    sched = ContinuousScheduler(eng, q)
    reqs = [q.submit(p, **kw) for p in seqs]
    sched.drain()
    return [r.result(timeout=300.0) for r in reqs]


# ---------------------------------------------------------------------------
# The cache-aware forward: HLO identity + logit parity
# ---------------------------------------------------------------------------


def assert_logits_match(got, want, what=""):
    """Cached logits against the full-context forward's, where the two are
    DIFFERENT XLA programs (a query of length 1, or another batch, against
    the whole sequence). They were written as `==` on jax 0.4.x, whose
    XLA:CPU gave both the same bits; jax 0.9.0's picks its dot kernel by
    shape and reassociates the contraction. Found (PR 30): max abs 1.64e-7
    on logits of 0.37 at most, 4.5e-7 of the largest logit (up to 5,752
    ulps of a logit near zero, so no ulp count of the element is the
    bound). Held to
    8 eps of the largest logit (9.5e-7 of it), the next power of two over
    what was found: every logit is a sum of products of that scale. Where
    the programs are the same one, the tests below still say `==`."""
    want = np.asarray(want)
    bound = 8 * np.finfo(np.float32).eps * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=bound,
                               err_msg=what)


class TestCacheForward:
    def test_no_cache_lowering_byte_identical(self, tiny):
        """The cache plumbing contributes ZERO ops when off: lowering the
        new module's no-cache forward is byte-identical to a verbatim copy
        of the PRE-CACHE module (same submodule names, so the texts align
        exactly — flax does not leak class names into HLO)."""
        import functools

        import flax.linen as nn

        from distributed_pytorch_training_tpu.models.layers import (
            MlpBlock, causal_mask, dot_product_attention,
            mask_vocab_padding,
        )

        class RefMHA(nn.Module):  # the pre-cache MultiHeadAttention
            num_heads: int
            head_dim: int

            @nn.compact
            def __call__(self, x, mask=None, deterministic=True):
                dense = functools.partial(nn.DenseGeneral,
                                          dtype=jnp.float32,
                                          param_dtype=jnp.float32,
                                          use_bias=True)
                qkv = dense(features=(3, self.num_heads, self.head_dim),
                            name="qkv")(x)
                q, k, v = (qkv[..., 0, :, :], qkv[..., 1, :, :],
                           qkv[..., 2, :, :])
                y = dot_product_attention(q, k, v, mask=mask,
                                          dtype=jnp.float32)
                return nn.DenseGeneral(features=x.shape[-1], axis=(-2, -1),
                                       dtype=jnp.float32,
                                       param_dtype=jnp.float32,
                                       use_bias=True, name="out")(y)

        class RefBlock(nn.Module):  # the pre-cache TransformerBlock
            num_heads: int
            head_dim: int
            mlp_dim: int

            @nn.compact
            def __call__(self, x, mask=None, deterministic=True):
                ln = functools.partial(nn.LayerNorm, epsilon=1e-5,
                                       dtype=jnp.float32,
                                       param_dtype=jnp.float32)
                y = ln(name="ln1")(x)
                y = RefMHA(num_heads=self.num_heads,
                           head_dim=self.head_dim, name="attn")(
                    y, mask=mask, deterministic=deterministic)
                x = x + y
                y = ln(name="ln2")(x)
                y = MlpBlock(hidden_dim=self.mlp_dim, dtype=jnp.float32,
                             param_dtype=jnp.float32, name="mlp",
                             )(y, deterministic=deterministic)
                return x + y

        class RefGPT2(nn.Module):  # the pre-cache GPT2LMHead.__call__
            @nn.compact
            def __call__(self, input_ids, train=False):
                b, s = input_ids.shape
                wte = nn.Embed(VOCAB, 32, dtype=jnp.float32,
                               param_dtype=jnp.float32,
                               embedding_init=nn.initializers.normal(
                                   stddev=0.02), name="wte")
                x = wte(input_ids)
                pos_ids = jnp.arange(s)[None, :]
                x = x + nn.Embed(64, 32, dtype=jnp.float32,
                                 param_dtype=jnp.float32,
                                 embedding_init=nn.initializers.normal(
                                     stddev=0.01), name="wpe")(pos_ids)
                mask = causal_mask(s)
                for i in range(2):
                    x = RefBlock(num_heads=2, head_dim=16, mlp_dim=128,
                                 name=f"block{i}")(x, mask=mask,
                                                   deterministic=not train)
                x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32,
                                 param_dtype=jnp.float32, name="ln_f")(x)
                logits = wte.attend(x)
                return mask_vocab_padding(logits.astype(jnp.float32),
                                          VOCAB)

        model, params = tiny
        ids = np.zeros((4, 8), np.int32)
        new_text = jax.jit(
            lambda p, i: model.apply({"params": p}, i, train=False)
        ).lower(params, ids).as_text()
        ref_text = jax.jit(
            lambda p, i: RefGPT2().apply({"params": p}, i, train=False)
        ).lower(params, ids).as_text()
        assert new_text == ref_text

    def test_prefill_is_eval_forward_bitwise(self, tiny):
        model, params = tiny
        rng = np.random.RandomState(1)
        ids = rng.randint(0, VOCAB, (3, 12)).astype(np.int32)
        ev = model.apply({"params": params}, ids, train=False)
        cache0 = model.init_cache(3, 16)
        pre, _cache = model.apply({"params": params}, ids, train=False,
                                  cache=cache0)
        assert bool(jnp.all(pre == ev))

    def test_prefill_decode_matches_full_forward(self, tiny):
        """The satellite pin: prefill over the prompt reproduces the
        full-context forward's logits BITWISE in fp32, and K forced decode
        steps reproduce them as far as two XLA:CPU programs can
        (`assert_logits_match`)."""
        model, params = tiny
        rng = np.random.RandomState(2)
        B, S, K = 3, 12, 4
        ids = rng.randint(0, VOCAB, (B, S + K)).astype(np.int32)
        full = model.apply({"params": params}, ids, train=False)
        cache = model.init_cache(B, S + K)
        pre, cache = model.apply({"params": params}, ids[:, :S],
                                 train=False, cache=cache)
        assert bool(jnp.all(pre == full[:, :S]))
        dec = []
        for k in range(K):
            pos = jnp.full((B,), S + k, jnp.int32)
            lg, cache = model.apply({"params": params},
                                    ids[:, S + k][:, None], train=False,
                                    cache=cache, cache_positions=pos)
            dec.append(lg[:, 0])
        assert_logits_match(jnp.stack(dec, axis=1), full[:, S:])

    def test_mixed_length_decode_matches_solo_forward(self, tiny):
        """Rows at DIFFERENT prompt lengths decode in one batch; each
        row's logits are its own solo full-context forward's
        (`assert_logits_match`: a batch of 3 x 12 against 1 x n+1) —
        padding and batch company are invisible."""
        model, params = tiny
        rng = np.random.RandomState(3)
        B, S = 3, 12
        lens = [5, 12, 9]
        toks = rng.randint(0, VOCAB, (B, S + 2)).astype(np.int32)
        ids = np.zeros((B, S), np.int32)
        for i, n in enumerate(lens):
            ids[i, :n] = toks[i, :n]
        cache = model.init_cache(B, S + 4)
        pre, cache = model.apply({"params": params}, ids, train=False,
                                 cache=cache)
        pos = jnp.asarray(lens, jnp.int32)
        nxt = jnp.asarray([toks[i, lens[i]] for i in range(B)],
                          jnp.int32)[:, None]
        lg, cache = model.apply({"params": params}, nxt, train=False,
                                cache=cache, cache_positions=pos)
        for i, n in enumerate(lens):
            solo = model.apply({"params": params}, toks[i:i + 1, :n + 1],
                               train=False)
            assert_logits_match(pre[i, :n], solo[0, :n], f"row {i}")
            assert_logits_match(lg[i, 0], solo[0, n], f"row {i} decode")

    def test_kernel_attention_with_cache_raises(self):
        def fake_kernel(q, k, v, mask=None, dtype=jnp.float32):
            return q

        model = tiny_model(attention_fn=fake_kernel)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32),
                            train=False)["params"]
        with pytest.raises(ValueError, match="XLA attention path"):
            model.apply({"params": params}, np.zeros((1, 8), np.int32),
                        train=False, cache=model.init_cache(1, 12))


# ---------------------------------------------------------------------------
# One server for a causal LM, a forward engine for the rest
# ---------------------------------------------------------------------------


class TestEngine:
    def test_forward_engine_refuses_a_causal_lm(self, mesh8, tiny):
        """A model with a cache has one server; the refusal names the
        builder of its engine."""
        model, params = tiny
        with pytest.raises(ValueError, match="build_slot_engine"):
            InferenceEngine(model, mesh8, ServeConfig(buckets=(8,), rows=8),
                            params)

    def test_token_engine_carries_nothing_of_the_forward_engine(self):
        from distributed_pytorch_training_tpu.serving.block_diffusion import (
            BlockDiffusionEngine,
        )
        from distributed_pytorch_training_tpu.serving.engine import (
            ServedModel,
        )
        from distributed_pytorch_training_tpu.serving.speculative import (
            SpeculativeEngine,
        )

        assert SlotEngine.__mro__ == (SlotEngine, ServedModel, object)
        for cls in (SlotEngine, BlockDiffusionEngine, SpeculativeEngine):
            for name in ("serve_tokens", "lower_decode", "generate",
                         "serve_images", "kv_cache_bytes"):
                assert not hasattr(cls, name), (cls.__name__, name)
        # and the base knows neither of its engines' models
        assert not hasattr(ServedModel, "serves_blocks")

    def test_forward_engine_serves_the_eval_forward_bitwise(self, mesh8,
                                                            bert, engine):
        """fp32 served logits == the compiled, sharded eval forward,
        bitwise, for the same (padded) inputs; nothing is generated; a
        request served alone and packed with company reads the same."""
        from distributed_pytorch_training_tpu.data.pack import (
            pack_token_rows,
        )
        from distributed_pytorch_training_tpu.parallel.sharding import (
            shard_batch,
        )

        model, _params = bert
        seqs = prompts((3, 8, 5))
        ids, _lengths, _ = pack_token_rows(seqs, 8, engine.config.rows)
        ev = np.asarray(jax.jit(
            lambda p, i: model.apply({"params": p}, i, train=False)
        )(engine._served, shard_batch(ids, mesh8)))
        before = engine.compiles
        packed = engine.serve_tokens(seqs, return_prompt_logits=True)
        for i, res in enumerate(packed):
            L = len(seqs[i])
            assert res.tokens.size == 0
            assert (res.prompt_logits == ev[i, :L]).all(), f"request {i}"
            np.testing.assert_array_equal(res.last_logits, ev[i, L - 1])
        solo = engine.serve_tokens([seqs[0]], return_prompt_logits=True)[0]
        np.testing.assert_array_equal(solo.prompt_logits,
                                      packed[0].prompt_logits)
        assert engine.compiles == before, "a request triggered a recompile"

    def test_config_validation(self, mesh8, tiny, bert):
        model, params = tiny
        with pytest.raises(ValueError, match="serve_dtype"):
            ServeConfig(serve_dtype="fp16")
        with pytest.raises(ValueError, match="divide over the mesh"):
            InferenceEngine(bert[0], mesh8,
                            ServeConfig(buckets=(8,), rows=3), bert[1])
        with pytest.raises(ValueError, match="PagedServeConfig"):
            SlotEngine(model, mesh8, ServeConfig(buckets=(8,), rows=8),
                       params)
        with pytest.raises(ValueError, match="max_position"):
            SlotEngine(model, mesh8,
                       paged_cfg(buckets=(64,), max_new_tokens=8), params)
        with pytest.raises(ValueError, match="causal LMs only"):
            SlotEngine(bert[0], mesh8, paged_cfg(), bert[1])
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            SlotEngine(model, mesh8, paged_cfg(), params).admit(
                0, prompts((9,))[0], 2, 0.0, 1.0, 0)


class TestInt8Serving:
    def test_quantize_grid_matches_wire_codec(self, tiny):
        """The serve-side weight quantizer IS the wire codec's grid: same
        codes, same scales as grad_sync._quantize_int8_rows on the same
        rows."""
        from distributed_pytorch_training_tpu.parallel.grad_sync import (
            _quantize_int8_rows,
        )

        _model, params = tiny
        served = quantize_params(params, min_elements=64)
        leaves = {
            path: leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(
                served, is_leaf=lambda x: isinstance(x, QuantizedLeaf))}
        quantized = [(p, l) for p, l in leaves.items()
                     if isinstance(l, QuantizedLeaf)]
        assert quantized, "nothing got quantized"
        orig = dict(jax.tree_util.tree_leaves_with_path(params))
        for path, ql in quantized:
            rows = np.asarray(orig[path], np.float32).reshape(
                -1, orig[path].shape[-1])
            q_ref, s_ref = _quantize_int8_rows(jnp.asarray(rows),
                                               fused=False)
            np.testing.assert_array_equal(
                np.asarray(ql.q).reshape(q_ref.shape), np.asarray(q_ref))
            np.testing.assert_array_equal(
                np.asarray(ql.scale).ravel(), np.asarray(s_ref))

    def test_dequant_error_bound(self, tiny):
        """One-shot error <= scale/2 per element (the wire codec's bound,
        no error feedback — weights are static); un-quantized leaves pass
        through exact."""
        _model, params = tiny
        served = quantize_params(params, min_elements=64)
        deq = dequantize_params(served)
        flat_served = jax.tree_util.tree_leaves(
            served, is_leaf=lambda x: isinstance(x, QuantizedLeaf))
        flat_params = jax.tree_util.tree_leaves(params)
        flat_deq = jax.tree_util.tree_leaves(deq)
        checked = 0
        for sv, orig, back in zip(flat_served, flat_params, flat_deq):
            if not isinstance(sv, QuantizedLeaf):
                np.testing.assert_array_equal(np.asarray(orig),
                                              np.asarray(back))
                continue
            bound = np.asarray(sv.scale)[..., None] / 2 + 1e-12
            err = np.abs(np.asarray(orig, np.float32) - np.asarray(back))
            assert (err <= bound).all()
            checked += 1
        assert checked >= 2

    def test_grid_values_round_trip_exactly(self):
        """Integer-valued weights with the per-row absmax pinned to 127
        sit exactly on the codec grid (scale exactly 1.0) and round-trip
        bit-exactly — the wire codec's grid test, applied to weights."""
        rng = np.random.RandomState(0)
        w = rng.randint(-127, 128, (8, 256)).astype(np.float32)
        w[:, 0] = 127.0
        served = quantize_params(w, min_elements=1)
        assert isinstance(served, QuantizedLeaf)
        np.testing.assert_array_equal(np.asarray(served.scale), 1.0)
        np.testing.assert_array_equal(
            np.asarray(dequantize_params(served)), w)

    def test_int8_engine_serves_and_saves_bytes(self, mesh8, tiny):
        """int8 WEIGHTS through the token engine: it serves, at under 1/2.5
        of the float32 bytes, and its logits are the float32 engine's
        within the codec's reach."""
        model, params = tiny
        eng = SlotEngine(
            model, mesh8,
            paged_cfg(serve_dtype="int8", quantize_min_elements=64), params)
        (res,) = serve_all(eng, prompts((5,)))
        assert res.tokens.shape == (2,)
        assert np.isfinite(res.last_logits).all()
        (ref,) = serve_all(SlotEngine(model, mesh8, paged_cfg(), params),
                           prompts((5,)))
        assert not np.array_equal(res.last_logits, ref.last_logits)
        np.testing.assert_allclose(res.last_logits, ref.last_logits,
                                   atol=0.05 * np.abs(ref.last_logits).max())
        acct = int8_weight_bytes(eng._served)
        fp32_bytes = sum(4 * l.size
                         for l in jax.tree_util.tree_leaves(params))
        assert acct["quantized_bytes"] + acct["exact_bytes"] \
            < fp32_bytes / 2.5


# ---------------------------------------------------------------------------
# Checkpoint serving: restore_latest + provenance + torn-skip inheritance
# ---------------------------------------------------------------------------


class TestCheckpointServing:
    def _save_state(self, mesh8, model, tmp_path, labels=(1,), seed=0):
        from distributed_pytorch_training_tpu.training import (
            TrainConfig, Trainer,
        )
        from distributed_pytorch_training_tpu.training.checkpoint import (
            CheckpointManager,
        )
        from distributed_pytorch_training_tpu.training.optim import sgd
        from distributed_pytorch_training_tpu.training.tasks import (
            LanguageModelingTask,
        )

        trainer = Trainer(LanguageModelingTask(), mesh8,
                          TrainConfig(seed=0))
        state = trainer.init_state(model, np.zeros((1, 8), np.int32),
                                   sgd(0.1), jax.random.PRNGKey(seed))
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        for label in labels:
            # distinct params per label so "which label restored" is
            # observable in the served logits
            state = state.replace(params=jax.tree_util.tree_map(
                lambda p: p + 0.01 * label, state.params))
            mgr.save(label, state, epoch=label)
        mgr.close()
        return state

    def test_from_checkpoint_serves_verified_weights(self, mesh8,
                                                     tmp_path):
        from distributed_pytorch_training_tpu.training.optim import sgd

        model = tiny_model()
        state = self._save_state(mesh8, model, tmp_path, labels=(1,))
        eng = SlotEngine.from_checkpoint(
            str(tmp_path), model, mesh8, paged_cfg(),
            sgd(0.1), np.zeros((1, 8), np.int32))
        info = eng.checkpoint_info
        assert info["label"] == 1 and info["verified"]
        assert isinstance(info["tree_digest"], str) \
            and len(info["tree_digest"]) == 64
        # served weights ARE the restored params, and what they serve is
        # what an engine built over those params serves, bitwise
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               jax.device_get(eng._served),
                               jax.device_get(state.params))
        seqs = prompts((6,))
        (res,) = serve_all(eng, seqs)
        (ref,) = serve_all(
            SlotEngine(model, mesh8, paged_cfg(), state.params), seqs)
        np.testing.assert_array_equal(res.last_logits, ref.last_logits)
        np.testing.assert_array_equal(res.tokens, ref.tokens)

    def test_torn_newest_falls_back_to_previous(self, mesh8, tmp_path):
        """Serving inherits the manifest-verified restore exactly: a torn
        newest checkpoint is skipped loudly and the previous valid one
        serves."""
        from distributed_pytorch_training_tpu.training.optim import sgd

        model = tiny_model()
        self._save_state(mesh8, model, tmp_path, labels=(1, 2))
        # tear label 2: truncate one of its array files
        victims = [p for p in (tmp_path / "2").rglob("*")
                   if p.is_file() and p.stat().st_size > 64]
        victims[0].write_bytes(b"torn")
        eng = SlotEngine.from_checkpoint(
            str(tmp_path), model, mesh8, paged_cfg(),
            sgd(0.1), np.zeros((1, 8), np.int32))
        assert eng.checkpoint_info["label"] == 1

    def test_missing_checkpoint_is_loud(self, mesh8, tmp_path):
        from distributed_pytorch_training_tpu.training.optim import sgd

        with pytest.raises(FileNotFoundError, match="no restorable"):
            SlotEngine.from_checkpoint(
                str(tmp_path / "empty"), tiny_model(), mesh8, paged_cfg(),
                sgd(0.1), np.zeros((1, 8), np.int32))


# ---------------------------------------------------------------------------
# The queue, and the forward engine's loop and drain (on BERT)
# ---------------------------------------------------------------------------


class TestBatching:
    def test_queue_groups_by_bucket_in_order(self):
        q = RequestQueue((8, 16))
        a = q.submit(np.ones(4, np.int32))
        b = q.submit(np.ones(12, np.int32))
        c = q.submit(np.ones(8, np.int32))
        group = q.next_batch(max_rows=8)
        # head (bucket 8) picks; c joins; b (bucket 16) stays queued
        assert [r.id for r in group] == [a.id, c.id]
        assert [r.id for r in q.next_batch(max_rows=8)] == [b.id]

    def test_submit_rejects_oversize_and_closed(self):
        q = RequestQueue((8,))
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            q.submit(np.ones(9, np.int32))
        q.close()
        with pytest.raises(RuntimeError, match="closed"):
            q.submit(np.ones(4, np.int32))

    def test_concurrent_submit_all_served(self, engine):
        q = RequestQueue(engine.config.buckets)
        stop = threading.Event()
        worker = threading.Thread(target=serve_forever,
                                  args=(engine, q, stop), daemon=True)
        worker.start()
        reqs = []
        lock = threading.Lock()

        def submitter(seed):
            for p in prompts((3, 9, 6), seed=seed):
                r = q.submit(p)
                with lock:
                    reqs.append(r)

        threads = [threading.Thread(target=submitter, args=(s,))
                   for s in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(reqs) == 9
        for r in reqs:
            res = r.result(timeout=120.0)
            assert res.last_logits.shape == (VOCAB,)
            assert r.t_done is not None
        stop.set()
        worker.join(timeout=30.0)
        assert not worker.is_alive()

    def test_drain_completes_pending_then_refuses(self, engine):
        q = RequestQueue(engine.config.buckets)
        pending = [q.submit(p) for p in prompts((4, 7), seed=5)]
        served = drain(engine, q)
        assert served == 2
        for r in pending:
            assert np.isfinite(r.result(timeout=1.0).last_logits).all()
        with pytest.raises(RuntimeError, match="closed"):
            q.submit(np.ones(4, np.int32))

    def test_failed_batch_fails_requests_not_loop(self, engine,
                                                  monkeypatch):
        q = RequestQueue(engine.config.buckets)
        stop = threading.Event()
        real = engine.serve_tokens
        calls = {"n": 0}

        def flaky(seqs, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected")
            return real(seqs, **kw)

        monkeypatch.setattr(engine, "serve_tokens", flaky)
        worker = threading.Thread(target=serve_forever,
                                  args=(engine, q, stop), daemon=True)
        worker.start()
        bad = q.submit(np.ones(4, np.int32))
        with pytest.raises(RuntimeError, match="injected"):
            bad.result(timeout=60.0)
        good = q.submit(np.ones(4, np.int32))
        assert good.result(timeout=60.0).last_logits.shape == (VOCAB,)
        stop.set()
        worker.join(timeout=30.0)


# ---------------------------------------------------------------------------
# The decode loop's AST rule (mutation-tested); the decode-step HLO contract
# is ``serving_paged`` (tests/test_continuous.py::TestPagedContract)
# ---------------------------------------------------------------------------


class TestServingContract:
    def test_mutation_ast_host_sync_in_decode_flags(self, tmp_path):
        from distributed_pytorch_training_tpu.analysis.ast_rules import (
            run_ast_rules,
        )

        path = tmp_path / "serving" / "continuous.py"
        path.parent.mkdir(parents=True)
        path.write_text(textwrap.dedent("""
            import jax

            def _step_decode_loop(self, steps):
                for _ in range(steps):
                    tok = jax.device_get(self.tok)
                return tok

            def _complete_finished(self, slots):
                return jax.device_get(slots)  # legal: after the loop
        """))
        found = run_ast_rules(files=[path],
                              rules=["no-host-sync-in-decode"])
        assert len(found) == 1 and "_step_decode_loop" in found[0].message

    def test_ast_rule_scopes_to_decode_loop_only(self, tmp_path):
        from distributed_pytorch_training_tpu.analysis.ast_rules import (
            run_ast_rules,
        )

        path = tmp_path / "serving" / "continuous.py"
        path.parent.mkdir(parents=True)
        path.write_text(textwrap.dedent("""
            import jax

            def _complete_finished(self, slots):
                return jax.device_get(slots)
        """))
        assert run_ast_rules(files=[path],
                             rules=["no-host-sync-in-decode"]) == []
        # and the real scheduler passes its own rule
        assert run_ast_rules(rules=["no-host-sync-in-decode"]) == []


# ---------------------------------------------------------------------------
# Telemetry: serving phases in the per-phase split
# ---------------------------------------------------------------------------


class TestServingTelemetry:
    def test_summary_buckets_serving_phases(self):
        from distributed_pytorch_training_tpu.telemetry.__main__ import (
            summarize,
        )

        events = [{"kind": "meta", "name": "stream", "schema": 1,
                   "run_id": "r"}]
        for name, ms in (("queue_wait", 5.0), ("prefill", 20.0),
                         ("slot_wait", 60.0), ("drain", 2.0)):
            events.append({"kind": "span", "name": name, "t0": 0.0,
                           "dur_ms": ms})
        s = summarize(events)
        assert set(s["step_split_pct"]) == {"queue_wait", "prefill",
                                            "slot_wait", "drain"}
        assert abs(sum(s["step_split_pct"].values()) - 100.0) < 0.1

    def test_engine_emits_serving_spans(self, engine, tmp_path):
        from distributed_pytorch_training_tpu import telemetry
        from distributed_pytorch_training_tpu.telemetry.__main__ import (
            read_stream,
        )

        stream = tmp_path / "t.jsonl"
        telemetry.configure(str(stream))
        try:
            q = RequestQueue(engine.config.buckets)
            q.submit(np.ones(4, np.int32))
            drain(engine, q)
        finally:
            telemetry.reset()
        events, bad = read_stream(str(stream))
        assert bad == 0
        names = {e["name"] for e in events if e.get("kind") == "span"}
        assert {"queue_wait", "prefill", "drain"} <= names
        assert "decode" not in names    # the forward engine generates none


# ---------------------------------------------------------------------------
# The bench row (fixed offered load) and `serving smoke`, in process
# ---------------------------------------------------------------------------

# the row `serving bench --json` prints; `chip_smoke.py` phase 3 reads it
BENCH_ROW_KEYS = {
    "achieved_rps", "backend", "buckets", "checkpoint", "compiles",
    "completed", "contracts", "dense_kv_bytes", "draft", "granularity",
    "kv_bytes_ratio", "kv_dtype", "max_new_tokens", "mean_ms", "mixed_want",
    "mode", "model", "n_requests", "offered_rps", "p50_ms", "p99_ms",
    "page_size", "paged_kv_bytes", "per_replica", "prefill_skips",
    "prefix_skip", "recompiles_after_warmup", "replica_deaths", "replicas",
    "rows", "shared_frac", "tail_resumes", "tokens", "tokens_per_sec",
    "ttft_p50_ms", "ttft_p99_ms"}

BENCH_N, BENCH_NEW, BENCH_BUCKETS = 12, 4, (8, 16)


def bench_wants(seed=0):
    """The per-request wants `mixed_want` draws: the load test's own rng
    stream (lengths, prompts, then wants)."""
    rng = np.random.RandomState(seed)
    lens = [int(rng.randint(1, max(BENCH_BUCKETS) + 1))
            for _ in range(BENCH_N)]
    for n in lens:
        rng.randint(0, VOCAB, n)
    return [int(rng.randint(1, BENCH_NEW + 1)) for _ in range(BENCH_N)]


def holds_schema(row):
    assert set(row) == BENCH_ROW_KEYS
    assert row["mode"] == "serving_continuous"
    assert row["p50_ms"] > 0 and row["p99_ms"] >= row["p50_ms"]
    assert row["achieved_rps"] > 0 and row["tokens_per_sec"] > 0
    assert row["tokens"] == BENCH_N * BENCH_NEW
    assert row["contracts"]["pass"] is True, row["contracts"]
    assert row["checkpoint"] is None  # random-init smoke, says so


def credits_the_wanted_tokens(row):
    wants = bench_wants()
    assert len(set(wants)) > 1
    assert row["tokens"] == sum(wants) < BENCH_N * BENCH_NEW


def skips_resident_prefills(row):
    assert row["prefill_skips"] > 0
    assert "ttft_warm_p50_ms" in row and "ttft_cold_p50_ms" in row


def cuts_the_pool_by_three(row):
    assert row["kv_bytes_ratio"] >= 3
    assert row["kv_codec"] == "xla"     # a mesh of eight: no Mosaic codec


class TestMeasureServing:
    @pytest.mark.parametrize("kw,holds", [
        (dict(), holds_schema),
        (dict(mixed_want=True), credits_the_wanted_tokens),
        (dict(shared_frac=0.5), skips_resident_prefills),
        (dict(kv_dtype="int8"), cuts_the_pool_by_three),
    ], ids=["schema", "mixed_want", "shared_frac", "int8_pages"])
    def test_bench_row(self, mesh8, devices, kw, holds):
        from distributed_pytorch_training_tpu.serving.loadtest import (
            measure_serving_continuous,
        )

        row = measure_serving_continuous(
            model_name="gpt2_124m", n_requests=BENCH_N, offered_rps=200.0,
            buckets=BENCH_BUCKETS, rows=8, max_new_tokens=BENCH_NEW,
            page_size=4, devices=devices,
            model_overrides=dict(max_position=32, **TINY), **kw)
        assert row["completed"] == row["n_requests"] == BENCH_N
        assert row["recompiles_after_warmup"] == 0
        holds(row)

    @pytest.mark.parametrize("model_name", ["resnet18", "bert_base"])
    def test_bench_refuses_a_model_without_a_cache_upfront(
            self, devices, model_name, monkeypatch):
        """Before any engine is built: the builders are never reached."""
        from distributed_pytorch_training_tpu.serving import loadtest

        def unreachable(*a, **kw):
            raise AssertionError("an engine was built")

        monkeypatch.setattr(loadtest, "build_slot_engine", unreachable)
        with pytest.raises(ValueError, match="has no cache"):
            loadtest.measure_serving_continuous(
                model_name=model_name, n_requests=1, devices=devices)


def smoke_span_names(tmp_path, model_name, more_overrides=""):
    from distributed_pytorch_training_tpu.serving.__main__ import main
    from distributed_pytorch_training_tpu.telemetry.__main__ import (
        read_stream,
    )

    assert main(["smoke", "--model", model_name, "--model-overrides",
                 "hidden_dim=32,depth=2,num_heads=2,vocab_size=97"
                 + more_overrides,
                 "--buckets", "8,16", "--rows", "8", "--max-new-tokens", "2",
                 "--prompt-len", "6", "--output-dir", str(tmp_path)]) == 0
    events, bad = read_stream(str(tmp_path / "telemetry_rank0.jsonl"))
    assert bad == 0
    return [e["name"] for e in events if e.get("kind") == "span"]


class TestSmokeInProcess:
    def test_a_causal_lm_goes_through_the_scheduler(self, mesh8, tmp_path):
        names = smoke_span_names(tmp_path, "gpt2_124m")
        # three prompts over two rungs, each admitted by a prefill, every
        # iteration fenced: the token server's stream, not a batch loop's
        assert names.count("prefill") == 3
        assert "sched_fence" in names and "slot_wait" in names
        assert "decode" not in names

    def test_a_model_without_a_cache_goes_through_the_forward_engine(
            self, mesh8, tmp_path):
        names = smoke_span_names(tmp_path, "bert_base", ",mlp_dim=64")
        assert {"queue_wait", "prefill", "drain"} <= set(names)
        assert not any(n.startswith("sched_") for n in names)


class TestImageServing:
    def test_serve_images_and_normalization_cache_key(self, mesh8):
        """resnet classification serves through the engine, and the
        compiled-program cache keys on the normalization constants — a
        second call with different mean/std must NOT reuse the first
        call's baked-in values."""
        from distributed_pytorch_training_tpu.models import get_model

        model = get_model("resnet18", num_classes=4)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 8, 8, 3), np.float32),
                               train=False)
        eng = InferenceEngine(
            model, mesh8, ServeConfig(buckets=(8,), rows=8),
            variables["params"], batch_stats=variables.get("batch_stats"))
        rng = np.random.RandomState(0)
        imgs = rng.randint(0, 256, (3, 8, 8, 3)).astype(np.uint8)
        mean, std = (0.5, 0.5, 0.5), (0.25, 0.25, 0.25)
        a = eng.serve_images(imgs, mean=mean, std=std)
        assert a.shape == (3, 4) and np.isfinite(a).all()
        compiles = eng.compiles
        # same stats: cached executable, no recompile
        np.testing.assert_array_equal(
            eng.serve_images(imgs, mean=mean, std=std), a)
        assert eng.compiles == compiles
        # different stats: MUST recompile and produce different logits
        b = eng.serve_images(imgs, mean=(0.1, 0.1, 0.1), std=(1.0, 1.0, 1.0))
        assert eng.compiles == compiles + 1
        assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# CLI e2e (slow): checkpoint -> serving smoke subprocess
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestServingCLI:
    def test_smoke_serves_checkpoint_end_to_end(self, mesh8, tmp_path):
        from distributed_pytorch_training_tpu.training import (
            TrainConfig, Trainer,
        )
        from distributed_pytorch_training_tpu.training.checkpoint import (
            CheckpointManager,
        )
        from distributed_pytorch_training_tpu.training.optim import (
            make_optimizer, make_schedule,
        )
        from distributed_pytorch_training_tpu.training.tasks import (
            LanguageModelingTask,
        )

        model = tiny_model(vocab_size=50257, max_position=64)
        trainer = Trainer(LanguageModelingTask(), mesh8,
                          TrainConfig(seed=0))
        # the chain train.py builds (make_optimizer + callable schedule,
        # no clip) — the serving CLI's auto template must match it
        tx = make_optimizer("adamw", make_schedule("constant", 1e-4))
        state = trainer.init_state(model, np.zeros((1, 8), np.int32),
                                   tx, jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
        mgr.save(1, state, epoch=1)
        mgr.close()

        import os

        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        out = subprocess.run(
            [sys.executable, "-m",
             "distributed_pytorch_training_tpu.serving", "smoke",
             "--model", "gpt2_124m",
             "--model-overrides",
             "hidden_dim=32,depth=2,num_heads=2,max_position=64",
             "--ckpt-dir", str(tmp_path / "ckpt"),
             "--buckets", "8,16", "--rows", "8", "--max-new-tokens", "2",
             "--output-dir", str(tmp_path / "out")],
            env=env, cwd=str(Path(__file__).resolve().parent.parent),
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        text = out.stdout + out.stderr
        assert "tree_digest" in text and "serving smoke: ok" in text
        # the telemetry stream landed with serving spans
        stream = tmp_path / "out" / "telemetry_rank0.jsonl"
        assert stream.exists()
        names = {json.loads(l).get("name")
                 for l in stream.read_text().splitlines() if l.strip()}
        assert {"queue_wait", "prefill", "sched_fence"} <= names

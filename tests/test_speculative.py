"""serving/speculative.py + prefix-resident admission (ISSUE 19).

Pins, in order:
* SpeculativeEngine validation: int8 pools refused, spec_k >= 1, the
  draft's vocab and position table must fit, and the scheduler refuses
  plain SlotEngines;
* the tentpole exactness pin: the speculative stream is BITWISE the
  non-speculative SlotEngine's (and the solo full-context greedy
  forward's) across accept/reject mixes, mixed temperatures, per-request
  seeds, and slot churn — with zero recompiles after warmup;
* a same-weights "oracle" draft accepts nearly everything and finishes
  in far fewer verify rounds than emitted tokens (the perf mechanism,
  pinned structurally rather than by wall clock);
* prefix-resident admission: a fully-resident prompt admits with ZERO
  prefill dispatch (span census: `prefill_skip`, no `prefill`), partial
  residency prefills only the tail — both bitwise vs the cold path, on
  the plain AND the speculative engine; the fp32-only / opt-out gates;
* draft-pool pressure: admission throttles when the draft pool cannot
  hold a request (target lease rolled back, request stays pending) and
  every request still completes bitwise with nothing leaked;
* the ``serving_spec`` contract + `spec-verify-donated` rule,
  mutation-tested per the checker's own standard (the n_emit side
  output must cost the alias table nothing);
* router mid-POST death: a replica dying mid-response (truncated body or
  chunk-boundary IncompleteRead) surfaces as ReplicaDead immediately and
  the seed-pinned resubmit emits on a survivor — clean under
  DPT_LOCKCHECK=1.
"""

import dataclasses as dc
import http.client
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu import telemetry
from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.serving.batching import RequestQueue
from distributed_pytorch_training_tpu.serving.continuous import (
    ContinuousScheduler, SlotEngine,
)
from distributed_pytorch_training_tpu.serving.paged import (
    PagedServeConfig, PagePool,
)
from distributed_pytorch_training_tpu.serving.router import (
    HttpReplica, InProcessReplica, ReplicaDead, Router,
)
from distributed_pytorch_training_tpu.serving.speculative import (
    SpeculativeEngine, SpeculativeScheduler,
)
from distributed_pytorch_training_tpu.utils import locktrace
from test_continuous import oracle_sample_tokens, probed_sampler

VOCAB = 97
SPEC_K = 3


def tiny_model(**kw):
    cfg = dict(vocab_size=VOCAB, hidden_dim=32, depth=2, num_heads=2,
               max_position=64)
    cfg.update(kw)
    return GPT2LMHead(**cfg)


def paged_cfg(**kw):
    cfg = dict(buckets=(8, 16), rows=8, max_new_tokens=6, page_size=4)
    cfg.update(kw)
    return PagedServeConfig(**cfg)


@pytest.fixture(scope="module")
def tiny(mesh8):
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    return model, params


@pytest.fixture(scope="module")
def draft_tiny():
    """A structurally SMALLER draft (1 block, hidden 16) with its own
    random init: its greedy proposals agree with the target's sampled
    stream only sometimes, which is exactly the mixed accept/reject
    regime the bitwise pin must survive."""
    model = tiny_model(hidden_dim=16, depth=1, num_heads=2)
    params = model.init(jax.random.PRNGKey(7), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    return model, params


@pytest.fixture(scope="module")
def spec_engine(mesh8, tiny, draft_tiny):
    model, params = tiny
    dmodel, dparams = draft_tiny
    eng = SpeculativeEngine(model, mesh8, paged_cfg(), params, dmodel,
                            dparams, spec_k=SPEC_K)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def plain_engine(mesh8, tiny):
    model, params = tiny
    eng = SlotEngine(model, mesh8, paged_cfg(), params)
    eng.warmup()
    return eng


def prompts(ns, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in ns]


_REF_PAD = 32          # >= longest prompt (16) + max_new_tokens (6)
_ref_fwd_cache: dict = {}


def ref_greedy(model, params, prompt, n):
    """The solo reference (test_continuous.py's bitwise anchor): greedy
    continuation off one fixed-pad jitted full-context forward."""
    fwd = _ref_fwd_cache.get(id(model))
    if fwd is None:
        fwd = jax.jit(lambda p, ids: model.apply({"params": p}, ids,
                                                 train=False))
        _ref_fwd_cache[id(model)] = fwd
    ids = np.zeros((1, _REF_PAD), np.int32)
    ids[0, :len(prompt)] = prompt
    cur = len(prompt)
    out = []
    for _ in range(n):
        logits = fwd(params, jnp.asarray(ids))
        nxt = int(jnp.argmax(logits[0, cur - 1]))
        out.append(nxt)
        ids[0, cur] = nxt
        cur += 1
    return np.asarray(out, np.int32)


def serve_all(engine, specs, scheduler_cls=None, timeout=300.0):
    """Reset the engine, push every spec through a fresh scheduler,
    drain, and return (scheduler, per-request Results in order)."""
    if scheduler_cls is None:
        scheduler_cls = (SpeculativeScheduler
                         if isinstance(engine, SpeculativeEngine)
                         else ContinuousScheduler)
    engine.reset_state()
    q = RequestQueue(engine.config.buckets)
    sched = scheduler_cls(engine, q)
    reqs = [q.submit(toks, **kw) for toks, kw in specs]
    sched.drain()
    return sched, [r.result(timeout=timeout) for r in reqs]


# ---------------------------------------------------------------------------
# Constructor validation: the exactness gates
# ---------------------------------------------------------------------------


class TestSpecValidation:
    def test_int8_pool_refused(self, mesh8, tiny, draft_tiny):
        model, params = tiny
        dmodel, dparams = draft_tiny
        with pytest.raises(ValueError, match="fp32"):
            SpeculativeEngine(model, mesh8, paged_cfg(kv_dtype="int8"),
                              params, dmodel, dparams, spec_k=SPEC_K)

    def test_spec_k_floor(self, mesh8, tiny, draft_tiny):
        model, params = tiny
        dmodel, dparams = draft_tiny
        with pytest.raises(ValueError, match="spec_k"):
            SpeculativeEngine(model, mesh8, paged_cfg(), params, dmodel,
                              dparams, spec_k=0)

    def test_vocab_mismatch_refused(self, mesh8, tiny):
        model, params = tiny
        dmodel = tiny_model(vocab_size=31, hidden_dim=16, depth=1)
        dparams = dmodel.init(jax.random.PRNGKey(1),
                              np.zeros((1, 8), np.int32),
                              train=False)["params"]
        with pytest.raises(ValueError, match="vocab"):
            SpeculativeEngine(model, mesh8, paged_cfg(), params, dmodel,
                              dparams, spec_k=SPEC_K)

    def test_scheduler_refuses_plain_engine(self, plain_engine):
        q = RequestQueue(plain_engine.config.buckets)
        with pytest.raises(ValueError, match="SpeculativeEngine"):
            SpeculativeScheduler(plain_engine, q)


# ---------------------------------------------------------------------------
# The tentpole pin: bitwise parity vs the non-speculative path
# ---------------------------------------------------------------------------


class TestSpecBitwiseParity:
    def test_greedy_matches_solo_forward_bitwise(self, spec_engine, tiny):
        model, params = tiny
        seqs = prompts((3, 8, 11, 16, 5, 13), seed=1)
        _, res = serve_all(spec_engine,
                           [(s, dict(temperature=0.0)) for s in seqs])
        for i, (s, r) in enumerate(zip(seqs, res)):
            np.testing.assert_array_equal(
                r.tokens, ref_greedy(model, params, s, 6),
                err_msg=f"request {i} (len {len(s)})")

    def test_mixed_temps_and_churn_match_plain_engine(self, spec_engine,
                                                      plain_engine):
        """12 requests over 8 rows (churn), mixed temperatures / top_p /
        per-request seeds and wants: every stream bitwise identical to
        the plain SlotEngine's under the plain scheduler. Acceptance is
        exact match, so the draft's numerics cannot leak into the output
        — this is the PARITY.md clause as an assertion."""
        rng = np.random.RandomState(3)
        seqs = prompts([int(rng.randint(1, 17)) for _ in range(12)],
                       seed=4)
        kws = [dict(temperature=float(rng.choice([0.0, 0.7, 1.0])),
                    top_p=float(rng.choice([0.9, 1.0])),
                    seed=int(100 + i),
                    max_new_tokens=int(rng.randint(1, 7)))
               for i in range(12)]
        specs = list(zip(seqs, kws))
        sched, spec_res = serve_all(spec_engine, specs)
        _, plain_res = serve_all(plain_engine, specs)
        assert sched.spec_rounds > 0 and sched.spec_proposed > 0
        for i, (a, b) in enumerate(zip(spec_res, plain_res)):
            np.testing.assert_array_equal(
                a.tokens, b.tokens,
                err_msg=f"request {i}: speculative stream diverged "
                        f"(kw {kws[i]})")

    def test_mixed_streams_are_the_unconditional_samplers(
            self, spec_engine, mesh8, tiny, monkeypatch):
        """The verify step samples rows x window through the sampler that
        branches; a plain engine traced around the oracle (every row
        through the nucleus, as before ISSUE 31) emits the same streams,
        greedy and sampling requests alike."""
        from distributed_pytorch_training_tpu.serving import continuous

        model, params = tiny
        monkeypatch.setattr(continuous, "sample_tokens",
                            oracle_sample_tokens)
        oracle = SlotEngine(model, mesh8, paged_cfg(), params)
        rng = np.random.RandomState(8)
        seqs = prompts([int(rng.randint(1, 17)) for _ in range(10)],
                       seed=9)
        specs = [(s, dict(temperature=float(rng.choice([0.0, 0.7, 1.0])),
                          top_p=float(rng.choice([0.9, 1.0])),
                          seed=200 + i,
                          max_new_tokens=int(rng.randint(2, 7))))
                 for i, s in enumerate(seqs)]
        assert {kw["temperature"] > 0 for _, kw in specs} == {True, False}
        _, want = serve_all(oracle, specs)
        _, got = serve_all(spec_engine, specs)
        for i, (a, b) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(
                a.tokens, b.tokens, err_msg=f"request {i} ({specs[i][1]})")

    def test_verify_holds_the_sampler_once(self, spec_engine):
        """One verify program with the branch inside it, as the plain
        decode step's: no second executable for all-greedy rounds."""
        text = spec_engine.lower_spec_verify().as_text()
        assert text.count("stablehlo.case") == 1

    def test_a_finished_sampling_slot_does_not_choose_the_verify_branch(
            self, devices, tiny, draft_tiny, monkeypatch):
        """The verify step hands the sampler live rows' temperatures
        only: once the sampling request is done, its idle slot (which
        keeps its temperature) no longer sends the greedy request's
        rounds through the nucleus branch."""
        from distributed_pytorch_training_tpu.parallel import (
            MeshSpec, build_mesh,
        )
        from distributed_pytorch_training_tpu.serving import speculative

        model, params = tiny
        dmodel, dparams = draft_tiny
        mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
        seen = []
        monkeypatch.setattr(speculative, "sample_tokens",
                            probed_sampler(seen))
        eng = SpeculativeEngine(model, mesh1,
                                paged_cfg(rows=4, buckets=(8,)), params,
                                dmodel, dparams, spec_k=SPEC_K)
        short, long = prompts((5, 7), seed=25)
        _, res = serve_all(eng, [
            (short, dict(temperature=0.9, seed=5, max_new_tokens=2)),
            (long, dict(temperature=0.0, max_new_tokens=6))])
        jax.effects_barrier()
        assert [len(r.tokens) for r in res] == [2, 6]
        np.testing.assert_array_equal(
            res[1].tokens, ref_greedy(model, params, long, 6))
        assert float(jnp.max(eng._control["temps"])) > 0.0
        rounds = [hot for n, hot in seen if n == 4 * (SPEC_K + 1)]
        # the first round ends the short request (one token left of two)
        assert rounds[0] is True and len(rounds) >= 2
        assert not any(rounds[1:])

    @pytest.mark.parametrize("temperatures", [(0.0,), (0.0, 0.8, 1.0)],
                             ids=["greedy", "mixed"])
    def test_zero_recompiles_after_warmup(self, spec_engine, temperatures):
        rng = np.random.RandomState(5)
        before = spec_engine.compiles
        specs = [(rng.randint(0, VOCAB, int(rng.randint(1, 17)))
                  .astype(np.int32),
                  dict(temperature=float(rng.choice(temperatures)),
                       seed=int(rng.randint(1, 1000)),
                       max_new_tokens=int(rng.randint(1, 7))))
                 for _ in range(20)]
        sched, res = serve_all(spec_engine, specs)
        assert len(res) == 20 and all(r.tokens.size for r in res)
        assert spec_engine.compiles == before, \
            "a draft/verify round recompiled after warmup"

    # slow tier: the oracle leg builds (and warms up) a THIRD engine just
    # to prove the acceptance machinery can accept — a quality
    # diagnostic, not a correctness pin; the bitwise-parity tests above
    # are the tier-1 story and hold at ANY accept ratio
    @pytest.mark.slow
    def test_oracle_draft_accepts_and_cuts_rounds(self, mesh8, tiny):
        """Draft == target: greedy proposals are the target's own argmax
        stream, so (temperature 0) every round accepts the full window.
        Pins the accept accounting AND the perf mechanism structurally:
        emitting `want` tokens takes ~want/(K+1) verify rounds, not
        `want` decode steps."""
        model, params = tiny
        eng = SpeculativeEngine(model, mesh8,
                                paged_cfg(buckets=(16,), rows=2), params,
                                model, params, spec_k=SPEC_K)
        sched, res = serve_all(
            eng, [(p, dict(temperature=0.0))
                  for p in prompts((9, 14), seed=6)])
        for p, r in zip(prompts((9, 14), seed=6), res):
            np.testing.assert_array_equal(
                r.tokens, ref_greedy(model, params, p, 6))
        # 2 requests x 6 tokens over K+1=4-token rounds: far fewer verify
        # rounds than the 12 per-token steps the plain path would fence
        assert sched.spec_rounds <= 6
        assert sched.accept_ratio >= 0.5, (
            f"oracle draft accept ratio {sched.accept_ratio:.3f} — the "
            "draft cache is starving (the K+1th propose write regressed?)")


# ---------------------------------------------------------------------------
# Prefix-resident admission: skip / resume, census + bitwise
# ---------------------------------------------------------------------------


class TestPrefixResidentAdmission:
    def _serve_seq(self, engine, prompt_list):
        """Serve prompts SEQUENTIALLY through one replica worker (each
        result awaited before the next submit) so later prompts see the
        residency earlier ones registered. Returns (scheduler, results,
        telemetry events)."""
        engine.reset_state()
        rec = telemetry.configure()          # ring-only stream
        try:
            replica = InProcessReplica("r0", engine)
            results = [replica.submit(p, temperature=0.0)
                       .result(timeout=120.0) for p in prompt_list]
            replica.stop()
            events = rec.tail(10_000)
        finally:
            telemetry.reset()
        return replica.scheduler, results, events

    @staticmethod
    def _spans(events, name):
        return [e for e in events
                if e["kind"] == "span" and e["name"] == name]

    def test_fully_resident_skips_prefill_bitwise(self, plain_engine,
                                                  tiny):
        """The zero-prefill census: an identical page-aligned prompt,
        served twice — the second admission dispatches NO prefill (span
        census), and both streams are bitwise the solo forward's."""
        model, params = tiny
        (p,) = prompts((16,), seed=8)        # 16 = 4 full pages
        sched, res, events = self._serve_seq(plain_engine, [p, p])
        assert sched.prefill_skips == 1 and sched.tail_resumes == 0
        assert len(self._spans(events, "prefill")) == 1   # the cold one
        assert len(self._spans(events, "prefill_skip")) == 1
        ref = ref_greedy(model, params, p, 6)
        for r in res:
            np.testing.assert_array_equal(r.tokens, ref)

    def test_partial_residency_prefills_tail_only_bitwise(
            self, plain_engine, tiny):
        model, params = tiny
        rng = np.random.RandomState(9)
        base = rng.randint(0, VOCAB, 8).astype(np.int32)   # 2 full pages
        ext = np.concatenate([base,
                              rng.randint(0, VOCAB, 5).astype(np.int32)])
        sched, res, _ = self._serve_seq(plain_engine, [base, ext])
        assert sched.tail_resumes == 1 and sched.prefill_skips == 0
        np.testing.assert_array_equal(res[0].tokens,
                                      ref_greedy(model, params, base, 6))
        np.testing.assert_array_equal(res[1].tokens,
                                      ref_greedy(model, params, ext, 6))

    def test_skip_composes_with_speculation_bitwise(self, spec_engine,
                                                    tiny):
        """Both tentpole halves at once: the second identical prompt
        skip-admits INTO the speculative round loop (last-prompt logits
        captured off verify window row 0) and still emits the bitwise
        stream."""
        model, params = tiny
        (p,) = prompts((16,), seed=10)
        sched, res, events = self._serve_seq(spec_engine, [p, p])
        assert sched.prefill_skips == 1
        assert len(self._spans(events, "prefill")) == 1
        assert sched.spec_rounds > 0
        ref = ref_greedy(model, params, p, 6)
        for r in res:
            np.testing.assert_array_equal(r.tokens, ref)
            # the skip admission's last-prompt logits (captured off
            # verify window row 0 via the last_pos protocol) must agree
            # with the stream: token #0 is their argmax under greedy
            assert int(np.argmax(r.last_logits)) == int(r.tokens[0])

    def test_gates_disable_the_fast_path(self, mesh8, tiny):
        """The exactness gates: int8 pools and prefix_sharing=False turn
        prefix skip OFF (construction only — no compile); an explicit
        prefix_skip=False opts out while shared pages keep deduping."""
        model, params = tiny
        assert SlotEngine(model, mesh8, paged_cfg(kv_dtype="int8"),
                          params).prefix_skip_enabled is False
        assert SlotEngine(model, mesh8, paged_cfg(prefix_sharing=False),
                          params).prefix_skip_enabled is False
        assert SlotEngine(model, mesh8, paged_cfg(prefix_skip=False),
                          params).prefix_skip_enabled is False
        assert SlotEngine(model, mesh8, paged_cfg(),
                          params).prefix_skip_enabled is True

    # slow tier: the opt-out leg builds its own engine just to prove the
    # escape hatch is cosmetic; the gates test above pins the flag
    # plumbing cheaply and the skip-path parity legs are the tier-1 story
    @pytest.mark.slow
    def test_opt_out_still_bitwise_with_full_prefill(self, mesh8, tiny):
        """prefix_skip=False serves the identical prompt twice through
        TWO full prefills (census: zero skips) and the stream is still
        bitwise — the fast path is an optimization, not a semantic."""
        model, params = tiny
        eng = SlotEngine(model, mesh8,
                         paged_cfg(buckets=(16,), rows=2,
                                   prefix_skip=False), params)
        (p,) = prompts((16,), seed=8)
        sched, res, events = self._serve_seq(eng, [p, p])
        assert sched.prefill_skips == 0 and sched.tail_resumes == 0
        assert len(self._spans(events, "prefill")) == 2
        ref = ref_greedy(model, params, p, 6)
        for r in res:
            np.testing.assert_array_equal(r.tokens, ref)


# ---------------------------------------------------------------------------
# Draft-pool pressure: throttle, never deadlock, never leak
# ---------------------------------------------------------------------------


class TestDraftPoolPressure:
    def test_exhausted_draft_pool_throttles_and_completes(self,
                                                          spec_engine,
                                                          tiny,
                                                          monkeypatch):
        """Shrink the draft allocator to two slots' worth: admissions
        past that fail the draft lease, roll the TARGET lease back, and
        park the request pending — every request still completes bitwise
        and the draft pool drains to its starting free count (nothing
        leaked through the rollback path). DPT_LOCKCHECK=1 is armed so
        the traced acquisition order must stay clean."""
        monkeypatch.setenv("DPT_LOCKCHECK", "1")
        locktrace.trace().reset()
        model, params = tiny
        spec_engine.reset_state()
        q = RequestQueue(spec_engine.config.buckets)
        sched = SpeculativeScheduler(spec_engine, q)
        dcfg = spec_engine.draft_config
        sched.draft_pool = PagePool(2 * dcfg.pages_per_slot + 1,
                                    dcfg.page_size, dcfg.pages_per_slot,
                                    prefix_sharing=False)
        free0 = sched.draft_pool.free_pages()
        seqs = prompts((5, 9, 13, 7, 11, 6), seed=21)
        reqs = [q.submit(s, temperature=0.0) for s in seqs]
        sched.drain()
        res = [r.result(timeout=300.0) for r in reqs]
        for i, (s, r) in enumerate(zip(seqs, res)):
            np.testing.assert_array_equal(
                r.tokens, ref_greedy(model, params, s, 6),
                err_msg=f"request {i} (len {len(s)})")
        assert sched.draft_pool.free_pages() == free0
        assert locktrace.cross_check() == []


# ---------------------------------------------------------------------------
# The serving_spec contract + spec-verify-donated rule (mutation-tested)
# ---------------------------------------------------------------------------


class TestSpecContract:
    # the registered-contract evaluator itself (get_contract +
    # evaluate_contract) runs in the full-matrix CLI acceptance test —
    # re-evaluating it here would pay a second engine build + verify
    # compile for no new coverage; this leg pins the census and the
    # rule on the LIVE warmed engine instead
    def test_live_engine_artifacts_pass(self, spec_engine):
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts, spec_serving_artifacts,
        )

        artifacts = spec_serving_artifacts(spec_engine)
        # fp32 pool (2 layer-stacked leaves) + every slot-control leaf:
        # the n_emit side output must not cost an alias entry
        assert artifacts.config["spec_cache_leaves"] == 12
        assert (artifacts.config["spec_cache_leaves"]
                == 2 + len(spec_engine._control))
        assert check_artifacts(artifacts) == []

    def test_mutation_missing_alias_entries_flag(self):
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            StepArtifacts, check_artifacts,
        )

        partial = StepArtifacts(
            name="mut", optimized_text=(
                "HloModule spec, input_output_alias={ {0}: (1, {}, "
                "may-alias) }, entry_computation_layout={()}"),
            config={"serving_spec": True, "donate_state": True,
                    "spec_cache_leaves": 12})
        found = check_artifacts(partial, rules=["spec-verify-donated"])
        assert len(found) == 1 and "1 of the >= 12" in found[0].message
        absent = StepArtifacts(
            name="mut2", optimized_text="HloModule spec",
            config={"serving_spec": True, "donate_state": True,
                    "spec_cache_leaves": 12})
        assert check_artifacts(absent, rules=["spec-verify-donated"])
        # non-spec configs are out of scope — the rule stays silent
        plain = StepArtifacts(name="t", optimized_text="HloModule x",
                              config={"donate_state": True})
        assert check_artifacts(plain, rules=["spec-verify-donated"]) == []

    def test_mutation_dropped_leaf_flags_on_real_lowering(self,
                                                          spec_engine):
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts, spec_serving_artifacts,
        )

        artifacts = spec_serving_artifacts(spec_engine)
        poisoned = dc.replace(
            artifacts, config={**artifacts.config,
                               "spec_cache_leaves":
                               artifacts.config["spec_cache_leaves"]
                               + 100})
        found = check_artifacts(poisoned, rules=["spec-verify-donated"])
        assert len(found) == 1


# ---------------------------------------------------------------------------
# Router mid-POST death: half a response is a death, retries are bitwise
# ---------------------------------------------------------------------------


class _FakeResp:
    """A urlopen context manager serving a scripted body."""

    status = 200

    def __init__(self, chunks, content_length=None, raise_mid=False):
        self._chunks = list(chunks)
        self.headers = ({"Content-Length": str(content_length)}
                        if content_length is not None else {})
        self._raise_mid = raise_mid

    def read(self, n):
        if not self._chunks:
            if self._raise_mid:
                raise http.client.IncompleteRead(b"", 64)
            return b""
        return self._chunks.pop(0)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class _StubPending:
    def __init__(self, replica):
        self.replica = replica

    def result(self, timeout=None):
        from distributed_pytorch_training_tpu.serving.batching import (
            Result,
        )

        return Result(tokens=np.arange(3, dtype=np.int32),
                      last_logits=np.zeros(VOCAB, np.float32))


class _StubReplica:
    def __init__(self, name, depth=0):
        self.name = name
        self.depth = depth
        self.submits = []

    def healthy(self):
        return True

    def queue_depth(self):
        return self.depth

    def submit(self, tokens, **kw):
        self.submits.append(kw)
        return _StubPending(self)


class TestRouterMidPostDeath:
    def test_truncated_body_is_replica_dead(self, monkeypatch):
        """A clean close short of Content-Length is half a response: the
        incremental read promotes it to IncompleteRead -> ReplicaDead,
        NOT a json decode error at the request timeout."""
        import urllib.request as _ur

        replica = HttpReplica("h", port=1)
        monkeypatch.setattr(
            _ur, "urlopen",
            lambda *a, **kw: _FakeResp([b'{"tokens": [1, 2'],
                                       content_length=4096))
        with pytest.raises(ReplicaDead, match="died mid-response"):
            replica.submit(np.ones(3, np.int32)).result(timeout=1.0)
        assert not replica.healthy()

    def test_chunk_boundary_death_is_replica_dead(self, monkeypatch):
        """The socket tears mid-read (http.client raises IncompleteRead
        itself): same verdict, same immediacy."""
        import urllib.request as _ur

        replica = HttpReplica("h", port=1)
        monkeypatch.setattr(
            _ur, "urlopen",
            lambda *a, **kw: _FakeResp([b'{"tok'], content_length=4096,
                                       raise_mid=True))
        with pytest.raises(ReplicaDead, match="died mid-response"):
            replica.submit(np.ones(3, np.int32)).result(timeout=1.0)
        assert not replica.healthy()

    def test_mid_post_death_reroutes_with_pinned_seed(self, monkeypatch):
        """The regression drill: replica dies mid-POST, the router
        resubmits to a survivor WITH THE ROUTE-TIME SEED (the retry
        emits the identical stream — sampling is a function of (request,
        seed) alone). Runs under DPT_LOCKCHECK=1: the traced lock order
        across router + queue locks must stay clean."""
        import urllib.request as _ur

        monkeypatch.setenv("DPT_LOCKCHECK", "1")
        locktrace.trace().reset()
        dying = HttpReplica("h", port=1)
        survivor = _StubReplica("s", depth=1)   # depth: h wins dispatch
        monkeypatch.setattr(
            _ur, "urlopen",
            lambda *a, **kw: _FakeResp([b'{"tokens": [9'],
                                       content_length=4096))
        router = Router([dying, survivor])
        req = router.submit(np.ones(4, np.int32))
        assert req.replica_name == "h"
        seed = req.kw["seed"]
        res = req.result(timeout=5.0)
        assert req.replica_deaths == 1 and req.replica_name == "s"
        assert survivor.submits[-1]["seed"] == seed
        np.testing.assert_array_equal(res.tokens,
                                      np.arange(3, dtype=np.int32))
        assert locktrace.cross_check() == []


# ---------------------------------------------------------------------------
# The CLI bench arm with --draft + --shared-frac (slow: subprocess e2e)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cli_bench_draft_and_shared_frac(tmp_path):
    """`serving bench --draft ... --shared-frac 0.5` runs
    the speculative + prefix-skip row end to end, reports accept_ratio
    and the warm/cold TTFT split, and exits 0 iff
    recompiles_after_warmup == 0 (the same hard gate as the plain arm)."""
    import json
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m",
         "distributed_pytorch_training_tpu.serving", "bench",
         "--json",
         "--model", "gpt2_124m",
         "--model-overrides",
         "vocab_size=64,hidden_dim=32,depth=2,num_heads=2",
         "--draft", "gpt2_124m", "--draft-k", "3",
         "--shared-frac", "0.5",
         "--buckets", "8,16", "--rows", "4", "--max-new-tokens", "4",
         "--requests", "10", "--offered-load", "32",
         "--output-dir", str(tmp_path / "out")],
        env=env, cwd=str(Path(__file__).resolve().parent.parent),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["draft"] == "gpt2_124m" and row["spec_rounds"] > 0
    assert "accept_ratio" in row and "accepted_per_verify" in row
    assert row["prefill_skips"] >= 1
    assert "ttft_warm_p50_ms" in row and "ttft_cold_p50_ms" in row
    assert row["recompiles_after_warmup"] == 0

"""serving/ — token-granular continuous batching + paged int8 KV +
multi-replica router (ISSUE 17).

Pins, in order:
* `PagePool` allocator semantics: refcounts, prefix sharing, LRU
  eviction of retained prefix pages, admission-control failure (None,
  nothing leaked);
* SlotEngine greedy decode is BITWISE the solo full-context forward for
  mixed-length requests, with joins/leaves at token granularity
  (per-request ``max_new_tokens`` completing mid-batch);
* zero recompiles after warmup across >= 20 mixed-length admissions;
* sampling determinism: the emitted stream is a function of (request,
  seed) alone — slot assignment, join order, and batch company are
  invisible; ``temperature=0`` is bitwise greedy;
* the int8 paged pool cuts KV bytes >= 3x vs the dense fp32 baseline and
  quantizes deterministically (same request -> same tokens, twice);
* `slot_wait` / `router_dispatch` spans + the slot-occupancy / page-pool
  gauges are registered span names, emitted live, and bucketed by
  `telemetry summary` into the step-time split (not "unaccounted");
* the ``serving_paged`` contract + `paged-pool-donated` rule,
  mutation-tested per the checker's own standard;
* the fleet acceptance drill: 20+ mixed-length requests over 2
  router-fronted replicas on DISJOINT device slices, one replica killed
  with work in flight — every request completes (seed-pinned resubmit),
  zero recompiles on either engine, outputs bitwise the solo forwards;
* scheduler kill fails queued-but-unpulled requests too (no orphaned
  waiters), and the router unit semantics (least-depth, resubmit).
"""

import collections
import socket
import subprocess
import sys
import threading
import time
import urllib.error
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu import telemetry
from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
from distributed_pytorch_training_tpu.serving import batching
from distributed_pytorch_training_tpu.serving.batching import RequestQueue
from distributed_pytorch_training_tpu.serving.continuous import (
    ContinuousScheduler, SlotEngine, sample_tokens,
)
from distributed_pytorch_training_tpu.serving.paged import (
    PagedServeConfig, PagePool,
)
from distributed_pytorch_training_tpu.serving.router import (
    HttpReplica, InProcessReplica, ReplicaDead, Router, RouterRequest,
)

VOCAB = 97


def tiny_model(**kw):
    cfg = dict(vocab_size=VOCAB, hidden_dim=32, depth=2, num_heads=2,
               max_position=64)
    cfg.update(kw)
    return GPT2LMHead(**cfg)


@pytest.fixture(scope="module")
def tiny(mesh8):
    model = tiny_model()
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    return model, params


def paged_cfg(**kw):
    cfg = dict(buckets=(8, 16), rows=8, max_new_tokens=6, page_size=4)
    cfg.update(kw)
    return PagedServeConfig(**cfg)


@pytest.fixture(scope="module")
def slot_engine(mesh8, tiny):
    model, params = tiny
    eng = SlotEngine(model, mesh8, paged_cfg(), params)
    eng.warmup()
    return eng


def test_auto_page_codec_is_the_xla_one_on_a_multi_device_mesh(mesh8, tiny,
                                                               devices):
    """The engine's programs are GSPMD programs and GSPMD cannot partition
    a Mosaic kernel, so on a mesh of more than one device the int8 page
    codec's "auto" resolves to the XLA-composed one (same grid, same page
    bytes); a one-device mesh keeps the backend gate's choice, and an
    explicit setting is never overridden."""
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh

    model, params = tiny
    mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
    int8 = dict(kv_dtype="int8")
    assert SlotEngine(model, mesh8, paged_cfg(**int8),
                      params)._fused_quantize is False
    assert SlotEngine(model, mesh1, paged_cfg(**int8),
                      params)._fused_quantize is None
    assert SlotEngine(model, mesh8, paged_cfg(fused_quantize=True, **int8),
                      params)._fused_quantize is True


def prompts(ns, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in ns]


_REF_PAD = 32          # >= longest prompt (16) + max_new_tokens (6)
_ref_fwd_cache: dict = {}


def ref_greedy(model, params, prompt, n):
    """The solo reference: greedy continuation off the full-context eval
    forward (test_serving.py's bitwise anchor, extended to a token loop).
    The forward is jitted at ONE fixed padded length so every reference
    decode in the file shares a single compile — the model is causal, so
    trailing pad cannot reach position cur-1, and the emitted argmax
    stream is identical to the per-length eager forward's (the float
    logits differ only by ~1e-7 fusion-order noise, which the pin — the
    TOKEN stream — does not see)."""
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


def serve_all(engine, specs, timeout=300.0):
    """Reset the engine, push every spec through a fresh scheduler, drain,
    and return the per-request Results in submission order. ``specs`` are
    (tokens, kw) pairs for RequestQueue.submit."""
    engine.reset_state()
    q = RequestQueue(engine.config.buckets)
    sched = ContinuousScheduler(engine, q)
    reqs = [q.submit(toks, **kw) for toks, kw in specs]
    sched.drain()
    return [r.result(timeout=timeout) for r in reqs]


# ---------------------------------------------------------------------------
# PagePool: the host-side allocator
# ---------------------------------------------------------------------------


class TestPagePool:
    def test_scratch_page_never_leased(self):
        pool = PagePool(9, 4, 4, prefix_sharing=False)
        lease = pool.alloc(list(range(6)), 8)
        assert lease is not None and lease.n_pages == 2
        assert 0 not in lease.pages[:lease.n_pages]
        # unused table entries point at scratch page 0
        assert all(p == 0 for p in lease.pages[lease.n_pages:])

    def test_release_returns_pages(self):
        pool = PagePool(9, 4, 4, prefix_sharing=False)
        free0 = pool.free_pages()
        lease = pool.alloc(list(range(6)), 8)
        assert pool.free_pages() == free0 - 2
        pool.release(lease)
        assert pool.free_pages() == free0

    def test_prefix_sharing_maps_same_pages(self):
        pool = PagePool(17, 4, 4)
        toks = list(range(11))          # pages 0..1 fully covered
        a = pool.alloc(toks, 13)
        b = pool.alloc(toks, 13)
        assert a is not None and b is not None
        # the fully-covered prompt pages are the SAME physical pages
        np.testing.assert_array_equal(a.pages[:2], b.pages[:2])
        # the partial tail page is private to each lease
        assert a.pages[2] != b.pages[2]
        assert b.shared == list(a.pages[:2]) and pool.prefix_hits == 2

    def test_divergent_prompts_do_not_share(self):
        pool = PagePool(17, 4, 4)
        a = pool.alloc(list(range(8)), 8)
        b = pool.alloc(list(range(1, 9)), 8)
        assert set(map(int, a.pages[:2])).isdisjoint(
            set(map(int, b.pages[:2])))

    def test_lru_eviction_of_retained_prefix(self):
        # 4 physical pages (1 scratch + 3): a released prefix page parks
        # retained; exhausting the free list evicts it (oldest first)
        pool = PagePool(4, 4, 3)
        a = pool.alloc(list(range(4)), 4)      # 1 fully-covered page
        pool.release(a)
        assert pool.stats()["retained"] == 1
        b = pool.alloc(list(range(100, 112)), 12)   # needs all 3 pages
        assert b is not None and pool.evictions == 1
        assert pool.stats()["retained"] == 0

    def test_alloc_failure_leaks_nothing(self):
        pool = PagePool(4, 4, 8, prefix_sharing=False)
        free0 = pool.free_pages()
        assert pool.alloc(list(range(4)), 17) is None   # needs 5 > 3 pages
        assert pool.free_pages() == free0

    def test_dry_free_list_never_duplicates_matched_prefix(self):
        # free list dry + the matched prefix page parked retained at
        # refcount 0: alloc must claim the match at match time, not
        # evict it in the fresh-page loop and re-lease it — one physical
        # page at two logical offsets would let the prefill scatter
        # corrupt the shared prefix
        pool = PagePool(3, 4, 2)               # scratch + pages {1, 2}
        a = pool.alloc(list(range(4)), 4)      # 1 fully-covered page
        pool.release(a)                        # -> retained, refcount 0
        b = pool.alloc(list(range(100, 104)), 4)   # drains the free list
        assert b is not None
        stats0 = pool.stats()
        # shared hit on the retained page + 1 fresh page nothing can
        # supply: admission control (None), NOT a duplicated lease
        c = pool.alloc(list(range(4)), 8)
        assert c is None
        assert pool.stats() == stats0          # rollback re-parked it
        pool.release(b)                        # room opens up
        d = pool.alloc(list(range(4)), 8)
        assert d is not None
        pages = list(map(int, d.pages[:d.n_pages]))
        assert len(set(pages)) == len(pages)   # all distinct
        assert d.shared and 0 not in pages

    def test_config_validation_and_floor(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            paged_cfg(kv_dtype="fp8")
        with pytest.raises(ValueError, match="page_size"):
            paged_cfg(page_size=0)
        cfg = paged_cfg()
        assert cfg.cache_len == 16 + 6
        assert cfg.pages_per_slot == 6           # ceil(22 / 4)
        assert cfg.total_pages == 8 * 6 + 1      # fail-safe floor + scratch


# ---------------------------------------------------------------------------
# SlotEngine: greedy bitwise parity + the zero-recompile census
# ---------------------------------------------------------------------------


class TestSlotEngineGreedy:
    def test_mixed_lengths_match_solo_forward_bitwise(self, slot_engine,
                                                      tiny):
        model, params = tiny
        seqs = prompts((3, 8, 11, 16, 5, 13), seed=1)
        res = serve_all(slot_engine,
                        [(s, dict(temperature=0.0)) for s in seqs])
        for i, (s, r) in enumerate(zip(seqs, res)):
            np.testing.assert_array_equal(
                r.tokens, ref_greedy(model, params, s, 6),
                err_msg=f"request {i} (len {len(s)})")

    def test_token_granular_join_leave(self, slot_engine, tiny):
        """Per-request budgets: rows leave the RUNNING batch the moment
        their own want is met (batch-mates keep decoding), and each
        stream is still the bitwise solo greedy prefix."""
        model, params = tiny
        seqs = prompts((4, 9, 6, 12, 7), seed=2)
        wants = [1, 6, 3, 5, 2]
        res = serve_all(slot_engine,
                        [(s, dict(temperature=0.0, max_new_tokens=w))
                         for s, w in zip(seqs, wants)])
        for s, w, r in zip(seqs, wants, res):
            assert r.tokens.shape == (w,)
            np.testing.assert_array_equal(
                r.tokens, ref_greedy(model, params, s, w))

    @pytest.mark.parametrize("temperatures", [(0.0,), (0.0, 0.8, 1.0)],
                             ids=["greedy", "mixed"])
    def test_zero_recompiles_after_warmup(self, slot_engine, temperatures):
        """Greedy or mixed, the census stays where `warmup` left it: both
        branches of the sampler are inside the warmed programs."""
        rng = np.random.RandomState(5)
        before = slot_engine.compiles
        specs = [(rng.randint(0, VOCAB, int(rng.randint(1, 17)))
                  .astype(np.int32),
                  dict(temperature=float(rng.choice(temperatures)),
                       seed=int(rng.randint(1, 1000)),
                       max_new_tokens=int(rng.randint(1, 7))))
                 for _ in range(22)]
        res = serve_all(slot_engine, specs)
        assert len(res) == 22 and all(r.tokens.size for r in res)
        assert slot_engine.compiles == before, \
            "an admission or decode step recompiled after warmup"

    def test_last_logits_match_eval_forward(self, slot_engine, tiny):
        """The compiled prefill's last-prompt logits agree with the eval
        forward to fusion-order noise (~1e-7 — the compiled (1, bucket)
        program fuses differently than the solo-shaped forward), and the
        emitted token IS their argmax — the bitwise pin lives on the
        token stream, not the float intermediates."""
        model, params = tiny
        (s,) = prompts((9,), seed=3)
        (r,) = serve_all(slot_engine, [(s, dict(temperature=0.0))])
        solo = np.asarray(
            model.apply({"params": params}, s[None],
                        train=False))[0, len(s) - 1]
        np.testing.assert_allclose(r.last_logits, solo, rtol=1e-5,
                                   atol=1e-6)
        assert int(r.tokens[0]) == int(np.argmax(r.last_logits))
        assert int(r.tokens[0]) == int(np.argmax(solo))


# ---------------------------------------------------------------------------
# Sampling determinism (the RNG-threading satellite)
# ---------------------------------------------------------------------------


@jax.named_scope("sample")
def oracle_sample_tokens(logits, keys, temperatures, top_ps):
    """`sample_tokens` as it stood before it branched (ISSUE 31): every
    row through the nucleus, the argmax kept by the closing where. The
    oracle the branching sampler is held to, token for token."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temps = jnp.maximum(temperatures, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / temps
    order = jnp.argsort(-scaled, axis=-1)           # descending
    sorted_l = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    masked = jnp.where(keep, sorted_l, jnp.finfo(jnp.float32).min)
    choice = jax.vmap(lambda k, row: jax.random.categorical(k, row))(
        keys, masked)
    sampled = jnp.take_along_axis(
        order, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temperatures <= 0.0, greedy, sampled)


def sub_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr nested in its equations' parameters
    (`pjit` bodies, `cond` branches, ...), depth first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from sub_jaxprs(sub)


def primitives(jaxpr):
    return [e.primitive.name for j in sub_jaxprs(jaxpr) for e in j.eqns]


@pytest.fixture(scope="module")
def oracle_engine(mesh8, tiny):
    """A SlotEngine whose programs were traced around the ORACLE sampler:
    what every stream was before the sampler branched."""
    from distributed_pytorch_training_tpu.serving import continuous

    model, params = tiny
    eng = SlotEngine(model, mesh8, paged_cfg(), params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(continuous, "sample_tokens", oracle_sample_tokens)
        eng.warmup()
    return eng


def probed_sampler(seen):
    """`sample_tokens` behind a probe: the predicate of its branch, as the
    device computes it from the temperatures a caller hands over, lands
    in ``seen`` as (rows, any row samples) once per execution."""
    def probed(logits, keys, temperatures, top_ps):
        jax.debug.callback(
            lambda hot, n=temperatures.shape[0]: seen.append((n, bool(hot))),
            jnp.any(temperatures > 0.0))
        return sample_tokens(logits, keys, temperatures, top_ps)

    return probed


def decode_step_counters(events):
    """(`serving_decode_steps`, `serving_decode_steps_all_greedy`) summed
    over ``events`` by telemetry's own summary."""
    from distributed_pytorch_training_tpu.telemetry.__main__ import summarize

    counters = summarize(events)["counters"]
    return (counters.get("serving_decode_steps", 0),
            counters.get("serving_decode_steps_all_greedy", 0))


# (temperatures, top_ps) of the oracle cases: rows of the decode step and
# the rows x window of the speculative verify step (each knob repeated)
_MIXED = ([0.0, 0.8, 0.0, 1.0, 0.0, 0.0, 1.5, 0.0],
          [1.0, 0.9, 0.3, 1.0, 1.0, 0.9, 0.7, 1.0])
_KNOBS = {
    "all_greedy": ([0.0] * 8, [1.0] * 8),
    "all_sampling": ([0.7, 1.0, 1.3, 0.2, 0.9, 1.0, 2.0, 0.5],
                     [0.9, 1.0, 0.5, 1.0, 0.95, 0.1, 1.0, 0.8]),
    "mixed": _MIXED,
    "one_sampling_row": ([0.0] * 7 + [1.0], [1.0] * 7 + [0.9]),
    "verify_window": tuple(np.repeat(knob, 3) for knob in _MIXED),
}


class TestSamplingDeterminism:
    def test_temperature_zero_is_argmax(self):
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(5, VOCAB), jnp.float32)
        keys = jnp.stack([jax.random.PRNGKey(i) for i in range(5)])
        toks = sample_tokens(logits, keys, jnp.zeros(5), jnp.ones(5))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.argmax(np.asarray(logits), -1))

    @pytest.mark.parametrize("row_sharded", [False, True],
                             ids=["one_device", "rows_over_mesh8"])
    @pytest.mark.parametrize("case", list(_KNOBS))
    def test_equals_the_unconditional_sampler(self, mesh8, case,
                                              row_sharded):
        """Token for token the sampler that ran every row through the
        nucleus: greedy rows on either branch, sampling rows on the one
        branch they ever see, alone or over the mesh's batch shards."""
        from distributed_pytorch_training_tpu.parallel.sharding import (
            batch_sharding,
        )

        temps, top_ps = (np.asarray(x, np.float32) for x in _KNOBS[case])
        rows = len(temps)
        rng = np.random.RandomState(rows)
        logits = rng.randn(rows, VOCAB).astype(np.float32)
        logits[:, 5] = logits[:, 11]      # a tie in every row
        keys = np.stack([np.asarray(jax.random.PRNGKey(40 + i), np.uint32)
                         for i in range(rows)])
        args = (logits, keys, temps, top_ps)
        if row_sharded:
            args = tuple(jax.device_put(a, batch_sharding(mesh8, a.ndim))
                         for a in args)
        got = jax.jit(sample_tokens)(*args)
        want = jax.jit(oracle_sample_tokens)(*args)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        greedy = temps <= 0.0
        np.testing.assert_array_equal(np.asarray(got)[greedy],
                                      np.argmax(logits, -1)[greedy])

    def test_one_cond_and_no_sort_on_the_greedy_side(self):
        """One program with two branches: exactly one `cond`, whose false
        branch (no row samples) is the argmax and nothing that sorts,
        gathers or draws; the true branch is where the sort lives."""
        args = (jnp.zeros((4, VOCAB)), jnp.zeros((4, 2), jnp.uint32),
                jnp.zeros(4), jnp.ones(4))
        jaxpr = jax.make_jaxpr(sample_tokens)(*args).jaxpr
        assert primitives(jaxpr).count("cond") == 1
        (cond,) = [e for j in sub_jaxprs(jaxpr) for e in j.eqns
                   if e.primitive.name == "cond"]
        no_row_samples, some_row_samples = (
            primitives(b.jaxpr) for b in cond.params["branches"])
        assert "argmax" in no_row_samples
        assert not {"sort", "gather", "cumsum", "random_bits",
                    "threefry2x32"} & set(no_row_samples)
        assert "sort" in some_row_samples and "argmax" in some_row_samples
        # nothing that sorts stands outside the cond either
        outside = [e.primitive.name for e in jaxpr.eqns]
        assert "sort" not in outside and "pjit" not in outside, outside

    @pytest.mark.parametrize("kind", ["paged_decode", "paged_prefill",
                                      "paged_resume"])
    def test_each_program_holds_the_sampler_once(self, slot_engine, kind):
        """One compiled program per kind, as before the sampler branched:
        the branch is inside the program (one conditional each), not a
        host-side choice between two executables."""
        lowered = {"paged_decode": slot_engine.lower_paged_decode,
                   "paged_prefill": lambda: slot_engine.lower_paged_prefill(8),
                   "paged_resume": lambda: slot_engine.lower_paged_resume(8),
                   }[kind]()
        assert lowered.as_text().count("stablehlo.case") == 1

    def test_stream_ignores_slots_join_order_and_company(self, slot_engine,
                                                         tiny):
        """Same (prompt, seed, knobs) -> identical tokens whether the
        request runs alone, joins last behind one crowd, or first ahead
        of a different one — slot index and batch-mates are invisible."""
        (target,) = prompts((7,), seed=10)
        t_kw = dict(temperature=0.8, top_p=0.9, seed=1234,
                    max_new_tokens=6)
        decoys_a = [(s, dict(temperature=1.0, seed=50 + i,
                             max_new_tokens=3 + i % 4))
                    for i, s in enumerate(prompts((5, 12, 3, 9, 15, 6, 4),
                                                  seed=11))]
        decoys_b = [(s, dict(temperature=0.0, max_new_tokens=2 + i % 5))
                    for i, s in enumerate(prompts((14, 2, 8, 10), seed=12))]
        alone = serve_all(slot_engine, [(target, t_kw)])[0]
        last = serve_all(slot_engine, decoys_a + [(target, t_kw)])[-1]
        first = serve_all(slot_engine, [(target, t_kw)] + decoys_b)[0]
        np.testing.assert_array_equal(alone.tokens, last.tokens)
        np.testing.assert_array_equal(alone.tokens, first.tokens)

    def test_greedy_stream_ignores_which_branch_its_company_chooses(
            self, slot_engine, tiny):
        """A greedy request alone and beside greedy company runs on the
        sampler's argmax branch; beside a sampling request every step it
        shares runs on the nucleus branch. Its stream is the solo greedy
        forward's in all three."""
        model, params = tiny
        (target,) = prompts((9,), seed=20)
        t_kw = dict(temperature=0.0, max_new_tokens=6)
        greedy_co = [(s, dict(temperature=0.0, max_new_tokens=3 + i))
                     for i, s in enumerate(prompts((4, 13, 7), seed=21))]
        sampling_co = [(s, dict(temperature=0.9, top_p=0.95, seed=70 + i,
                                max_new_tokens=2 + 2 * i))
                       for i, s in enumerate(prompts((6, 11, 3), seed=22))]
        want = ref_greedy(model, params, target, 6)
        for company in ([], greedy_co, sampling_co,
                        greedy_co + sampling_co):
            got = serve_all(slot_engine, company + [(target, t_kw)])[-1]
            np.testing.assert_array_equal(got.tokens, want)

    def test_every_stream_is_the_unconditional_samplers(self, slot_engine,
                                                        oracle_engine):
        """Mixed traffic with churn (12 requests over 8 rows) through the
        engine and through one traced around the oracle sampler: every
        stream identical, the sampling requests' included — a sampling
        row's token is what it was before the sampler branched."""
        rng = np.random.RandomState(23)
        seqs = prompts([int(rng.randint(1, 17)) for _ in range(12)],
                       seed=24)
        specs = [(s, dict(temperature=float(rng.choice([0.0, 0.7, 1.2])),
                          top_p=float(rng.choice([0.9, 1.0])),
                          seed=300 + i,
                          max_new_tokens=int(rng.randint(1, 7))))
                 for i, s in enumerate(seqs)]
        assert {kw["temperature"] > 0 for _, kw in specs} == {True, False}
        got = serve_all(slot_engine, specs)
        want = serve_all(oracle_engine, specs)
        for i, (a, b) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(
                a.tokens, b.tokens, err_msg=f"request {i} ({specs[i][1]})")

    def test_a_finished_sampling_slot_does_not_choose_the_branch(
            self, devices, tiny, monkeypatch):
        """The predicate sees live rows only. A sampling request finishes
        and its slot lies idle, still holding its temperature, while a
        greedy request decodes on: those steps hand the sampler no
        positive temperature (the device's own predicate, probed), and
        the scheduler's two counters say the same from the host's mirror."""
        from distributed_pytorch_training_tpu.serving import continuous

        model, params = tiny
        mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
        seen = []
        monkeypatch.setattr(continuous, "sample_tokens",
                            probed_sampler(seen))
        eng = SlotEngine(model, mesh1, paged_cfg(rows=4, buckets=(8,)),
                         params)
        short, long = prompts((5, 7), seed=25)
        rec = telemetry.configure()
        try:
            res = serve_all(eng, [
                (short, dict(temperature=0.9, seed=5, max_new_tokens=2)),
                (long, dict(temperature=0.0, max_new_tokens=6))])
            jax.effects_barrier()
            events = rec.tail(10_000)
        finally:
            telemetry.reset()
        assert [len(r.tokens) for r in res] == [2, 6]
        np.testing.assert_array_equal(
            res[1].tokens, ref_greedy(model, params, long, 6))
        # the slot the sampling request left still holds its temperature
        assert float(jnp.max(eng._control["temps"])) > 0.0
        decode = [hot for rows, hot in seen if rows == 4]
        # token #0 comes from the prefill: one shared step ends the short
        # request, four more finish the long one
        assert decode == [True] + [False] * 4
        assert [hot for rows, hot in seen if rows == 1] == [True, False]
        assert decode_step_counters(events) == (len(decode),
                                                decode.count(False))

    @pytest.mark.parametrize("sampling", [0, 1], ids=["all_greedy",
                                                      "one_sampling"])
    def test_counters_say_how_often_the_argmax_branch_runs(
            self, slot_engine, sampling):
        """`serving_decode_steps_all_greedy` counts the decode steps whose
        every live request is greedy: all of them for greedy traffic,
        fewer as soon as one request samples (never none here: the
        sampling request is the shortest)."""
        specs = [(s, dict(temperature=0.0, max_new_tokens=6))
                 for s in prompts((5, 9, 12, 3), seed=26)]
        specs += [(prompts((6,), seed=27)[0],
                   dict(temperature=1.0, seed=9, max_new_tokens=3))
                  ] * sampling
        rec = telemetry.configure()
        try:
            serve_all(slot_engine, specs)
            events = rec.tail(10_000)
        finally:
            telemetry.reset()
        steps, all_greedy = decode_step_counters(events)
        assert steps >= 5
        if sampling:
            assert 0 < all_greedy < steps
        else:
            assert all_greedy == steps

    def test_distinct_seeds_diverge(self, slot_engine):
        (s,) = prompts((8,), seed=13)
        kw = dict(temperature=1.0, top_p=1.0, max_new_tokens=6)
        a, b = serve_all(slot_engine, [(s, dict(seed=1, **kw)),
                                       (s, dict(seed=2, **kw))])
        assert not np.array_equal(a.tokens, b.tokens)


# ---------------------------------------------------------------------------
# int8 pages: the HBM cut + deterministic quantization
# ---------------------------------------------------------------------------


class TestInt8Pages:
    @pytest.fixture(scope="class")
    def int8_engine(self, mesh8):
        # head_dim 32 (the smallest real-model head width — gpt2 heads
        # are 64): the per-(row, head) fp32 scale amortizes over the head
        # dim, so the >= 3x cut needs real head widths; the depth-2
        # hidden-32 toy's head_dim 16 pays 25% scale overhead and lands
        # at ~2.9x, which is the honest accounting, not a miss
        model = tiny_model(hidden_dim=64)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32),
                            train=False)["params"]
        # one bucket: these tests pin bytes + determinism, not bucket
        # routing (TestSlotEngineGreedy owns that), and each extra
        # bucket is a whole extra prefill compile at hidden 64
        eng = SlotEngine(model, mesh8,
                         paged_cfg(buckets=(16,), kv_dtype="int8"), params)
        eng.warmup()
        return eng

    def test_byte_ratio_at_least_3x(self, int8_engine):
        ratio = (int8_engine.dense_baseline_bytes()
                 / int8_engine.paged_bytes())
        assert ratio >= 3.0, f"int8 paged/dense byte ratio {ratio:.2f} < 3"

    def test_quantization_is_deterministic(self, int8_engine):
        """The wire-codec grid story: serving the same requests twice
        (fresh pool each time) emits identical tokens — the int8
        perturbation is a deterministic function of the values, so every
        replica agrees (the router's resubmit-invisibility premise)."""
        seqs = prompts((6, 11, 4), seed=14)
        specs = [(s, dict(temperature=0.0)) for s in seqs]
        first = serve_all(int8_engine, specs)
        second = serve_all(int8_engine, specs)
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.last_logits, b.last_logits)


class TestFusedPagedScatter:
    """ISSUE 20 satellite: the int8 page write path rides the PR 6 fused
    Pallas quantize kernels — every paged scatter (row, window, prefill)
    threads the codec's ``fused`` tri-state down to
    ``grad_sync._quantize_int8_rows``. On CPU the kernel runs in Pallas
    interpreter mode, and the PR 6 exactness model says the pool BYTES
    cannot depend on the flag: codes AND scales bitwise identical, fused
    vs the XLA-composed reference."""

    L, PAGES, PS, H, D = 2, 5, 4, 2, 8

    def _pool(self):
        from distributed_pytorch_training_tpu.models.layers import (
            init_paged_kv,
        )

        return init_paged_kv(self.L, self.PAGES, self.PS, self.H, self.D,
                             quantized=True)

    def _rand(self, shape, seed):
        return jnp.asarray(np.random.RandomState(seed)
                           .randn(*shape).astype(np.float32))

    def _assert_pools_bitwise(self, a, b):
        for leaf in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)),
                err_msg=f"paged pool leaf {leaf} depends on the fused flag")

    def test_row_scatter_fused_is_bitwise(self):
        from distributed_pytorch_training_tpu.models.layers import (
            scatter_paged_rows,
        )

        table = jnp.array([[1, 2], [3, 4], [2, 1]], jnp.int32)
        positions = jnp.array([0, 5, 3], jnp.int32)
        active = jnp.array([True, True, False])
        k = self._rand((self.L, 3, self.H, self.D), seed=0)
        v = self._rand((self.L, 3, self.H, self.D), seed=1)
        out = {f: scatter_paged_rows(self._pool(), table, positions, k, v,
                                     active, fused=f)
               for f in (False, True)}
        self._assert_pools_bitwise(out[False], out[True])
        assert np.asarray(out[True].k).any()  # the write actually landed

    def test_window_scatter_fused_is_bitwise(self):
        from distributed_pytorch_training_tpu.models.layers import (
            scatter_paged_window,
        )

        table = jnp.array([[1, 2], [3, 4]], jnp.int32)
        positions = jnp.array([[0, 1, 2], [4, 5, 6]], jnp.int32)
        active = jnp.array([[True, True, False], [True, True, True]])
        k = self._rand((self.L, 2, 3, self.H, self.D), seed=2)
        v = self._rand((self.L, 2, 3, self.H, self.D), seed=3)
        out = {f: scatter_paged_window(self._pool(), table, positions, k,
                                       v, active, fused=f)
               for f in (False, True)}
        self._assert_pools_bitwise(out[False], out[True])
        assert np.asarray(out[True].k).any()

    def test_prefill_scatter_fused_is_bitwise(self):
        from distributed_pytorch_training_tpu.models.layers import (
            scatter_paged_prefill,
        )

        page_row = jnp.array([1, 3], jnp.int32)
        k = self._rand((self.L, 2 * self.PS, self.H, self.D), seed=4)
        v = self._rand((self.L, 2 * self.PS, self.H, self.D), seed=5)
        length = jnp.int32(6)  # bucket padding past 6 must be dropped
        out = {f: scatter_paged_prefill(self._pool(), page_row, k, v,
                                        length, fused=f)
               for f in (False, True)}
        self._assert_pools_bitwise(out[False], out[True])
        assert np.asarray(out[True].k).any()


# ---------------------------------------------------------------------------
# Telemetry: registered spans, live gauges, summary bucketing
# ---------------------------------------------------------------------------


class TestServingTelemetry:
    def test_span_names_registered(self):
        from distributed_pytorch_training_tpu.telemetry.recorder import (
            REGISTERED_SPAN_NAMES, SERVING_SPAN_NAMES,
        )

        assert {"slot_wait", "router_dispatch"} <= set(SERVING_SPAN_NAMES)
        assert {"slot_wait", "router_dispatch"} <= set(
            REGISTERED_SPAN_NAMES)

    def test_spans_and_gauges_emitted_and_bucketed(self, slot_engine):
        """A routed serve emits slot_wait + router_dispatch spans and the
        occupancy/page-pool gauges; `telemetry summary` folds the spans
        into the step-time split instead of "unaccounted"."""
        from distributed_pytorch_training_tpu.telemetry.__main__ import (
            summarize,
        )

        slot_engine.reset_state()
        rec = telemetry.configure()          # ring-only stream
        try:
            replica = InProcessReplica("r0", slot_engine)
            router = Router([replica])
            reqs = [router.submit(s, temperature=0.0)
                    for s in prompts((5, 9, 12), seed=15)]
            for r in reqs:
                r.result(timeout=120.0)
            replica.stop()
            events = rec.tail(10_000)
        finally:
            telemetry.reset()
        names = {e["name"] for e in events if e["kind"] == "span"}
        assert {"slot_wait", "router_dispatch", "prefill"} <= names
        gauges = {e["name"] for e in events if e["kind"] == "gauge"}
        assert {"serving_slot_occupancy", "serving_page_pool_free",
                "serving_queue_depth"} <= gauges
        summary = summarize(events)
        assert "slot_wait" in summary["spans"]
        assert "router_dispatch" in summary["spans"]
        # the split accounts the serving phases by name (a typo'd name
        # would vanish into "unaccounted"); synthetic durations keep the
        # assertion robust to microsecond real spans rounding to 0
        synth = summarize([
            {"kind": "span", "name": n, "dur_ms": 5.0}
            for n in ("slot_wait", "router_dispatch")])
        assert set(synth["step_split_pct"]) == {"slot_wait",
                                                "router_dispatch"}


# ---------------------------------------------------------------------------
# The scheduler iteration as spans: five phases that tile `step`, children
# inside `sched_admit` and `sched_complete`, exact token counts at the fence
# ---------------------------------------------------------------------------

PHASES = ("sched_pull", "sched_admit", "sched_dispatch", "sched_fence",
          "sched_complete")
# what may lie inside which phase
CHILDREN = {"page_alloc": ("sched_admit",),
            "prefill": ("sched_admit",), "prefill_skip": ("sched_admit",),
            "page_table_put": ("sched_admit", "sched_complete"),
            "slot_fetch": ("sched_complete",)}
# a wall-clock second of the 2020s has 2.4e-7 s between neighbouring
# doubles and `dur_ms` is rounded to 1e-4 ms: "the same instant" and
# "inside" are held to a microsecond
CLOCK_EPS_S = 1e-6


@pytest.fixture(scope="module")
def spec_slot_engine(mesh8, tiny):
    from distributed_pytorch_training_tpu.serving.speculative import (
        SpeculativeEngine,
    )

    model, params = tiny
    draft = tiny_model(hidden_dim=16, depth=1, num_heads=2)
    dparams = draft.init(jax.random.PRNGKey(7), np.zeros((1, 8), np.int32),
                         train=False)["params"]
    eng = SpeculativeEngine(model, mesh8, paged_cfg(), params, draft,
                            dparams, spec_k=3)
    eng.warmup()
    return eng


def emitted_so_far(sched, reqs):
    """Tokens of every result so far plus those the running slots have
    emitted, from the host mirror (``want - left``)."""
    done = sum(len(r.result(timeout=0).tokens) for r in reqs
               if r.t_done is not None)
    return done + sum(st.want - st.left for st in sched.running.values())


@pytest.fixture(scope="module", params=["plain", "speculative"])
def phase_run(request, slot_engine, spec_slot_engine):
    """A run of mixed admissions and completions under the recorder: more
    requests than rows (so some wait), budgets of 1..6 (so slots leave at
    different fences), one prompt three times (so the plain engine takes
    its skip admission), a second wave that joins mid-run. The token
    count of `sched_fence` is read against the host mirror twice: with
    requests still running, and after the drain."""
    from distributed_pytorch_training_tpu.serving.speculative import (
        SpeculativeScheduler,
    )

    engine, cls = {"plain": (slot_engine, ContinuousScheduler),
                   "speculative": (spec_slot_engine, SpeculativeScheduler),
                   }[request.param]
    engine.reset_state()
    q = RequestQueue(engine.config.buckets)
    sched = cls(engine, q)
    again = prompts((8,), seed=4)[0]
    first = [(p, w) for p, w in zip(
        prompts((5, 9, 12, 3, 16, 7, 11, 4, 6, 13), seed=21),
        (6, 3, 1, 6, 2, 6, 5, 4, 6, 2))] + [(again, 4)]
    second = [(again, 6), (prompts((10,), seed=5)[0], 3), (again, 1)]
    rec = telemetry.configure(None, ring_size=1 << 16)
    try:
        reqs = [q.submit(p, max_new_tokens=w) for p, w in first]
        for _ in range(4):
            sched.step()
        events = [e for e in rec.tail(1 << 16) if e["kind"] == "span"]
        midway = (sum(e["tokens"] for e in events
                      if e["name"] == "sched_fence"),
                  emitted_so_far(sched, reqs), len(sched.running))
        reqs += [q.submit(p, max_new_tokens=w) for p, w in second]
        sched.drain()
        results = [r.result(timeout=120.0) for r in reqs]
        events = [e for e in rec.tail(1 << 16) if e["kind"] == "span"]
    finally:
        telemetry.reset()
    return dict(kind=request.param, sched=sched, events=events,
                results=results, midway=midway,
                wants=[w for _, w in first + second])


def by_iteration(events):
    out = collections.defaultdict(list)
    for e in events:
        if "iter" in e:
            out[e["iter"]].append(e)
    return out


class TestSchedulerPhases:
    def test_phases_tile_every_iteration(self, phase_run):
        """The phase spans of one ``iter`` are the five in order (or the
        first two, for an iteration with nothing running), each starting
        where the one before it ends, so that their durations sum to
        `sched_pull`'s start .. `sched_complete`'s end; iterations follow
        each other without overlapping."""
        iterations = by_iteration(phase_run["events"])
        assert len(iterations) >= 6
        advanced, last_end = 0, 0.0
        for it in sorted(iterations):
            spans = [e for e in iterations[it] if e["name"] in PHASES]
            names = tuple(e["name"] for e in spans)
            assert names in (PHASES, PHASES[:2]), (it, names)
            advanced += names == PHASES
            assert spans[0]["t0"] >= last_end - CLOCK_EPS_S
            for a, b in zip(spans, spans[1:]):
                assert a["t0"] + a["dur_ms"] / 1e3 == pytest.approx(
                    b["t0"], abs=CLOCK_EPS_S)
            last_end = spans[-1]["t0"] + spans[-1]["dur_ms"] / 1e3
            assert sum(e["dur_ms"] for e in spans) / 1e3 == pytest.approx(
                last_end - spans[0]["t0"], abs=CLOCK_EPS_S)
        assert advanced >= 6

    def test_children_lie_inside_their_phase(self, phase_run):
        """Every child span carries its iteration, slot and request, lies
        inside one phase of that iteration that may hold it, and the
        children of a phase leave it a self time that is not negative."""
        iterations = by_iteration(phase_run["events"])
        seen = collections.Counter()
        for it, events in iterations.items():
            phases = {e["name"]: e for e in events if e["name"] in PHASES}
            inside = collections.Counter()
            for e in events:
                if e["name"] not in CHILDREN:
                    continue
                assert {"slot", "request"} <= set(e), e
                a, b = e["t0"], e["t0"] + e["dur_ms"] / 1e3
                home = [n for n in CHILDREN[e["name"]] if n in phases
                        and phases[n]["t0"] - CLOCK_EPS_S <= a
                        and b <= phases[n]["t0"] + phases[n]["dur_ms"] / 1e3
                        + CLOCK_EPS_S]
                assert len(home) == 1, (it, e, phases)
                if e["name"] == "page_table_put":
                    assert e["at"] == home[0][len("sched_"):]
                inside[home[0]] += e["dur_ms"]
                seen[e["name"]] += 1
            for name, ms in inside.items():
                assert ms <= phases[name]["dur_ms"] + 1e3 * CLOCK_EPS_S
        n = len(phase_run["results"])
        assert seen["page_alloc"] >= n and seen["slot_fetch"] == n
        assert seen["page_table_put"] == 2 * n
        assert seen["prefill"] + seen["prefill_skip"] == n
        if phase_run["kind"] == "plain":   # the repeated prompt skipped
            assert seen["prefill_skip"] == \
                phase_run["sched"].prefill_skips > 0

    def test_fence_tokens_are_exact(self, phase_run):
        """`tokens` summed over `sched_fence` is what the server has
        emitted: the tokens of all results plus those of requests still
        running, mid-run and after the drain; the phases' other counts
        add up to the requests."""
        fenced, mirrored, running = phase_run["midway"]
        assert running > 0 and fenced == mirrored > 0
        events, results = phase_run["events"], phase_run["results"]
        assert [len(r.tokens) for r in results] == phase_run["wants"]
        total = lambda name, key: sum(  # noqa: E731
            e[key] for e in events if e["name"] == name)
        assert total("sched_fence", "tokens") == sum(phase_run["wants"])
        assert total("sched_pull", "took") == len(results)
        assert total("sched_admit", "admitted") == len(results)
        assert total("sched_complete", "completed") == len(results)
        # fence by fence: what the steps emit, plus the token #0 of every
        # admission of the iteration that went through a prefill
        for it, spans in by_iteration(events).items():
            fence = [e for e in spans if e["name"] == "sched_fence"]
            if not fence:
                continue
            prefills = sum(e["name"] == "prefill" for e in spans)
            stepped = fence[0]["tokens"] - prefills
            per_slot = 1 if phase_run["kind"] == "plain" else 4  # K + 1
            assert fence[0]["live"] * fence[0]["steps"] <= stepped <= \
                fence[0]["live"] * fence[0]["steps"] * per_slot, (it, spans)

    def test_an_idle_poll_and_an_unconfigured_step_emit_nothing(
            self, slot_engine):
        slot_engine.reset_state()
        q = RequestQueue(slot_engine.config.buckets)
        sched = ContinuousScheduler(slot_engine, q)
        req = q.submit(prompts((6,), seed=2)[0], max_new_tokens=2)
        assert telemetry.get() is None
        while sched.step():      # unconfigured: the whole request
            pass
        assert len(req.result(timeout=60.0).tokens) == 2
        rec = telemetry.configure(None, ring_size=256)
        try:
            before = sched.iteration
            assert sched.step() is False     # an idle poll
            assert sched.iteration == before + 1
            assert [e for e in rec.tail(256) if e["kind"] != "meta"] == []
        finally:
            telemetry.reset()

    @pytest.mark.parametrize("program", ["paged_decode", "paged_prefill"])
    def test_paged_programs_lower_the_same_with_the_recorder_on(
            self, slot_engine, program):
        """The spans are host-side only: the lowered text of the paged
        decode and prefill programs cannot tell a configured recorder
        from none (the train step's pin, `test_telemetry.py`, for the
        programs the scheduler dispatches)."""
        lower = {"paged_decode": slot_engine.lower_paged_decode,
                 "paged_prefill": lambda: slot_engine.lower_paged_prefill(8),
                 }[program]
        assert telemetry.get() is None
        off = lower().as_text()
        telemetry.configure(None, ring_size=16)
        try:
            on = lower().as_text()
        finally:
            telemetry.reset()
        assert on == off

    def test_scheduler_spans_are_registered_unaccounted(self):
        from distributed_pytorch_training_tpu.telemetry import metrics_http
        from distributed_pytorch_training_tpu.telemetry.__main__ import (
            summarize,
        )
        from distributed_pytorch_training_tpu.telemetry.recorder import (
            REGISTERED_SPAN_NAMES, SCHEDULER_SPAN_NAMES,
        )

        assert set(SCHEDULER_SPAN_NAMES) == set(PHASES) | {
            "page_alloc", "page_table_put", "slot_fetch"}
        assert set(SCHEDULER_SPAN_NAMES) <= set(REGISTERED_SPAN_NAMES)
        assert not set(SCHEDULER_SPAN_NAMES) & set(metrics_http._PHASES)
        # they run around `prefill` and inside `slot_wait`: in the spans
        # table, never in the step-time split
        summary = summarize(
            [{"kind": "span", "name": n, "dur_ms": 5.0}
             for n in (*SCHEDULER_SPAN_NAMES, "prefill")])
        assert set(SCHEDULER_SPAN_NAMES) <= set(summary["spans"])
        assert set(summary["step_split_pct"]) == {"prefill"}


# ---------------------------------------------------------------------------
# The serving_paged contract + paged-pool-donated rule (mutation-tested)
# ---------------------------------------------------------------------------


class TestPagedContract:
    def test_contract_passes_on_mesh(self, mesh8):
        from distributed_pytorch_training_tpu.analysis.contracts import (
            get_contract,
        )
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts, evaluate_contract,
        )

        contract = get_contract("serving_paged")
        # the matrix pins the int8 arm — the most droppable leaves
        assert contract.config.get("paged_kv_dtype") == "int8"
        artifacts = evaluate_contract(contract, mesh=mesh8)
        # layer-stacked pool: 4 int8 leaves (codes + scales), NOT x depth
        assert artifacts.config["paged_cache_leaves"] == 4
        findings = check_artifacts(artifacts)
        assert findings == [], [str(f) for f in findings]

    def test_live_engine_artifacts_pass(self, slot_engine):
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts, paged_serving_artifacts,
        )

        artifacts = paged_serving_artifacts(slot_engine)
        assert artifacts.config["paged_cache_leaves"] == 2  # fp32 k/v
        assert check_artifacts(artifacts) == []

    def test_mutation_missing_alias_entries_flag(self):
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            StepArtifacts, check_artifacts,
        )

        partial = StepArtifacts(
            name="mut", optimized_text=(
                "HloModule paged, input_output_alias={ {0}: (1, {}, "
                "may-alias) }, entry_computation_layout={()}"),
            config={"serving_paged": True, "donate_state": True,
                    "paged_cache_leaves": 4})
        found = check_artifacts(partial, rules=["paged-pool-donated"])
        assert len(found) == 1 and "1 of the >= 4" in found[0].message
        absent = StepArtifacts(
            name="mut2", optimized_text="HloModule paged",
            config={"serving_paged": True, "donate_state": True,
                    "paged_cache_leaves": 2})
        assert check_artifacts(absent, rules=["paged-pool-donated"])
        train = StepArtifacts(name="t", optimized_text="HloModule x",
                              config={"donate_state": False})
        assert check_artifacts(train, rules=["paged-pool-donated"]) == []

    def test_mutation_dropped_leaf_flags(self, slot_engine):
        """Raising the census above the real table simulates one pool
        leaf falling out of the alias set — the rule must fire on the
        REAL lowering, not only on synthetic text."""
        import dataclasses as dc

        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts, paged_serving_artifacts,
        )

        artifacts = paged_serving_artifacts(slot_engine)
        poisoned = dc.replace(
            artifacts, config={**artifacts.config,
                               "paged_cache_leaves":
                               artifacts.config["paged_cache_leaves"]
                               + 100})
        found = check_artifacts(poisoned, rules=["paged-pool-donated"])
        assert len(found) == 1

    def test_mutation_host_transfer_in_decode_flags(self, slot_engine):
        """The train step's no-host-transfer rule binds on the decode
        step's artifacts: a callback smuggled into its text is flagged
        with NO rule relaxation."""
        import dataclasses as dc

        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts, paged_serving_artifacts,
        )

        artifacts = paged_serving_artifacts(slot_engine)
        poisoned = dc.replace(
            artifacts, optimized_text=artifacts.optimized_text +
            '\n  custom-call(), custom_call_target="xla_python_cpu_callback"')
        found = check_artifacts(poisoned, rules=["no-host-transfer"])
        assert len(found) == 1


# ---------------------------------------------------------------------------
# Router unit semantics (no devices)
# ---------------------------------------------------------------------------


class _StubPending:
    def __init__(self, replica, fail_first=False):
        self.replica = replica
        self.fail = fail_first

    def result(self, timeout=None):
        if self.fail or self.replica.dead:
            raise ReplicaDead(f"replica {self.replica.name} died")
        from distributed_pytorch_training_tpu.serving.batching import Result

        return Result(tokens=np.zeros(1, np.int32),
                      last_logits=np.zeros(VOCAB, np.float32))


class _StubReplica:
    def __init__(self, name, depth=0):
        self.name = name
        self.depth = depth
        self.dead = False
        self.submits = []

    def healthy(self):
        return not self.dead

    def queue_depth(self):
        return self.depth

    def submit(self, tokens, **kw):
        if self.dead:
            raise ReplicaDead(f"replica {self.name} is down")
        self.submits.append(kw)
        return _StubPending(self)


class TestRouterUnits:
    def test_least_depth_wins(self):
        a, b = _StubReplica("a", depth=5), _StubReplica("b", depth=1)
        router = Router([a, b])
        for _ in range(3):
            router.submit(np.ones(4, np.int32)).result(timeout=1.0)
        assert len(b.submits) == 3 and not a.submits

    def test_seed_pinned_at_route_time_and_survives_resubmit(self):
        a, b = _StubReplica("a"), _StubReplica("b")
        router = Router([a, b])
        req = router.submit(np.ones(4, np.int32))
        seed = req.kw["seed"]
        assert seed is not None
        first = req.replica_name
        req._inner.fail = True            # the dispatched copy dies
        router.replicas[first].dead = True
        req.result(timeout=1.0)           # resubmits to the survivor
        assert req.replica_deaths == 1 and req.replica_name != first
        survivor = router.replicas[req.replica_name]
        assert survivor.submits[-1]["seed"] == seed

    def test_distinct_requests_get_distinct_seeds(self):
        router = Router([_StubReplica("a")])
        r1 = router.submit(np.ones(4, np.int32))
        r2 = router.submit(np.ones(4, np.int32))
        assert r1.kw["seed"] != r2.kw["seed"]

    def test_no_healthy_replicas_raises(self):
        a = _StubReplica("a")
        a.dead = True
        router = Router([a])
        with pytest.raises(ReplicaDead, match="no healthy"):
            router.submit(np.ones(4, np.int32))

    def test_duplicate_names_rejected(self):
        with pytest.raises(ValueError, match="unique"):
            Router([_StubReplica("a"), _StubReplica("a")])

    def test_slow_replica_times_out_without_resubmit(self):
        """A healthy-but-slow replica raises TimeoutError from result():
        the router must surface it, not declare the replica dead and
        stack a duplicate in-flight copy of the request on it."""
        class _SlowPending:
            def result(self, timeout=None):
                raise TimeoutError("still pending")

        class _SlowReplica(_StubReplica):
            def submit(self, tokens, **kw):
                self.submits.append(kw)
                return _SlowPending()

        a = _SlowReplica("a")
        req = Router([a]).submit(np.ones(4, np.int32))
        with pytest.raises(TimeoutError):
            req.result(timeout=0.2)
        assert req.replica_deaths == 0
        assert len(a.submits) == 1     # exactly one in-flight copy

    def test_replica_death_loop_respects_deadline(self):
        """Every dispatch dies instantly while the replica still reports
        healthy (the pathological spin): the caller's deadline must
        surface as TimeoutError, never an unbounded resubmit loop."""
        class _DyingPending:
            def __init__(self, name):
                self.name = name

            def result(self, timeout=None):
                time.sleep(0.001)
                raise ReplicaDead(f"replica {self.name} died")

        class _DyingReplica(_StubReplica):
            def submit(self, tokens, **kw):
                self.submits.append(kw)
                return _DyingPending(self.name)

        router = Router([_DyingReplica("a"), _DyingReplica("b")])
        req = router.submit(np.ones(4, np.int32))
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError, match="replica deaths"):
            req.result(timeout=0.2)
        assert time.perf_counter() - t0 < 5.0
        assert req.replica_deaths >= 1

    def test_http_pending_timeout_is_not_a_death(self, monkeypatch):
        """Socket timeouts (bare or URLError-wrapped) surface as
        TimeoutError and leave the replica healthy; a refused connection
        is ReplicaDead and marks it down."""
        import urllib.request as _ur

        replica = HttpReplica("h", port=1)

        for exc in (socket.timeout("timed out"),
                    urllib.error.URLError(socket.timeout("timed out"))):
            def _raise(*a, _exc=exc, **kw):
                raise _exc
            monkeypatch.setattr(_ur, "urlopen", _raise)
            with pytest.raises(TimeoutError):
                replica.submit(np.ones(3, np.int32)).result(timeout=0.1)
            assert replica.healthy()   # slow is not dead

        def _refuse(*a, **kw):
            raise ConnectionRefusedError("refused")
        monkeypatch.setattr(_ur, "urlopen", _refuse)
        with pytest.raises(ReplicaDead):
            replica.submit(np.ones(3, np.int32)).result(timeout=0.1)
        assert not replica.healthy()


# ---------------------------------------------------------------------------
# Landing a kill with work in flight, without a clock
# ---------------------------------------------------------------------------


class _AnnouncingLock:
    """A scheduler's lock that says when a caller found it held
    (``waited``) and when that caller has had it and let go (``passed``)."""

    def __init__(self):
        self._inner = threading.Lock()
        self._waiter = None
        self.waited = threading.Event()
        self.passed = threading.Event()

    def __enter__(self):
        if not self._inner.acquire(blocking=False):
            if not self.waited.is_set():
                self._waiter = threading.get_ident()
                self.waited.set()
            self._inner.acquire()

    def __exit__(self, *exc):
        self._inner.release()
        if self._waiter == threading.get_ident():
            self.passed.set()


def hold_a_step_for_kill(monkeypatch, sched, stop, ready=lambda: True):
    """Make `sched.kill()` land mid-step and with work in flight, whatever
    the machine's load. The first decode step for which ``ready()`` holds
    keeps the scheduler's lock until a kill() is WAITING for it; the
    worker then leaves that kill() the step boundary (``stop.is_set`` is
    what `run` reads there, outside the lock: a plain Lock is not fair,
    and on a loaded box the worker used to barge past the waiter until
    nothing was left to kill). Returns the event to wait on before calling
    kill(), and the lock. The timeouts only bound a failure."""
    lock = _AnnouncingLock()
    in_step = threading.Event()
    decode_step, is_set = sched.engine.decode_step, stop.is_set

    def held_decode_step():
        if ready() and not lock.waited.is_set():
            in_step.set()
            assert lock.waited.wait(30.0), "kill() never took the lock"
        return decode_step()

    def yielding_is_set():
        if lock.waited.is_set():
            lock.passed.wait(30.0)
        return is_set()

    monkeypatch.setattr(sched, "_lock", lock)
    monkeypatch.setattr(sched.engine, "decode_step", held_decode_step)
    monkeypatch.setattr(stop, "is_set", yielding_is_set)
    return in_step, lock


# ---------------------------------------------------------------------------
# Scheduler kill: nothing hangs
# ---------------------------------------------------------------------------


class TestSchedulerKill:
    def test_kill_fails_queued_pending_and_running(self, slot_engine):
        """An injected death resolves EVERY accepted request — including
        the ones still parked in the queue (an abandoned queue entry
        would hang its waiter forever; the router needs the error to
        resubmit)."""
        slot_engine.reset_state()
        q = RequestQueue(slot_engine.config.buckets)
        sched = ContinuousScheduler(slot_engine, q)
        reqs = [q.submit(s, temperature=0.0)
                for s in prompts((4, 7, 10), seed=16)]
        failed = sched.kill()
        assert len(failed) == 3
        for r in reqs:
            with pytest.raises(RuntimeError, match="died"):
                r.result(timeout=5.0)
        # the queue refuses new work after the death
        with pytest.raises(RuntimeError):
            q.submit(np.ones(4, np.int32))

    def test_kill_mid_step_resolves_each_request_exactly_once(
            self, monkeypatch):
        """kill() runs on the CALLER's thread while the worker is inside
        step(): it must wait for the step boundary — no 'dict changed
        size' crash iterating running/pending, and no request resolved
        twice (set_result by the completing step AND set_error by the
        kill). The scheduler lock is what keeps this green.

        No clock paces it (`hold_a_step_for_kill`; a `time.sleep` did, and
        on a loaded box the worker served all 30 before the kill got the
        lock): the kill lands on the step after the first completions."""
        cfg = paged_cfg()
        completed: list = []           # slots whose results are out

        class _StubEngine:
            config = cfg
            _control = {"tok": np.zeros(cfg.rows, np.int32)}

            def set_page_row(self, slot, row):
                pass

            def admit(self, slot, tokens, want, temperature, top_p, seed):
                return cfg.buckets[-1]

            def decode_step(self):
                pass

            def fetch_slot(self, slot):
                completed.append(slot)
                return (np.zeros(cfg.max_new_tokens, np.int32),
                        np.zeros(VOCAB, np.float32))

        resolutions = collections.Counter()
        count_lock = threading.Lock()
        orig_result = batching.Request.set_result
        orig_error = batching.Request.set_error

        def counting_result(self, res):
            with count_lock:
                resolutions[self.id] += 1
            orig_result(self, res)

        def counting_error(self, err):
            with count_lock:
                resolutions[self.id] += 1
            orig_error(self, err)

        monkeypatch.setattr(batching.Request, "set_result",
                            counting_result)
        monkeypatch.setattr(batching.Request, "set_error", counting_error)

        q = RequestQueue(cfg.buckets)
        sched = ContinuousScheduler(_StubEngine(), q)
        stop = threading.Event()
        in_step, lock = hold_a_step_for_kill(
            monkeypatch, sched, stop, ready=lambda: bool(completed))
        worker_err: list = []

        def run():
            try:
                sched.run(stop)
            except BaseException as e:  # noqa: BLE001 - the race crash
                worker_err.append(e)

        # all 30 are queued before the worker starts: rows=8, so results
        # are out AND work is in flight when the held step is reached
        reqs = [q.submit(s) for s in prompts([4] * 30, seed=23)]
        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert in_step.wait(30.0), f"no step was held: {worker_err}"
        sched.kill()
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert not worker_err, f"worker crashed: {worker_err}"
        assert lock.passed.is_set()    # the kill did wait for a step
        served = failed = 0            # everything resolves, nothing hangs
        for r in reqs:
            try:
                r.result(timeout=5.0)
                served += 1
            except RuntimeError:       # the kill's error (ReplicaDead kin)
                failed += 1
        assert served + failed == len(reqs) and served > 0 and failed > 0
        assert len(resolutions) == len(reqs)
        assert set(resolutions.values()) == {1}, (
            f"double-resolved requests: "
            f"{[i for i, n in resolutions.items() if n > 1]}")


# ---------------------------------------------------------------------------
# The fleet acceptance drill: 2 replicas, 1 death, all bitwise
# ---------------------------------------------------------------------------


class TestFleetAcceptance:
    @pytest.fixture(scope="class")
    def fleet_engines(self, devices, tiny):
        """Two SlotEngines on DISJOINT 4-device slices — the fleet
        topology (replicas do not share chips), and a hard in-process
        requirement: the row-sharded decode carries collectives, and two
        scheduler threads dispatching collective programs over
        OVERLAPPING device sets deadlock the CPU rendezvous."""
        model, params = tiny
        engines = []
        for i in range(2):
            mesh = build_mesh(MeshSpec(data=4),
                              devices=devices[i * 4:(i + 1) * 4])
            eng = SlotEngine(model, mesh, paged_cfg(), params)
            eng.warmup()
            engines.append(eng)
        return engines

    def test_fleet_kill_all_complete_bitwise(self, fleet_engines, tiny,
                                             monkeypatch):
        model, params = tiny
        eng_a, eng_b = fleet_engines
        warm = (eng_a.compiles, eng_b.compiles)
        ra = InProcessReplica("r0", eng_a, start=False)
        in_step, _lock = hold_a_step_for_kill(monkeypatch, ra.scheduler,
                                              ra._stop)
        ra._thread.start()
        rb = InProcessReplica("r1", eng_b)
        router = Router([ra, rb])
        rng = np.random.RandomState(7)
        seqs = [rng.randint(0, VOCAB, int(rng.randint(1, 17)))
                .astype(np.int32) for _ in range(22)]
        reqs = [router.submit(s, temperature=0.0, max_new_tokens=6)
                for s in seqs]
        # the death must land with work IN FLIGHT on r0: its first decode
        # step is held until the kill waits for it (a poll of queue_depth
        # did this once, and under load r0 drained before the kill got in)
        assert in_step.wait(60.0), "r0 never held work to kill"
        failed = ra.kill()
        assert failed, "the kill found nothing in flight"
        results = [r.result(timeout=300.0) for r in reqs]

        assert len(results) == 22
        assert sum(r.replica_deaths for r in reqs) >= 1
        assert not ra.healthy() and rb.healthy()
        # zero recompiles on BOTH engines, through death and resubmission
        assert (eng_a.compiles, eng_b.compiles) == warm
        # every stream bitwise the solo full-context greedy forward —
        # resubmission is invisible in the output
        for i, (s, res) in enumerate(zip(seqs, results)):
            np.testing.assert_array_equal(
                res.tokens, ref_greedy(model, params, s, 6),
                err_msg=f"request {i} (len {len(s)}, "
                        f"deaths {reqs[i].replica_deaths})")
        router.stop()


# ---------------------------------------------------------------------------
# The CLI bench arm (slow: subprocess e2e)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_cli_bench_continuous_exits_zero(tmp_path):
    """`serving bench --mixed-want` runs the offered-load
    row end to end and exits 0 iff recompiles_after_warmup == 0 (the
    hard gate the fleet bench arms reuse)."""
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m",
         "distributed_pytorch_training_tpu.serving", "bench",
         "--mixed-want",
         "--model", "gpt2_124m",
         "--model-overrides", "hidden_dim=32,depth=2,num_heads=2",
         "--buckets", "8,16", "--rows", "8", "--max-new-tokens", "4",
         "--requests", "8", "--offered-load", "16",
         "--output-dir", str(tmp_path / "out")],
        env=env, cwd=str(Path(__file__).resolve().parent.parent),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr

"""Draft-model speculative decoding over the paged cache (ISSUE 19).

Every emitted token in the continuous engine costs one full target
forward. Speculative decoding buys several tokens per target forward
without changing a single emitted byte:

* A small DRAFT model proposes K greedy tokens per live slot per round,
  decoding over its OWN smaller paged pool (same page machinery, fp32).
* The TARGET verifies all K+1 window positions in ONE batched forward —
  the per-row-positions decode mode of models/gpt2.py generalized to an
  S-token window, whose row j is BITWISE the s=1 decode step at that
  position (the window parity pin in models/layers.py).
* Acceptance is exact token match: window output j is the token the
  plain path would have sampled at that position (same logits bitwise,
  same ``fold_in(request_key, position)`` key), and a proposal is
  accepted only when it EQUALS that token. Every emitted token is
  target-sampled, so the stream is pinned BITWISE vs the non-speculative
  SlotEngine — the draft's numerics steer only the accept RATIO, never
  the output (PARITY.md "Exactness model: speculative decode").
* Rejection is structural rollback, never re-prefill: the round commits
  the window's target k/v rows page-locally and advances the frontier by
  the accepted count only; stale rows past the frontier are rewritten
  in-view before any later window can see them (same masking argument as
  bucket padding), and the draft simply restarts its next propose run
  from the target's frontier.

fp32 pools only: an int8 pool would hand the verify window FRESH fp32
k/v for in-window rows where the plain path reads the dequantized page
bytes it committed one step earlier — residency in the window would
change the stream. The engine refuses int8 outright (the same exactness
economics as the prefix-skip gate in serving/continuous.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..data.pack import bucket_for
from ..models.layers import gather_paged_kv, scatter_paged_prefill, \
    scatter_paged_window
from .batching import Request, RequestQueue
from .continuous import ContinuousScheduler, SlotEngine, sample_tokens
from .paged import PagedServeConfig, PageLease, PagePool


class SpeculativeEngine(SlotEngine):
    """`SlotEngine` plus a draft model and two extra compiled programs:

    * ``draft_propose`` — K sequential draft decode steps over the draft
      pool (one gather, K in-view applies, one window scatter back),
      returning (rows, K) greedy proposals. Reads the TARGET control's
      positions/tok READ-ONLY — the draft keeps no control of its own,
      so rejection rollback is free: the next round re-reads the
      target's frontier.
    * ``spec_verify`` — the target's K+1-window forward + exact-match
      acceptance + window commit, replacing `decode_step` in the
      speculative scheduler's round. Donates pool + control exactly like
      the plain decode step (the ``serving_spec`` contract pins it) and
      additionally returns the per-slot emitted count — the ONE value
      the host must see each round.

    Draft prefill compiles per bucket like the target's; the whole
    program set compiles at `warmup` and the census stays flat.
    """

    def __init__(self, model, mesh, config: PagedServeConfig, params,
                 draft_model, draft_params, spec_k: int = 4,
                 batch_stats: Any = None, rules=None):
        if config.kv_dtype != "fp32":
            raise ValueError(
                "speculative decoding needs an fp32 page pool: the verify "
                "window reads in-window rows as fresh fp32 where the "
                "plain int8 path reads dequantized page bytes — int8 "
                "speculation would change the emitted stream (PARITY.md)")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        super().__init__(model, mesh, config, params,
                         batch_stats=batch_stats, rules=rules)
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        # the draft pool must cover prompt + want + K positions per slot:
        # the last propose run of a request writes draft k/v up to
        # (n + want - 2) + K - 1. Sizing via the same config math keeps
        # the fail-safe floor semantics (paged.py `total_pages`).
        self.draft_config = dataclasses.replace(
            config, max_new_tokens=config.max_new_tokens + spec_k,
            kv_dtype="fp32", n_pages=0)
        if self.draft_padded_len > draft_model.max_position:
            raise ValueError(
                f"draft pages_per_slot * page_size = "
                f"{self.draft_padded_len} exceeds the draft model's "
                f"max_position {draft_model.max_position}")
        self._require_windows("speculative decoding")
        if getattr(draft_model, "vocab_size", None) != getattr(
                model, "vocab_size", None):
            raise ValueError(
                f"draft vocab {getattr(draft_model, 'vocab_size', None)} "
                f"!= target vocab {getattr(model, 'vocab_size', None)}: "
                "proposals are target-vocab token ids compared by exact "
                "match — the vocabularies must be the same table")
        self._draft_served = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, draft_params), self._rep)
        self.reset_draft_state()

    @property
    def draft_padded_len(self) -> int:
        cfg = self.draft_config
        return cfg.pages_per_slot * cfg.page_size

    def reset_state(self) -> None:
        super().reset_state()
        if hasattr(self, "draft_model"):   # base __init__ calls us early
            self.reset_draft_state()

    def reset_draft_state(self) -> None:
        """Zeroed draft pool + all-scratch draft table (compiled programs
        survive, same as `reset_state`)."""
        cfg = self.draft_config
        dpool = self.draft_model.init_paged_pool(
            cfg.total_pages, cfg.page_size, quantized=False)
        self._draft_pool = jax.device_put(dpool, self._rep)
        self._draft_table = np.zeros(
            (cfg.rows, cfg.pages_per_slot), np.int32)
        self._draft_table_dev = jax.device_put(self._draft_table,
                                               self._row_sharding(2))
        self._proposals = jax.device_put(
            np.zeros((cfg.rows, self.spec_k), np.int32),
            self._row_sharding(2))

    def draft_set_page_row(self, slot: int, row: np.ndarray) -> None:
        """`set_page_row` for the draft table (host numpy authoritative,
        device copy refreshed here, never in the round)."""
        self._draft_table[slot] = row
        self._draft_table_dev = jax.device_put(self._draft_table,
                                               self._row_sharding(2))

    # -- compiled programs ---------------------------------------------------

    def _draft_vars(self, dparams) -> dict:
        return {"params": dparams}

    def _draft_pool_avals(self):
        return jax.tree_util.tree_map(
            lambda x: self._rep_aval(x.shape, x.dtype), self._draft_pool)

    def _make_draft_prefill(self, bucket: int) -> Callable:
        def dprefill(dserved, dpool, dtable, ids, length, slot):
            cache0 = self.draft_model.init_cache(1, bucket)
            _logits, cache = self.draft_model.apply(
                self._draft_vars(dserved), ids, train=False, cache=cache0)
            row = dtable[slot]
            k_seqs = jnp.stack([c[0][0] for c in cache])
            v_seqs = jnp.stack([c[1][0] for c in cache])
            return scatter_paged_prefill(dpool, row, k_seqs, v_seqs,
                                         length)

        return dprefill

    def _make_draft_propose(self) -> Callable:
        k_spec = self.spec_k
        dpad = self.draft_padded_len

        def propose(dserved, dpool, dtable, positions, tok, budget):
            # K greedy draft steps chained through the dense in-view
            # cache: step j feeds the previous proposal at positions + j
            # and writes its k/v row in view; ONE window scatter commits
            # all K rows back to the draft pool afterwards. The target's
            # positions/tok are read-only inputs — draft state never
            # feeds back into target state except through `proposals`.
            active = budget > 0
            k_all, v_all = gather_paged_kv(dpool, dtable,
                                           dtype=self.draft_model.dtype)
            cache = tuple((k_all[l], v_all[l])
                          for l in range(self.draft_model.depth))
            cur = tok
            props = []
            # K+1 applies for K proposals: the last one only writes its
            # k/v row — a fully-accepted round advances the frontier by
            # K+1, and the next propose run attends position p+K, so the
            # draft cache must cover it (skipping this write starves the
            # draft after its first perfect round and craters the accept
            # ratio)
            for j in range(k_spec + 1):
                logits, cache = self.draft_model.apply(
                    self._draft_vars(dserved), cur[:, None], train=False,
                    cache=cache, cache_positions=positions + j)
                if j < k_spec:
                    cur = jnp.argmax(logits[:, 0],
                                     axis=-1).astype(jnp.int32)
                    props.append(cur)
            proposals = jnp.stack(props, axis=1)          # (rows, K)
            win_pos = positions[:, None] + jnp.arange(k_spec + 1)[None, :]
            idxc = jnp.clip(win_pos, 0, dpad - 1)[:, :, None, None]
            k_rows = jnp.stack([jnp.take_along_axis(c[0], idxc, axis=1)
                                for c in cache])   # (L, rows, K, H, D)
            v_rows = jnp.stack([jnp.take_along_axis(c[1], idxc, axis=1)
                                for c in cache])
            act = active[:, None] & (win_pos < dpad)
            new_dpool = scatter_paged_window(dpool, dtable, win_pos,
                                             k_rows, v_rows, act)
            return new_dpool, proposals

        return propose

    def _make_spec_verify(self) -> Callable:
        cfg: PagedServeConfig = self.config
        rows, s = cfg.rows, self.spec_k + 1
        pad = self.padded_len

        def verify(served, pool, control, page_table, proposals):
            params = self._dequant(served)
            active = control["budget"] > 0
            positions = control["positions"]
            tok = control["tok"]
            # the verify window: the committed-next token plus the K
            # draft proposals, one batched S-row forward over the pool
            window = jnp.concatenate([tok[:, None], proposals], axis=1)
            k_all, v_all = gather_paged_kv(pool, page_table,
                                           dtype=self.model.dtype)
            cache = tuple((k_all[l], v_all[l])
                          for l in range(self.model.depth))
            logits, new_cache = self.model.apply(
                self._apply_vars(params), window, train=False,
                cache=cache, cache_positions=positions)  # (rows, S, vocab)
            # sample every window output with ITS position's key — window
            # row j's token is bitwise the plain step's at that position
            # (same logits by the window parity pin, same fold_in key,
            # and sample_tokens is row-independent: whichever of its two
            # branches the other rows make it take, a row's token is the
            # same). A dead row's outputs are all dropped below (n_emit
            # 0), so its stale temperature is zeroed like the plain
            # step's and cannot choose the branch.
            win_pos = positions[:, None] + jnp.arange(s)[None, :]
            step_keys = jax.vmap(jax.random.fold_in)(
                jnp.repeat(control["keys"], s, axis=0),
                (win_pos + 1).reshape(-1))
            outs = sample_tokens(
                logits.reshape(rows * s, -1), step_keys,
                jnp.repeat(jnp.where(active, control["temps"], 0.0), s),
                jnp.repeat(control["top_ps"], s)).reshape(rows, s)
            # exact-match acceptance: keep the longest prefix of
            # proposals that equals the target-sampled stream, then emit
            # one more (the target's own token at the first mismatch) —
            # never past the remaining budget
            match = (outs[:, :-1] == proposals).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            n_emit = jnp.where(
                active, jnp.minimum(n_acc + 1, control["budget"]), 0)
            # commit ALL S window rows page-locally: rows past the new
            # frontier hold a rejected continuation, but every later
            # reader rewrites them in-view before its mask can expose
            # them (the structural-rollback argument above)
            idxc = jnp.clip(win_pos, 0, pad - 1)[:, :, None, None]
            k_rows = jnp.stack([jnp.take_along_axis(c[0], idxc, axis=1)
                                for c in new_cache])
            v_rows = jnp.stack([jnp.take_along_axis(c[1], idxc, axis=1)
                                for c in new_cache])
            act = active[:, None] & (win_pos < pad)
            new_pool = scatter_paged_window(pool, page_table, win_pos,
                                            k_rows, v_rows, act)
            # emit outs[:n_emit] into out_buf at this slot's cursor
            out_idx = jnp.where(
                jnp.arange(s)[None, :] < n_emit[:, None],
                control["emitted"][:, None] + jnp.arange(s)[None, :],
                cfg.max_new_tokens)
            out_buf = control["out_buf"].at[
                jnp.arange(rows)[:, None], out_idx].set(outs, mode="drop")
            last = jnp.take_along_axis(
                outs, jnp.clip(n_emit - 1, 0, s - 1)[:, None],
                axis=1)[:, 0]
            # skip-admitted slots capture their last-prompt logits off
            # window row 0 — same last_pos protocol as the plain step
            cap = positions == control["last_pos"]
            new_control = dict(control)
            new_control["tok"] = jnp.where(active, last, tok)
            new_control["positions"] = positions + n_emit
            new_control["budget"] = control["budget"] - n_emit
            new_control["emitted"] = control["emitted"] + n_emit
            new_control["out_buf"] = out_buf
            new_control["last_buf"] = jnp.where(
                cap[:, None], logits[:, 0], control["last_buf"])
            new_control["last_pos"] = jnp.where(
                cap, -1, control["last_pos"])
            return new_pool, new_control, n_emit

        return verify

    def lower_draft_prefill(self, bucket: int):
        """The lowered B=1 draft admission fill — draft pool DONATED."""
        cfg = self.draft_config
        dpool_avals = self._draft_pool_avals()
        scalar_i = self._rep_aval((), jnp.int32)
        return jax.jit(
            self._make_draft_prefill(bucket), donate_argnums=(1,),
            out_shardings=self._out_shardings(dpool_avals),
        ).lower(self._draft_served, dpool_avals,
                self._row_aval((cfg.rows, cfg.pages_per_slot), jnp.int32),
                self._rep_aval((1, bucket), jnp.int32), scalar_i, scalar_i)

    def lower_draft_propose(self):
        """The lowered K-step propose round — draft pool DONATED; target
        positions/tok/budget are read-only inputs."""
        cfg = self.draft_config
        rows = cfg.rows
        dpool_avals = self._draft_pool_avals()
        outs = (dpool_avals,
                self._row_aval((rows, self.spec_k), jnp.int32))
        return jax.jit(
            self._make_draft_propose(), donate_argnums=(1,),
            out_shardings=self._out_shardings(outs),
        ).lower(self._draft_served, dpool_avals,
                self._row_aval((rows, cfg.pages_per_slot), jnp.int32),
                self._row_aval((rows,), jnp.int32),
                self._row_aval((rows,), jnp.int32),
                self._row_aval((rows,), jnp.int32))

    def lower_spec_verify(self):
        """The lowered K+1-window verify step — pool + control DONATED
        exactly like the plain decode step's (the `serving_spec` contract
        reads this); the extra ``n_emit`` output is the round's one
        host-visible value."""
        cfg: PagedServeConfig = self.config
        pool_avals = self._pool_avals()
        ctrl_avals = self._control_avals()
        outs = (pool_avals, ctrl_avals,
                self._row_aval((cfg.rows,), jnp.int32))
        return jax.jit(
            self._make_spec_verify(), donate_argnums=(1, 2),
            out_shardings=self._out_shardings(outs),
        ).lower(self._served, pool_avals, ctrl_avals,
                self._row_aval((cfg.rows, cfg.pages_per_slot), jnp.int32),
                self._row_aval((cfg.rows, self.spec_k), jnp.int32))

    def _executable(self, kind: str, bucket: int):
        if kind not in ("draft_prefill", "draft_propose", "spec_verify"):
            return super()._executable(kind, bucket)
        key = (kind, bucket)
        if key not in self._compiled:
            lowered = {
                "draft_prefill": lambda: self.lower_draft_prefill(bucket),
                "draft_propose": self.lower_draft_propose,
                "spec_verify": self.lower_spec_verify,
            }[kind]()
            self._compile(kind, bucket, lowered)
        return self._compiled[key]

    def warmup(self) -> int:
        super().warmup()
        self._executable("draft_propose", 0)
        self._executable("spec_verify", 0)
        for b in self.config.buckets:
            self._executable("draft_prefill", b)
        return self.compiles

    # -- runtime entries -----------------------------------------------------

    def draft_admit(self, slot: int, tokens: np.ndarray) -> int:
        """Fill the slot's draft pages from the prompt (no control, no
        sampling — the draft only ever needs k/v). Unfenced like the
        target admission; the scheduler's round fence bounds it."""
        cfg = self.draft_config
        bucket = bucket_for(len(tokens), cfg.buckets)
        ids = np.full((1, bucket), cfg.pad_id, np.int32)
        ids[0, :len(tokens)] = tokens
        dev = lambda x: jax.device_put(x, self._rep)  # noqa: E731
        exe = self._executable("draft_prefill", bucket)
        self._draft_pool = exe(
            self._draft_served, self._draft_pool, self._draft_table_dev,
            dev(ids), dev(np.int32(len(tokens))), dev(np.int32(slot)))
        return bucket

    def draft_propose(self) -> None:
        """One K-token propose round for every live slot (device-chained;
        the proposals buffer feeds `verify_step` without a host trip)."""
        exe = self._executable("draft_propose", 0)
        self._draft_pool, self._proposals = exe(
            self._draft_served, self._draft_pool, self._draft_table_dev,
            self._control["positions"], self._control["tok"],
            self._control["budget"])

    def verify_step(self):
        """One verify round over the whole slot pool; returns the (rows,)
        per-slot emitted-count DEVICE array — the scheduler fetches it
        once per round (acceptance is inherently a host decision: the
        budget mirrors must advance by the true accepted counts)."""
        exe = self._executable("spec_verify", 0)
        self._pool, self._control, n_emit = exe(
            self._served, self._pool, self._control, self._table_dev,
            self._proposals)
        return n_emit

    def draft_bytes(self) -> int:
        """At-rest bytes of the draft pool (fp32) — the bench's HBM
        accounting includes the speculation tax explicitly."""
        from ..models.layers import paged_kv_bytes

        return paged_kv_bytes(self._draft_pool)


class SpeculativeScheduler(ContinuousScheduler):
    """`ContinuousScheduler` whose advance is one propose + verify round.

    The three base-class hooks manage the draft lease lifecycle: a
    request is admitted only when BOTH pools can hold it (`_draft_admit`
    — a failed draft lease rolls the target lease back and the request
    stays pending), the draft prefill dispatches right after the target
    admission lands (`_post_admit`), and completion releases the draft
    pages with the target's (`_post_complete`). Everything else — skip /
    resume admission, TTFT stamping, drain/kill — is inherited unchanged.
    """

    def __init__(self, engine: SpeculativeEngine, queue: RequestQueue):
        if not isinstance(engine, SpeculativeEngine):
            raise ValueError("SpeculativeScheduler needs a "
                             "SpeculativeEngine (draft model + verify "
                             "step); plain SlotEngines run under "
                             "ContinuousScheduler")
        super().__init__(engine, queue)
        dcfg = engine.draft_config
        # the draft allocator: no prefix sharing (draft pages are never
        # content-addressed — the draft always prefills its own copy, so
        # a draft admission can never change target residency/behavior)
        self.draft_pool = PagePool(dcfg.total_pages, dcfg.page_size,
                                   dcfg.pages_per_slot,
                                   prefix_sharing=False)
        self._draft_leases: Dict[int, PageLease] = {}   # guarded-by: _lock
        self._draft_pending: Dict[int, PageLease] = {}  # guarded-by: _lock
        # acceptance census: proposals offered vs accepted (the gauge the
        # bench's accept-ratio column reads)
        self.spec_rounds = 0                            # guarded-by: _lock
        self.spec_proposed = 0                          # guarded-by: _lock
        self.spec_accepted = 0                          # guarded-by: _lock

    @property
    def accept_ratio(self) -> float:
        """Accepted draft tokens / proposed draft tokens, cumulative."""
        with self._lock:
            return (self.spec_accepted / self.spec_proposed
                    if self.spec_proposed else 0.0)

    # -- draft lease lifecycle (the base-class hooks) ------------------------

    def _draft_admit(self, req: Request, lease: PageLease,
                     want: int) -> bool:   # lock-held: _lock
        eng: SpeculativeEngine = self.engine
        dlease = self.draft_pool.alloc(
            req.tokens, len(req.tokens) + want + eng.spec_k)
        if dlease is None:
            return False
        self._draft_pending[req.id] = dlease
        return True

    def _post_admit(self, slot: int, req: Request) -> None:  # lock-held: _lock
        eng: SpeculativeEngine = self.engine
        dlease = self._draft_pending.pop(req.id)
        self._draft_leases[slot] = dlease
        eng.draft_set_page_row(slot, dlease.pages)
        t0 = time.perf_counter()
        bucket = eng.draft_admit(slot, req.tokens)
        telemetry.span_event("draft_decode", time.perf_counter() - t0,
                             prefill=True, bucket=bucket, slot=slot,
                             request=req.id)

    def _post_complete(self, slot: int) -> None:   # lock-held: _lock
        eng: SpeculativeEngine = self.engine
        dlease = self._draft_leases.pop(slot, None)
        if dlease is not None:
            self.draft_pool.release(dlease)
            eng.draft_set_page_row(
                slot, np.zeros(eng.draft_config.pages_per_slot, np.int32))

    # -- the speculative round -----------------------------------------------

    def _advance(self) -> Tuple[int, int, int]:   # lock-held: _lock
        """One propose + verify round: up to K+1 tokens per slot per
        fence. The n_emit fetch is the round's one host sync — the
        accepted counts ARE host state (budget mirrors, completion), and
        the caller fences right after anyway; the per-token
        no-host-sync contract (`_step_decode_loop`) is untouched because
        this path never runs it. Returns the base class's triple: one
        round, the slots with budget left, the tokens the round emitted
        within those budgets."""
        eng: SpeculativeEngine = self.engine
        live = len(self.running)
        budgeted = sum(st.left > 0 for st in self.running.values()) \
            if telemetry.is_configured() else 0
        emitted = 0
        t0 = time.perf_counter()
        eng.draft_propose()
        t1 = time.perf_counter()
        telemetry.span_event("draft_decode", t1 - t0, k=eng.spec_k,
                             slots=live)
        n_emit = np.asarray(jax.device_get(eng.verify_step()))
        t2 = time.perf_counter()
        telemetry.span_event("spec_verify", t2 - t1, slots=live)
        for slot, st in self.running.items():
            got = int(n_emit[slot])
            emitted += min(got, st.left)
            st.left = max(st.left - got, 0)
            # emitted - 1 of each round's tokens came from accepted
            # proposals (the +1 is the target's own token); the clamp to
            # the remaining budget is still "accepted" for the ratio —
            # the draft was right, the request just ended
            self.spec_accepted += max(got - 1, 0)
        self.spec_proposed += eng.spec_k * live
        self.spec_rounds += 1
        if self.spec_proposed:
            # inline, not the accept_ratio property: that takes _lock
            # for external readers and this method already holds it
            telemetry.gauge("spec_accept_ratio",
                            self.spec_accepted / self.spec_proposed)
        return 1, budgeted, emitted


SpeculativeEngine.scheduler_cls = SpeculativeScheduler


def serve_speculative(engine: SpeculativeEngine, queue: RequestQueue,
                      stop, log=None) -> int:
    """Worker-loop twin of `serve_continuous` for the speculative
    scheduler (the CLI runs one per replica thread when --draft is
    armed)."""
    return SpeculativeScheduler(engine, queue).run(stop, log=log)

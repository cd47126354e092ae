"""Continuous batching for a model that generates by DIFFUSION OVER BLOCKS
(models/sdar.py): the unit of a served step is a block of ``B =
model.block_length`` positions, not a token.

Positions fall in blocks of B from 0. A prompt of L tokens has its first ``L0
= (L // B) * B`` prefilled under the block-causal mask; nothing is sampled
from a prefill, it leaves K/V for positions below L0. Block n covers ``[L0 +
nB, L0 + (n + 1)B)``: the first opens holding the prompt's ``L - L0``
remaining tokens and MASK elsewhere, later blocks open all MASK. Whether a
position is masked is a BIT in the control block, never ``id == MASK`` (a
prompt may hold that id).

* `BlockDiffusionEngine` is a `SlotEngine` whose shared step is
  ``block_step``, ONE compiled program for all rows as the decode step is:
  every live row forwards its WINDOW of B ids against its committed pages,
  every window position seeing the committed positions and all B fresh
  keys. A row with a bit set DENOISES: among its masked positions the ``B /
  T`` of the highest confidence ``max softmax(logits_i)`` (ties to the lower
  position) take ``argmax logits_i`` (``T = denoising_steps``, a request's
  parameter; the static low-confidence schedule at temperature 0). A row
  whose bits are all clear COMMITS: the K/V this forward computed for its B
  final ids are written to the pool (they differ from every denoise step's,
  whose inputs were partly MASK, so nothing is written before), the block's
  tokens are emitted (less the prompt's remainder, the last block cut to the
  request's ``want``), ``positions`` moves by B and the next block opens.
  The two differ only in bookkeeping, so rows in different phases share a
  step and admission never waits for a block boundary. ``step_buf`` beside
  ``out_buf`` keeps the denoise step at which each emitted token was
  unmasked; ``last_buf`` keeps the first denoise step's logits at its
  window's last position.
* The window read is `SlotEngine.kv_path`'s: ``"kernel"``
  (`ops.paged_attention` with W = B query positions of grouped heads, the
  pool in place) on one TPU with an unquantized pool, ``"gather"`` (the
  reference read) everywhere else. ``page_size`` is a multiple of B and
  blocks are aligned, so a block lies in one page.
* `BlockDiffusionScheduler` is the host loop. With the static schedule a
  row's phase is a function of how many steps it has taken, so the host
  mirror moves by a block at a commit and by nothing at a denoise step,
  with no fetch; a request's first token lands at its first commit's fence.

Scopes of the step, by which the benchmark's ``sdar_block_*_ms`` read a
trace (``benchmark/layer_metrics/_sdar_regions.py``): ``kv_gather``,
``model`` (with the model's own inside), ``unmask``, ``kv_scatter``,
``bookkeeping``. Prefix skip, resume and the speculative engine are not for
such a model and raise (ROADMAP R18); so does a request that samples.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..data.pack import bucket_for
from ..models.layers import (
    PagedRead,
    gather_paged_kv,
    scatter_paged_prefill,
    scatter_paged_window,
)
from .batching import Request
from .continuous import _COUNTERS, ContinuousScheduler, SlotEngine, _SlotState
from .paged import PagedServeConfig


def denoise_steps_of(masked: int, per_step: int) -> int:
    """Denoise steps a block with ``masked`` positions to fill takes."""
    return -(-masked // per_step)


class BlockDiffusionEngine(SlotEngine):
    """`SlotEngine` whose shared step forwards a window a row and commits a
    block. The program kinds keep their names (``paged_prefill``,
    ``paged_decode``), so warm-up, the compile census and the
    ``serving_paged`` donation contract read it as they read the decode
    step."""

    serves_blocks = True

    def __init__(self, model, mesh, config: PagedServeConfig, params,
                 batch_stats=None, rules=None):
        block = int(getattr(model, "block_length", 1))
        if block < 2:
            raise ValueError(
                "BlockDiffusionEngine serves a model that generates by "
                f"blocks (block_length > 1), got {block}: a SlotEngine "
                "serves this one")
        if config.page_size % block or any(b % block for b in config.buckets):
            raise ValueError(
                f"page_size {config.page_size} and buckets {config.buckets} "
                f"must be whole blocks of {block} positions")
        self.block_length = block
        super().__init__(model, mesh, config, params,
                         batch_stats=batch_stats, rules=rules)

    # -- state --------------------------------------------------------------

    def _init_control(self) -> Dict[str, jnp.ndarray]:
        """The slot state of a decode step plus a row's phase: the window's
        ids, which of its positions are still masked, at which step each was
        unmasked, the denoise steps taken in this block, how many positions
        a step unmasks, and how many window positions of the first block are
        the prompt's (emitted by nobody). ``positions`` is the window's
        start; ``last_pos`` is 0 until the request's first denoise step has
        left its logits (at the window's last position) in ``last_buf``."""
        cfg: PagedServeConfig = self.config
        rows, b = cfg.rows, self.block_length
        return {
            **super()._init_control(),
            "win_ids": jnp.zeros((rows, b), jnp.int32),
            "win_masked": jnp.zeros((rows, b), bool),
            "win_step": jnp.full((rows, b), -1, jnp.int32),
            "steps_done": jnp.zeros((rows,), jnp.int32),
            "per_step": jnp.ones((rows,), jnp.int32),
            "skip": jnp.zeros((rows,), jnp.int32),
            "step_buf": jnp.full((rows, cfg.max_new_tokens), -1, jnp.int32),
        }

    # -- compiled programs ---------------------------------------------------

    def _make_paged_prefill(self, bucket: int) -> Callable:
        cfg: PagedServeConfig = self.config
        b = self.block_length

        def prefill(served, pool, control, page_table, ids, length, slot,
                    want, per_step):
            params = self._dequant(served)
            # the logits are nobody's: the head is dead code in this program
            _, cache = self.model.apply(
                self._apply_vars(params), ids, train=False,
                cache=self.model.init_cache(1, bucket))
            known = (length // b) * b
            seqs = [jnp.stack([leaf[0] for leaf in leaves])
                    for leaves in zip(*cache)]
            new_pool = scatter_paged_prefill(pool, page_table[slot], *seqs,
                                             known,
                                             fused=self._fused_quantize)
            # the first block opens holding the prompt's remainder
            held = length - known
            window = jax.lax.dynamic_slice_in_dim(ids[0], known, b)
            put = lambda name, value: control[name].at[slot].set(value)  # noqa: E731
            control = dict(
                control,
                positions=put("positions", known),
                budget=put("budget", want),
                emitted=put("emitted", 0),
                out_buf=put("out_buf", jnp.zeros(
                    (cfg.max_new_tokens,), jnp.int32)),
                step_buf=put("step_buf", jnp.full(
                    (cfg.max_new_tokens,), -1, jnp.int32)),
                last_pos=put("last_pos", 0),
                win_ids=put("win_ids", window),
                win_masked=put("win_masked", jnp.arange(b) >= held),
                win_step=put("win_step", jnp.full((b,), -1, jnp.int32)),
                steps_done=put("steps_done", 0),
                per_step=put("per_step", per_step),
                skip=put("skip", held))
            return new_pool, control

        return prefill

    def _make_paged_decode(self) -> Callable:
        """``block_step`` (module note). One step on a v5e: PERF.md section
        5."""
        cfg: PagedServeConfig = self.config
        rows, b = cfg.rows, self.block_length
        fused = self._fused_quantize
        kernel = self.kv_path == "kernel"
        counted = self._counter_names
        mask_id = int(self.model.mask_token_id)
        heads, head_dim = self.model.num_kv_heads, self.model.head_dim
        if counted:
            from ..training.tasks import step_counters

        def block_step(served, pool, control, page_table):
            params = self._dequant(served)
            active = control["budget"] > 0
            start = control["positions"]
            masked = control["win_masked"] & active[:, None]
            denoising = masked.any(axis=1)
            committing = active & ~denoising
            ids = jnp.where(control["win_masked"], mask_id,
                            control["win_ids"])
            if kernel:
                cache = PagedRead(pool=pool, page_table=page_table,
                                  live=jnp.where(active, start, 0))
            else:
                with jax.named_scope("kv_gather"):
                    views = gather_paged_kv(pool, page_table,
                                            dtype=self.model.dtype)
                    cache = tuple(tuple(view[l] for view in views)
                                  for l in range(self.model.depth))
            with jax.named_scope("model"):
                out = self.model.apply(
                    self._apply_vars(params), ids, train=False, cache=cache,
                    cache_positions=start,
                    **({"mutable": ["counters"]} if counted else {}))
                (logits, fresh), sown = out if counted else (out, None)
            with jax.named_scope("unmask"):
                # confidence = max softmax = 1 / sum exp(logit - max)
                top = logits.max(axis=-1)
                confidence = 1.0 / jnp.exp(logits - top[..., None]).sum(-1)
                best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                score = jnp.where(masked, confidence, -1.0)   # (rows, B)
                at = jnp.arange(b)
                ahead = (score[:, None, :] > score[:, :, None]) | (
                    (score[:, None, :] == score[:, :, None])
                    & (at[None, None, :] < at[None, :, None]))
                rank = (ahead & masked[:, None, :]).sum(-1)
                chosen = masked & (rank < control["per_step"][:, None])
            # write half: the B fresh rows of every COMMITTING row, every
            # layer at once; a denoising row's leave nothing behind
            with jax.named_scope("kv_scatter"):
                win_pos = start[:, None] + at[None, :]
                new_pool = scatter_paged_window(
                    pool, page_table, win_pos,
                    *(jnp.stack(leaves).reshape(
                        len(fresh), rows, b, heads, head_dim)
                      for leaves in zip(*fresh)),
                    committing[:, None] & (win_pos < self.padded_len),
                    fused=fused)
            with jax.named_scope("bookkeeping"):
                # a committing row emits its window past the prompt's part,
                # cut to what the request still wants
                skip, emitted = control["skip"], control["emitted"]
                n_emit = jnp.where(
                    committing, jnp.minimum(b - skip, control["budget"]), 0)
                take = at[None, :] - skip[:, None]            # (rows, B)
                put = (take >= 0) & (take < n_emit[:, None])
                row = jnp.where(put, jnp.arange(rows)[:, None], rows)
                col = emitted[:, None] + take
                new = dict(control)
                new["out_buf"] = control["out_buf"].at[row, col].set(
                    control["win_ids"], mode="drop")
                new["step_buf"] = control["step_buf"].at[row, col].set(
                    control["win_step"], mode="drop")
                new["emitted"] = emitted + n_emit
                new["budget"] = control["budget"] - n_emit
                new["positions"] = start + jnp.where(committing, b, 0)
                new["skip"] = jnp.where(committing, 0, skip)
                # the next block opens all MASK; a denoising row unmasks
                opens = committing[:, None]
                new["win_masked"] = jnp.where(
                    opens, True, control["win_masked"] & ~chosen)
                new["win_ids"] = jnp.where(
                    opens, 0, jnp.where(chosen, best, control["win_ids"]))
                new["win_step"] = jnp.where(
                    opens, -1, jnp.where(
                        chosen, control["steps_done"][:, None],
                        control["win_step"]))
                new["steps_done"] = jnp.where(
                    committing, 0,
                    control["steps_done"] + denoising.astype(jnp.int32))
                # a request's first denoise step keeps its logits at the
                # window's last position, which a first step always finds
                # masked (a static slice: gathered at the first masked
                # position instead, a row a slot, this cost 2.2 of a 43.5 ms
                # step on the chip, and under a `lax.cond` on "some row is
                # at its first" 0.6 GB of temporaries; PERF.md section 6)
                cap = denoising & (control["last_pos"] == 0)
                new["last_buf"] = jnp.where(cap[:, None], logits[:, b - 1],
                                            control["last_buf"])
                new["last_pos"] = jnp.where(cap, -1, control["last_pos"])
                if counted:
                    got = step_counters(sown.get("counters", {}))
                    new[_COUNTERS] = control[_COUNTERS] + jnp.stack(
                        [got[name] for name in counted]
                        + [jnp.ones((), jnp.float32)])
            return new_pool, new

        return block_step

    def lower_paged_prefill(self, bucket: int):
        """The lowered B=1 admission step: slot, length, want and the
        positions a step unmasks traced, pool + control DONATED."""
        cfg: PagedServeConfig = self.config
        pool_avals = self._pool_avals()
        ctrl_avals = self._control_avals()
        scalar_i = self._rep_aval((), jnp.int32)
        return jax.jit(
            self._make_paged_prefill(bucket), donate_argnums=(1, 2),
            out_shardings=self._out_shardings((pool_avals, ctrl_avals)),
        ).lower(self._served, pool_avals, ctrl_avals,
                self._row_aval((cfg.rows, cfg.pages_per_slot), jnp.int32),
                self._rep_aval((1, bucket), jnp.int32),
                scalar_i, scalar_i, scalar_i, scalar_i)

    # -- what such a model has no program for --------------------------------

    @property
    def prefix_skip_enabled(self) -> bool:
        return False

    @property
    def _windows_supported(self) -> bool:
        """Skip and resume enter the one-token decode step mid-prompt; a
        block engine has no such step (ROADMAP R18)."""
        return False

    def _require_windows(self, program: str) -> None:
        raise ValueError(
            f"{program} is for a model that emits a token a step: this one "
            f"generates by blocks of {self.block_length} (ROADMAP R18)")

    # -- the runtime entries (scheduler-facing) ------------------------------

    def refuses(self, req: Request) -> Optional[str]:
        """Why this engine cannot serve ``req``, or None."""
        steps = req.denoising_steps
        if steps is not None and (
                steps < 1 or self.block_length % int(steps)):
            return (f"denoising_steps {steps} does not divide the block "
                    f"length {self.block_length}")
        if req.temperature > 0.0:
            return ("a block-diffusion model is served at temperature 0 "
                    "(the static low-confidence schedule)")
        return None

    def admit(self, slot: int, tokens: np.ndarray, want: int,
              denoising_steps: Optional[int] = None) -> int:
        """Dispatch the slot's admission prefill (the prompt's whole blocks
        under the block mask; no token is emitted) and return the bucket
        served. Does not fence (`SlotEngine.admit`)."""
        cfg: PagedServeConfig = self.config
        bucket = bucket_for(len(tokens), cfg.buckets)
        ids = np.full((1, bucket), cfg.pad_id, np.int32)
        ids[0, :len(tokens)] = tokens
        per_step = self.block_length // int(denoising_steps
                                            or self.block_length)
        dev = lambda x: jax.device_put(x, self._rep)  # noqa: E731
        pre = self._executable("paged_prefill", bucket)
        self._pool, self._control = pre(
            self._served, self._pool, self._control, self._table_dev,
            dev(ids), dev(np.int32(len(tokens))), dev(np.int32(slot)),
            dev(np.int32(want)), dev(np.int32(per_step)))
        return bucket

    block_step = SlotEngine.decode_step

    def fetch_slot(self, slot: int) -> Tuple[np.ndarray, ...]:
        """ONE host fetch of a finished slot: tokens, the kept logits row,
        and the denoise step at which each token was unmasked."""
        return jax.device_get((self._control["out_buf"][slot],
                               self._control["last_buf"][slot],
                               self._control["step_buf"][slot]))


@dataclasses.dataclass
class _BlockState(_SlotState):
    """`_SlotState` plus the row's phase, replayed from the static schedule:
    steps until (and including) the commit of the block in flight, what that
    commit emits, the denoise steps a later block takes, and the committed
    positions the row's reads cover."""

    per_block: int = 1
    to_commit: int = 1
    emit_next: int = 0
    start: int = 0

    def steps_to_finish(self, block: int) -> int:
        after = self.left - self.emit_next
        return self.to_commit + -(-after // block) * (self.per_block + 1)

    def stepped(self, block: int) -> int:
        """One step of the row on the mirror; the tokens it committed."""
        self.to_commit -= 1
        if self.to_commit:
            return 0
        out = self.emit_next
        self.left -= out
        self.start += block
        self.to_commit = self.per_block + 1
        self.emit_next = min(block, self.left)
        return out


class BlockDiffusionScheduler(ContinuousScheduler):
    """`ContinuousScheduler` whose advance is block steps: the host mirror
    (``left``, which the benchmark reads under ``_lock``) moves by a block at
    a row's commit and by nothing at a denoise step."""

    prefill_emits = 0

    def __init__(self, engine: BlockDiffusionEngine, queue):
        if not isinstance(engine, BlockDiffusionEngine):
            raise ValueError(
                "BlockDiffusionScheduler needs a BlockDiffusionEngine; "
                "plain SlotEngines run under ContinuousScheduler")
        super().__init__(engine, queue)

    def _try_admit(self, req: Request) -> bool:   # lock-held: _lock
        why = self.engine.refuses(req)
        if why is not None:   # resolved here, as an error: never admitted
            self._t_popped.pop(req.id, None)
            req.set_error(ValueError(why))
            return True
        return super()._try_admit(req)

    def _admit_cold(self, slot: int, req: Request,
                    want: int) -> Tuple[int, int]:   # lock-held: _lock
        return self.engine.admit(slot, req.tokens, want,
                                 req.denoising_steps), want

    def _post_admit(self, slot: int, req: Request) -> None:  # lock-held: _lock
        block = self.engine.block_length
        st = self.running[slot]
        steps = int(req.denoising_steps or block)
        held = len(req.tokens) % block
        self.running[slot] = _BlockState(
            req=st.req, lease=st.lease, bucket=st.bucket, want=st.want,
            left=st.left, per_block=steps,
            to_commit=denoise_steps_of(block - held, block // steps) + 1,
            emit_next=min(block - held, st.want),
            start=len(req.tokens) - held)

    def _first_token_landed(self, st: _SlotState) -> bool:  # lock-held: _lock
        return st.left < st.want

    def _result_extras(self, st: _SlotState, more) -> dict:
        return {"unmask_steps": np.asarray(more[0][:st.want], np.int32)}

    def _advance(self) -> Tuple[int, int, int]:   # lock-held: _lock
        """1..burst block steps, the mirror replayed a step at a time — no
        host fetch. Returns the base class's triple: steps, the rows that
        went in with budget left, the tokens the steps committed."""
        block = self.engine.block_length
        live_rows = [st for st in self.running.values() if st.left > 0]
        steps = 1
        if not self.pending and not len(self.queue) and live_rows:
            steps = max(1, min(min(st.steps_to_finish(block)
                                   for st in live_rows), self.burst_steps))
        tokens = commits = forwards = reads = 0
        for _ in range(steps):
            self.engine.block_step()
            for st in live_rows:
                if st.left > 0:
                    forwards += 1
                    reads += st.start
                    out = st.stepped(block)
                    tokens += out
                    commits += out > 0
        self._fence_extra = {"commit_rows": commits}
        telemetry.counter("serving_block_steps", steps)
        telemetry.counter("serving_block_commit_rows", commits)
        telemetry.counter("serving_block_denoise_rows", forwards - commits)
        telemetry.counter("serving_block_positions_forwarded",
                          forwards * block)
        telemetry.counter("serving_block_tokens_committed", tokens)
        # committed positions the steps' reads covered (a window's own B
        # keys are fresh, not cached)
        telemetry.counter("serving_live_cache_tokens", reads)
        return steps, len(live_rows), tokens


BlockDiffusionEngine.scheduler_cls = BlockDiffusionScheduler

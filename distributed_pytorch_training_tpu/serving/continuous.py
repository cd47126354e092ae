"""Token-granular continuous batching over a paged KV cache: the server
of a causal LM. Requests join and leave the running batch between tokens,
so a short request never waits for its longest batch-mate and an arrival
never waits for a cycle to end. The decode loop is built around SLOTS:

* `SlotEngine` owns ONE compiled decode step over a fixed pool of
  ``rows`` slots plus one compiled prefill per bucket rung. A request is
  admitted into a free slot by its bucket's prefill (slot index, prompt
  length, sampling knobs are all TRACED scalars — admission never
  recompiles), and from then on the shared decode step advances EVERY
  live slot one token per call. Requests join and leave the running
  batch at token granularity; the per-row position/budget masks are the
  substrate (`budget > 0` is liveness, inactive rows' cache writes are
  dropped).
* The KV cache is the PAGED pool (models/layers.py): the decode step
  reads each slot's pages and scatters the one fresh row back. On one TPU
  with an unquantized pool the read is `ops.paged_attention`, page by
  page, in place (`SlotEngine.kv_path` == "kernel"); everywhere else the
  pages are gathered into the same dense view the bitwise-pinned decode
  attention consumes ("gather", the reference read). Page residency is a
  host decision (serving/paged.py `PagePool`): prefix sharing, eviction,
  int8 pages — none of it touches the compiled step.
* Sampling is threaded PER REQUEST like training threads per-step RNG
  keys: each slot carries its request's (key, temperature, top_p), and
  the token at absolute position ``q`` is sampled with
  ``fold_in(request_key, q)`` — a function of the request alone, so the
  emitted stream is identical regardless of slot assignment, join order,
  or batch company (the determinism satellite pins this).
  ``temperature=0`` short-circuits to argmax, and a step none of whose
  live rows samples runs the argmax
  alone (`sample_tokens` branches on the device).
* `ContinuousScheduler` is the host loop: admit from the queue
  (``RequestQueue.take`` — FIFO, bucket-blind), run the decode step,
  mirror per-slot budgets in Python ints, and complete requests the
  moment THEIR budget hits zero (host fetches happen here, outside the
  AST-pinned ``_step_decode_loop``). ``slot_wait`` spans and the
  slot-occupancy / page-pool gauges are emitted here, and the iteration
  itself as five phase spans that tile ``step`` (``sched_pull`` ..
  ``sched_complete``, sharing their boundaries and the iteration's
  ``iter``) with ``page_alloc`` / ``page_table_put`` / ``slot_fetch``
  inside the admission and the completion.

Layout: the page POOL is replicated over the mesh (pages are
slot-agnostic — prefix sharing crosses slots), while the per-slot
control arrays, page table, and every (rows, ...) intermediate of the
decode step SHARD over the batch axis whenever rows divide the shard
count — each device decodes its own slots and only the freshly written
k/v rows all-gather back into the pool (tokens, (L, rows, H, D) — tiny).
Everything is DONATED through both compiled programs, so each step
updates in place — the ``serving_paged`` HLO contract (analysis/) pins
the alias table.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..data.pack import bucket_for
from ..models.layers import (
    PagedKV,
    PagedRead,
    gather_paged_kv,
    paged_kv_bytes,
    scatter_paged_prefill,
    scatter_paged_rows,
    scatter_paged_window,
)
from ..ops.paged_attention import paged_attention_backend_supported
from ..parallel.mesh import batch_shard_count
from ..parallel.sharding import batch_sharding, replicated
from ..utils.locktrace import named_lock
from .batching import Request, RequestQueue, Result
from .engine import ServedModel
from .paged import PagedServeConfig, PageLease, PagePool


_COUNTERS = "model_counters"   # the control block's entry for them


@jax.named_scope("sample")
def sample_tokens(logits: jnp.ndarray, keys: jnp.ndarray,
                  temperatures: jnp.ndarray,
                  top_ps: jnp.ndarray) -> jnp.ndarray:
    """Per-row temperature/top-p sampling, (rows, vocab) logits -> (rows,)
    int32 tokens. Every op is row-independent and each row consumes its
    OWN key (``keys`` (rows, 2) uint32), so a row's token is a function of
    (its logits, its key, its knobs) alone — batch-mates, slot index, and
    pool size are invisible (the determinism contract). ``temperature <= 0``
    selects plain argmax.

    One program, two branches, chosen ON THE DEVICE by the temperatures
    it is handed: when no row has ``temperature > 0`` the argmax is the
    whole of it (the sort and the sorted gather over (rows, vocab) are
    35 of a 38 ms decode step at 64 x 50,257 on a v5e, PERF.md); when
    some row samples, every row goes through the nucleus branch, whose
    closing ``where`` still hands a greedy row its argmax. A greedy row's
    token is therefore the same on both branches and a sampling row only
    ever sees the second, so the contract above holds whichever is taken.
    Callers zero the temperature of a row whose token they drop (a dead
    slot), so that a finished sampling request does not hold later
    all-greedy steps on the nucleus branch."""

    def greedy_only():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def nucleus():
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        temps = jnp.maximum(temperatures, 1e-6)[:, None]
        scaled = logits.astype(jnp.float32) / temps
        order = jnp.argsort(-scaled, axis=-1)           # descending
        sorted_l = jnp.take_along_axis(scaled, order, axis=-1)
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # nucleus: keep the smallest prefix with mass >= top_p; the first
        # column always survives (cum - prob == 0 < top_p)
        keep = (cum - probs) < top_ps[:, None]
        masked = jnp.where(keep, sorted_l, jnp.finfo(jnp.float32).min)
        choice = jax.vmap(lambda k, row: jax.random.categorical(k, row))(
            keys, masked)
        sampled = jnp.take_along_axis(
            order, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
        return jnp.where(temperatures <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(temperatures > 0.0), nucleus, greedy_only)


class SlotEngine(ServedModel):
    """The compiled half of continuous batching: one paged decode step
    over the whole slot pool, one B=1 paged prefill per bucket, state
    donated and chained device-to-device. ``compiles`` (inherited) is
    the census the zero-recompile contract reads: after `warmup`,
    admissions, decode steps, and completions never compile."""

    # whether this engine's step commits a block of positions: what a model
    # that generates by blocks (``block_length`` > 1) asks of its engine
    serves_blocks = False

    def __init__(self, model, mesh, config: PagedServeConfig, params,
                 batch_stats: Any = None, rules=None):
        if not isinstance(config, PagedServeConfig):
            raise ValueError(
                "SlotEngine needs a PagedServeConfig (page_size/kv_dtype "
                "knobs) — plain ServeConfig drives the forward engine")
        block = int(getattr(model, "block_length", 1))
        if block > 1 and not self.serves_blocks:
            raise ValueError(
                f"{type(self).__name__} emits a token a step and this model "
                f"generates by blocks of {block}: "
                "serving.block_diffusion.BlockDiffusionEngine serves it "
                "(serving.build.build_slot_engine picks it; ROADMAP R18)")
        super().__init__(model, mesh, config, params,
                         batch_stats=batch_stats, rules=rules)
        if not hasattr(model, "init_cache"):
            raise ValueError("continuous batching decodes causal LMs only")
        if self.padded_len > model.max_position:
            raise ValueError(
                f"pages_per_slot * page_size = {self.padded_len} exceeds "
                f"the model's max_position {model.max_position} — the "
                "gathered dense view must fit the position table")
        self._rep = replicated(mesh)
        # Slot rows shard over the mesh's batch shards whenever they
        # divide — each device then decodes rows/n_shards slots instead of
        # redundantly decoding ALL of them (replicated state means every
        # device repeats the whole forward; on the 8-way CPU mesh that was
        # an 8x per-step compute tax). The page POOL stays replicated —
        # pages are slot-agnostic (prefix sharing crosses slots), so the
        # decode step reads it locally and the written rows all-gather
        # back (tiny: one (L, rows, H, D) per k/v per token).
        n_shards = batch_shard_count(mesh)
        self._row_sharded = n_shards > 1 and config.rows % n_shards == 0
        # The int8 page codec's kernel choice, settled here where the mesh
        # is known: the engine's programs are GSPMD programs, and GSPMD
        # cannot partition a Mosaic kernel (a lowering error on any
        # multi-device TPU program), so on a mesh of more than one device
        # "auto" means the XLA-composed codec — same grid, same page bytes.
        # An explicit True is kept and fails loudly at lowering there.
        self._fused_quantize = (
            False if config.fused_quantize is None and mesh.size > 1
            else config.fused_quantize)
        self.reset_state()

    def _row_sharding(self, ndim: int):
        """Sharding for a (rows, ...) slot-state array: leading dim over
        the batch shards when rows divide, replicated otherwise."""
        if self._row_sharded:
            return batch_sharding(self.mesh, ndim)
        return self._rep

    # -- state --------------------------------------------------------------

    @property
    def padded_len(self) -> int:
        """Width of the gathered dense view (pages_per_slot * page_size,
        >= bucket + max_new). The extra tail positions hold scratch/stale
        FINITE values the decode mask zeroes exactly — same argument as
        dense bucket padding."""
        cfg: PagedServeConfig = self.config
        return cfg.pages_per_slot * cfg.page_size

    def _init_control(self) -> Dict[str, jnp.ndarray]:
        cfg: PagedServeConfig = self.config
        rows, vocab = cfg.rows, self.model.padded_vocab
        return {
            # token occupying `positions` (written by the NEXT decode step)
            "tok": jnp.zeros((rows,), jnp.int32),
            "positions": jnp.zeros((rows,), jnp.int32),
            # tokens still to emit; budget > 0 IS slot liveness
            "budget": jnp.zeros((rows,), jnp.int32),
            "emitted": jnp.zeros((rows,), jnp.int32),
            # per-request sampling state, threaded like per-step RNG keys
            "keys": jnp.zeros((rows, 2), jnp.uint32),
            "temps": jnp.zeros((rows,), jnp.float32),
            "top_ps": jnp.ones((rows,), jnp.float32),
            # per-slot output accumulators, fetched ONCE at completion
            "out_buf": jnp.zeros((rows, cfg.max_new_tokens), jnp.int32),
            "last_buf": jnp.zeros((rows, vocab), jnp.float32),
            # prefix-skip support: the position whose decode logits should
            # be captured into last_buf (-1 = already captured — the
            # prefill path writes last_buf itself; a skip-admitted slot
            # never ran a prefill, so its first decode step captures the
            # last-prompt logits here: bitwise the prefill's on the
            # reference read in fp32 on the CPU mesh (the decode-vs-full
            # parity pin), within rounding in bf16 on a TPU, either read
            # (PARITY.md has the chip's measurement))
            "last_pos": jnp.full((rows,), -1, jnp.int32),
            # what the model's layers count a decode step (``step_counters``
            # of a model that routes: `models.moe.HeldExpertsMoe`), summed
            # over the decode steps since the last reset, and those steps'
            # count last: kept on the device and fetched apart
            # (`fetch_step_counters`), never inside the loop
            **({_COUNTERS: jnp.zeros((len(self._counter_names) + 1,),
                                     jnp.float32)}
               if self._counter_names else {}),
        }

    @property
    def _counter_names(self) -> Tuple[str, ...]:
        return tuple(getattr(self.model, "step_counters", ()))

    def _control_sharding(self, name: str, ndim: int):
        """Slot state shards by rows; the model's counters are no slot's."""
        return self._rep if name == _COUNTERS else self._row_sharding(ndim)

    def reset_state(self) -> None:
        """(Re)build the device state: zeroed paged pool (page 0 scratch —
        all-finite by construction), idle control rows, all-scratch page
        table. Compiled executables survive a reset (the census does not
        restart)."""
        cfg: PagedServeConfig = self.config
        pool = self.model.init_paged_pool(
            cfg.total_pages, cfg.page_size,
            quantized=cfg.kv_dtype == "int8")
        self._pool = jax.device_put(pool, self._rep)
        self._control = {
            k: jax.device_put(v, self._control_sharding(k, v.ndim))
            for k, v in self._init_control().items()}
        self._page_table = np.zeros(
            (cfg.rows, cfg.pages_per_slot), np.int32)
        self._table_dev = jax.device_put(self._page_table,
                                         self._row_sharding(2))

    def set_page_row(self, slot: int, row: np.ndarray) -> None:
        """Point one slot's table row at its leased pages (all-zeros =
        scratch = released). Host numpy is the source of truth; the device
        copy refreshes here — NEVER inside the decode loop."""
        self._page_table[slot] = row
        self._table_dev = jax.device_put(self._page_table,
                                         self._row_sharding(2))

    # -- compiled programs ---------------------------------------------------

    def _rep_aval(self, shape, dtype) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._rep)

    def _row_aval(self, shape, dtype) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=self._row_sharding(len(shape)))

    def _pool_avals(self):
        return jax.tree_util.tree_map(
            lambda x: self._rep_aval(x.shape, x.dtype), self._pool)

    def _control_avals(self):
        return {k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=self._control_sharding(k, v.ndim))
                for k, v in self._control.items()}

    def _make_paged_prefill(self, bucket: int) -> Callable:
        cfg: PagedServeConfig = self.config

        def prefill(served, pool, control, page_table, ids, length, slot,
                    want, key, temp, top_p):
            params = self._dequant(served)
            cache0 = self.model.init_cache(1, bucket)
            logits, cache = self.model.apply(
                self._apply_vars(params), ids, train=False, cache=cache0)
            # eval-forward-bitwise logits; token #0 comes from the last
            # REAL prompt position and occupies absolute position `length`
            last = jnp.take(logits[0], jnp.maximum(length - 1, 0), axis=0)
            k0 = jax.random.fold_in(key, length)
            t0 = sample_tokens(last[None, :], k0[None, :], temp[None],
                               top_p[None])[0]
            page_row = page_table[slot]
            # stack each leaf of the per-block prompt rows (k and v of a
            # K/V cache) to (L, S, ...): the pool is layer-stacked, so the
            # whole prompt lands in ONE scatter a leaf
            seqs = [jnp.stack([leaf[0] for leaf in leaves])
                    for leaves in zip(*cache)]
            new_pool = scatter_paged_prefill(pool, page_row, *seqs, length,
                                             fused=self._fused_quantize)
            out_row = jnp.zeros((cfg.max_new_tokens,), jnp.int32)
            out_row = out_row.at[0].set(t0)
            control = dict(control)
            control["tok"] = control["tok"].at[slot].set(t0)
            control["positions"] = control["positions"].at[slot].set(length)
            control["budget"] = control["budget"].at[slot].set(want - 1)
            control["emitted"] = control["emitted"].at[slot].set(1)
            control["keys"] = control["keys"].at[slot].set(key)
            control["temps"] = control["temps"].at[slot].set(temp)
            control["top_ps"] = control["top_ps"].at[slot].set(top_p)
            control["out_buf"] = control["out_buf"].at[slot].set(out_row)
            control["last_buf"] = control["last_buf"].at[slot].set(last)
            control["last_pos"] = control["last_pos"].at[slot].set(-1)
            return new_pool, control

        return prefill

    @property
    def kv_path(self) -> str:
        """How the decode step reads the pool: ``"kernel"`` —
        `ops.paged_attention`, page by page, in place — where everything
        it needs is visible when the step is traced: an unquantized pool
        (the kernel reads no codes and scales), a one-device mesh (GSPMD
        cannot partition a Mosaic kernel, the rule `_fused_quantize`
        follows), a TPU backend and pages that are whole tiles; else
        ``"gather"``, the reference read (`gather_paged_kv` +
        `decode_dot_product_attention`), which every window program
        (resume, speculative verify and draft) takes whatever this says.
        No option selects it. The ``compile`` span of ``paged_decode``
        carries it."""
        cfg: PagedServeConfig = self.config
        kernel = (cfg.kv_dtype != "int8" and self.mesh.size == 1
                  and paged_attention_backend_supported()
                  and self.model.paged_read_supports(cfg.page_size))
        return "kernel" if kernel else "gather"

    @property
    def expert_path(self) -> Optional[str]:
        """How the decode step's expert layers multiply their grouped
        products, as the layer's own module answers for the step's positions
        (`HeldExpertsMoe.expert_path`): ``"kernel"``, `ops.grouped_product`,
        or ``"xla"``, `lax.ragged_dot`; None for a model without an expert
        layer. Static a program, so the ``compile`` span of ``paged_decode``
        carries it beside `kv_path`."""
        ask = getattr(self.model, "expert_path", None)
        window = int(getattr(self.model, "block_length", 1))
        return ask(self.config.rows * window) if ask else None

    def _make_paged_decode(self) -> Callable:
        """The decode step, its parts under `jax.named_scope`s (`kv_gather`,
        `model`, `kv_scatter`, `sample`, `bookkeeping`): trace-time metadata
        by which the benchmark's `batch_decode_*_ms` read a profiler trace
        (`PAGED_DECODE` in benchmark/layer_metrics/_regions.py). On the
        kernel read `kv_gather` lies inside `model`, around each block's
        `paged_attention` call. One step on a v5e, GPT-2 124M in bf16, 64
        rows of up to 1024 positions: 197.7 ms of device time on the gather
        read with the pool at rest as (L, pages, page, H, D) (ledger, PR
        24), 37.8 ms on the kernel read with every row through the nucleus
        (ledger, PR 30: the sampler 34.9, the twelve kernel calls 1.6), and
        2.4 ms when no live row samples, the sampler 0.012 of it (my chip
        run, PR 31; PERF.md section 5)."""
        rows = self.config.rows
        fused = self._fused_quantize
        kernel = self.kv_path == "kernel"
        counted = self._counter_names
        if counted:   # the fold of what the layers sowed, as a train step's
            from ..training.tasks import step_counters

        def decode(served, pool, control, page_table):
            params = self._dequant(served)
            active = control["budget"] > 0
            positions = control["positions"]
            tok = control["tok"]
            if kernel:
                # read half, in place: each block's attention reads the
                # slot's pages straight from the pool (positions below the
                # row's own; a dead row reads none) and takes the fresh
                # row as an input. The pool is read-only inside the model.
                cache = PagedRead(pool=pool, page_table=page_table,
                                  live=jnp.where(active, positions, 0))
            else:
                # read half, the reference: every slot's pages -> the
                # dense view the bitwise-pinned decode attention consumes
                # unchanged, per-layer slices of one gather
                with jax.named_scope("kv_gather"):
                    views = gather_paged_kv(pool, page_table,
                                            dtype=self.model.dtype)
                    cache = tuple(tuple(view[l] for view in views)
                                  for l in range(self.model.depth))
            with jax.named_scope("model"):
                out = self.model.apply(
                    self._apply_vars(params), tok[:, None], train=False,
                    cache=cache, cache_positions=positions,
                    **({"mutable": ["counters"]} if counted else {}))
                (logits, new_cache), sown = out if counted else (out, None)
            # write half: ONE fresh row per live slot per layer, stacked
            # over the layers -> ONE in-place scatter a leaf back to the pool
            with jax.named_scope("kv_scatter"):
                if not kernel:
                    # the views come back whole: each row's own position
                    new_cache = [
                        tuple(jnp.take_along_axis(
                            view, positions.reshape(
                                (-1,) + (1,) * (view.ndim - 1)), axis=1)[:, 0]
                              for view in layer) for layer in new_cache]
                new_pool = scatter_paged_rows(
                    pool, page_table, positions,
                    *(jnp.stack(leaves) for leaves in zip(*new_cache)),
                    active, fused=fused)
            # the token at position p+1, from THIS request's key stream
            step_keys = jax.vmap(jax.random.fold_in)(
                control["keys"], positions + 1)
            # a finished slot keeps its temperature until it is admitted
            # again and its token is dropped below: it must not choose
            # the sampler's branch
            nxt = sample_tokens(logits[:, 0], step_keys,
                                jnp.where(active, control["temps"], 0.0),
                                control["top_ps"])
            with jax.named_scope("bookkeeping"):
                act = active.astype(jnp.int32)
                safe_row = jnp.where(active, jnp.arange(rows), rows)
                out_buf = control["out_buf"].at[
                    safe_row, control["emitted"]].set(nxt, mode="drop")
                # a skip-admitted slot's first step captures the last-prompt
                # logits the prefill would have stored (see `last_pos` in
                # `_init_control`); -1 for everyone else
                cap = positions == control["last_pos"]
                new_control = dict(control)
                new_control["tok"] = jnp.where(active, nxt, tok)
                new_control["positions"] = positions + act
                new_control["budget"] = control["budget"] - act
                new_control["emitted"] = control["emitted"] + act
                new_control["out_buf"] = out_buf
                new_control["last_buf"] = jnp.where(
                    cap[:, None], logits[:, 0], control["last_buf"])
                new_control["last_pos"] = jnp.where(
                    cap, -1, control["last_pos"])
                if counted:
                    got = step_counters(sown.get("counters", {}))
                    new_control[_COUNTERS] = control[_COUNTERS] + jnp.stack(
                        [got[name] for name in counted]
                        + [jnp.ones((), jnp.float32)])
            return new_pool, new_control

        return decode

    def _rep_out(self, tree):
        return jax.tree_util.tree_map(lambda _: self._rep, tree)

    def _out_shardings(self, tree):
        """Each output keeps its aval's own sharding (pool replicated,
        control row-sharded) — donation requires in/out layouts to
        match."""
        return jax.tree_util.tree_map(lambda x: x.sharding, tree)

    def lower_paged_prefill(self, bucket: int):
        """The lowered B=1 admission step — slot/length/knobs traced, pool
        + control DONATED (exposed for the serving_paged contract)."""
        cfg: PagedServeConfig = self.config
        pool_avals = self._pool_avals()
        ctrl_avals = self._control_avals()
        scalar_i = self._rep_aval((), jnp.int32)
        scalar_f = self._rep_aval((), jnp.float32)
        outs = (pool_avals, ctrl_avals)
        return jax.jit(
            self._make_paged_prefill(bucket), donate_argnums=(1, 2),
            out_shardings=self._out_shardings(outs),
        ).lower(self._served, pool_avals, ctrl_avals,
                self._row_aval((cfg.rows, cfg.pages_per_slot), jnp.int32),
                self._rep_aval((1, bucket), jnp.int32),
                scalar_i, scalar_i, scalar_i,
                self._rep_aval((2,), jnp.uint32), scalar_f, scalar_f)

    def lower_paged_decode(self):
        """The lowered shared decode step: advances every live slot one
        token. Pool + control are DONATED — in-place page updates are what
        the page-table-donation HLO rule pins."""
        cfg: PagedServeConfig = self.config
        pool_avals = self._pool_avals()
        ctrl_avals = self._control_avals()
        outs = (pool_avals, ctrl_avals)
        return jax.jit(
            self._make_paged_decode(), donate_argnums=(1, 2),
            out_shardings=self._out_shardings(outs),
        ).lower(self._served, pool_avals, ctrl_avals,
                self._row_aval((cfg.rows, cfg.pages_per_slot), jnp.int32))

    # -- prefix-resident admission (ISSUE 19) --------------------------------

    @property
    def prefix_skip_enabled(self) -> bool:
        """Whether admission may skip/shorten prefill for resident
        prefixes. fp32 pools only: an int8 skip would read dequantized
        pages where the cold prefill reads fresh fp32 — residency would
        change the emitted stream and break the router's same-seed-retry
        determinism (PARITY.md documents the exclusion). The kernel read
        of the decode step keeps it on: on the v5e in bf16 a skip-admitted
        request's token #0 was the cold one's for 8 prompts of 8 on either
        read, and on neither are the kept logits the prefill's bits (my
        chip run, PR 25; PARITY.md)."""
        cfg: PagedServeConfig = self.config
        return (cfg.prefix_sharing and cfg.prefix_skip
                and cfg.kv_dtype == "fp32" and self._windows_supported)

    @property
    def _windows_supported(self) -> bool:
        """The skip and resume programs (and the speculative engine's
        windows) read and write K/V views: a pool of another row format
        (`layers.PagedLatent`) has the prefill and the S=1 decode step
        only, and asking for one of the others raises (ROADMAP R5)."""
        return isinstance(self._pool, PagedKV)

    def _require_windows(self, program: str) -> None:
        if not self._windows_supported:
            raise ValueError(
                f"{program} is K/V-only: this model's pool is a "
                f"{type(self._pool).__name__} (ROADMAP R5)")

    def _make_paged_skip(self) -> Callable:
        cfg: PagedServeConfig = self.config

        def skip(control, slot, last_tok, length, want, key, temp, top_p):
            # Fully resident prompt: no forward at all. The slot enters
            # the shared decode step at position length-1 holding the last
            # prompt token; that step rewrites the resident row with its
            # own bytes (idempotent — the prefix-sharing safety argument),
            # samples token #0 with fold_in(key, length) exactly like the
            # prefill path, and captures the last-prompt logits via
            # last_pos. budget = want (nothing emitted yet), vs the
            # prefill path's want - 1.
            control = dict(control)
            control["tok"] = control["tok"].at[slot].set(last_tok)
            control["positions"] = control["positions"].at[slot].set(
                length - 1)
            control["budget"] = control["budget"].at[slot].set(want)
            control["emitted"] = control["emitted"].at[slot].set(0)
            control["keys"] = control["keys"].at[slot].set(key)
            control["temps"] = control["temps"].at[slot].set(temp)
            control["top_ps"] = control["top_ps"].at[slot].set(top_p)
            control["out_buf"] = control["out_buf"].at[slot].set(
                jnp.zeros((cfg.max_new_tokens,), jnp.int32))
            control["last_pos"] = control["last_pos"].at[slot].set(
                length - 1)
            return control

        return skip

    def _make_paged_resume(self, bucket: int) -> Callable:
        """Tail-only prefill for a PARTIALLY resident prompt: feed just
        the uncovered suffix through the verify-window decode mode at
        offset ``start`` — each tail row attends the resident pages plus
        the in-window causal prefix, so its logits (and written k/v) are
        bitwise the full prefill's rows (the window parity pin)."""
        cfg: PagedServeConfig = self.config
        fused = self._fused_quantize

        def resume(served, pool, control, page_table, ids, start, length,
                   slot, want, key, temp, top_p):
            params = self._dequant(served)
            row_tbl = jax.lax.dynamic_slice_in_dim(page_table, slot, 1, 0)
            k_all, v_all = gather_paged_kv(pool, row_tbl,
                                           dtype=self.model.dtype)
            cache = tuple((k_all[l], v_all[l])
                          for l in range(self.model.depth))
            logits, new_cache = self.model.apply(
                self._apply_vars(params), ids, train=False, cache=cache,
                cache_positions=start[None])
            tail = length - start
            last = jnp.take(logits[0], jnp.maximum(tail - 1, 0), axis=0)
            k0 = jax.random.fold_in(key, length)
            t0 = sample_tokens(last[None, :], k0[None, :], temp[None],
                               top_p[None])[0]
            # commit the tail k/v rows at positions [start, length)
            win_pos = (start + jnp.arange(bucket))[None, :]     # (1, S)
            idxc = jnp.clip(win_pos[0], 0, self.padded_len - 1)
            k_wins = jnp.stack([jnp.take_along_axis(
                c[0], idxc[None, :, None, None], axis=1) for c in new_cache
            ])                                        # (L, 1, S, H, D)
            v_wins = jnp.stack([jnp.take_along_axis(
                c[1], idxc[None, :, None, None], axis=1) for c in new_cache
            ])
            act = (win_pos < length) & (win_pos < self.padded_len)
            new_pool = scatter_paged_window(pool, row_tbl, win_pos, k_wins,
                                            v_wins, act, fused=fused)
            out_row = jnp.zeros((cfg.max_new_tokens,), jnp.int32)
            out_row = out_row.at[0].set(t0)
            control = dict(control)
            control["tok"] = control["tok"].at[slot].set(t0)
            control["positions"] = control["positions"].at[slot].set(length)
            control["budget"] = control["budget"].at[slot].set(want - 1)
            control["emitted"] = control["emitted"].at[slot].set(1)
            control["keys"] = control["keys"].at[slot].set(key)
            control["temps"] = control["temps"].at[slot].set(temp)
            control["top_ps"] = control["top_ps"].at[slot].set(top_p)
            control["out_buf"] = control["out_buf"].at[slot].set(out_row)
            control["last_buf"] = control["last_buf"].at[slot].set(last)
            control["last_pos"] = control["last_pos"].at[slot].set(-1)
            return new_pool, control

        return resume

    def lower_paged_skip(self):
        """The lowered control-only skip admission — every knob traced,
        control DONATED (no pool, no forward: the zero-dispatch path)."""
        self._require_windows("paged_skip")
        ctrl_avals = self._control_avals()
        scalar_i = self._rep_aval((), jnp.int32)
        scalar_f = self._rep_aval((), jnp.float32)
        return jax.jit(
            self._make_paged_skip(), donate_argnums=(0,),
            out_shardings=self._out_shardings(ctrl_avals),
        ).lower(ctrl_avals, scalar_i, scalar_i, scalar_i, scalar_i,
                self._rep_aval((2,), jnp.uint32), scalar_f, scalar_f)

    def lower_paged_resume(self, bucket: int):
        """The lowered tail-only prefill (partial residency) — pool +
        control DONATED like the full prefill's."""
        self._require_windows("paged_resume")
        cfg: PagedServeConfig = self.config
        pool_avals = self._pool_avals()
        ctrl_avals = self._control_avals()
        scalar_i = self._rep_aval((), jnp.int32)
        scalar_f = self._rep_aval((), jnp.float32)
        outs = (pool_avals, ctrl_avals)
        return jax.jit(
            self._make_paged_resume(bucket), donate_argnums=(1, 2),
            out_shardings=self._out_shardings(outs),
        ).lower(self._served, pool_avals, ctrl_avals,
                self._row_aval((cfg.rows, cfg.pages_per_slot), jnp.int32),
                self._rep_aval((1, bucket), jnp.int32),
                scalar_i, scalar_i, scalar_i, scalar_i,
                self._rep_aval((2,), jnp.uint32), scalar_f, scalar_f)

    def _executable(self, kind: str, bucket: int):
        key = (kind, bucket)
        if key not in self._compiled:
            lowered = {
                "paged_prefill": lambda: self.lower_paged_prefill(bucket),
                "paged_decode": self.lower_paged_decode,
                "paged_skip": self.lower_paged_skip,
                "paged_resume": lambda: self.lower_paged_resume(bucket),
            }[kind]()
            path = {"kv_path": self.kv_path,
                    "cache": type(self._pool).__name__} \
                if kind == "paged_decode" else {}
            if path and self.expert_path:
                path["expert_path"] = self.expert_path
            self._compile(kind, bucket, lowered, **path)
        return self._compiled[key]

    def warmup(self) -> int:
        """Compile the decode step + every bucket's prefill (and, when
        prefix skip is live, the skip + per-bucket tail-resume programs)
        up front; the census is flat from here (the zero-recompile
        acceptance)."""
        self._executable("paged_decode", 0)
        for b in self.config.buckets:
            self._executable("paged_prefill", b)
        if self.prefix_skip_enabled:
            self._executable("paged_skip", 0)
            for b in self.config.buckets:
                self._executable("paged_resume", b)
        return self.compiles

    # -- the three runtime entries (scheduler-facing) ------------------------

    def admit(self, slot: int, tokens: np.ndarray, want: int,
              temperature: float, top_p: float, seed: int) -> int:
        """Dispatch the slot's admission prefill (token #0 is emitted
        inside) and return the bucket served. Does NOT fence: the prefill
        rides the donated pool/control chain and the scheduler's per-step
        fence bounds it — fencing every admission would serialize the
        whole admission wave behind host-device round trips (measured
        ~25% of capacity at saturation)."""
        cfg: PagedServeConfig = self.config
        bucket = bucket_for(len(tokens), cfg.buckets)
        ids = np.full((1, bucket), cfg.pad_id, np.int32)
        ids[0, :len(tokens)] = tokens
        key = np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)
        dev = lambda x: jax.device_put(x, self._rep)  # noqa: E731
        pre = self._executable("paged_prefill", bucket)
        self._pool, self._control = pre(
            self._served, self._pool, self._control, self._table_dev,
            dev(ids), dev(np.int32(len(tokens))), dev(np.int32(slot)),
            dev(np.int32(want)), dev(key),
            dev(np.float32(temperature)), dev(np.float32(top_p)))
        return bucket

    def admit_skip(self, slot: int, last_tok: int, length: int, want: int,
                   temperature: float, top_p: float, seed: int) -> None:
        """Admit a FULLY prefix-resident request with no forward at all:
        one control-only program arms the slot to enter the shared decode
        step at the resumed position (see `_make_paged_skip` — token #0
        and the last-prompt logits come out of that step: bitwise the
        prefill path's in fp32 on the CPU mesh, PARITY.md for a TPU)."""
        key = np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)
        dev = lambda x: jax.device_put(x, self._rep)  # noqa: E731
        exe = self._executable("paged_skip", 0)
        self._control = exe(
            self._control, dev(np.int32(slot)), dev(np.int32(last_tok)),
            dev(np.int32(length)), dev(np.int32(want)), dev(key),
            dev(np.float32(temperature)), dev(np.float32(top_p)))

    def admit_resume(self, slot: int, tokens: np.ndarray, start: int,
                     want: int, temperature: float, top_p: float,
                     seed: int) -> int:
        """Admit a PARTIALLY resident request: prefill only the uncovered
        tail ``tokens[start:]`` through the tail bucket's resume program
        (verify-window forward at offset ``start`` over the resident
        pages). Returns the tail bucket served."""
        cfg: PagedServeConfig = self.config
        tail = tokens[start:]
        bucket = bucket_for(len(tail), cfg.buckets)
        ids = np.full((1, bucket), cfg.pad_id, np.int32)
        ids[0, :len(tail)] = tail
        key = np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)
        dev = lambda x: jax.device_put(x, self._rep)  # noqa: E731
        exe = self._executable("paged_resume", bucket)
        self._pool, self._control = exe(
            self._served, self._pool, self._control, self._table_dev,
            dev(ids), dev(np.int32(start)), dev(np.int32(len(tokens))),
            dev(np.int32(slot)), dev(np.int32(want)), dev(key),
            dev(np.float32(temperature)), dev(np.float32(top_p)))
        return bucket

    def decode_step(self) -> None:
        """One compiled decode step over the whole slot pool — every
        chained value stays on device (no fetch; the scheduler's
        ``_step_decode_loop`` is the AST-pinned caller)."""
        dec = self._executable("paged_decode", 0)
        self._pool, self._control = dec(
            self._served, self._pool, self._control, self._table_dev)

    def fetch_slot(self, slot: int) -> Tuple[np.ndarray, np.ndarray]:
        """ONE host fetch of a finished slot's outputs (tokens row +
        last-prompt logits) — completion-time only, never in the loop."""
        return jax.device_get((self._control["out_buf"][slot],
                               self._control["last_buf"][slot]))

    # -- byte accounting -----------------------------------------------------

    def paged_bytes(self) -> int:
        """At-rest bytes of the live paged pool (codes + scales when
        int8); compare `dense_baseline_bytes` for the dense fp32
        baseline the >= 3x cut is measured against."""
        return paged_kv_bytes(self._pool)

    def dense_baseline_bytes(self) -> int:
        """What a dense cache would hold at this config, fp32: the
        model's own (`init_cache`) for every row at full length."""
        cfg: PagedServeConfig = self.config
        cache = jax.eval_shape(lambda: self.model.init_cache(
            cfg.rows, max(cfg.buckets) + cfg.max_new_tokens))
        return 4 * sum(int(leaf.size)
                       for leaf in jax.tree_util.tree_leaves(cache))

    def fetch_step_counters(self) -> Dict[str, float]:
        """ONE host fetch of the model's step counters (`_init_control`):
        each name's sum over the decode steps since the last reset, and
        those steps' count under ``"steps"``. Empty for a model that counts
        nothing. Never called from the decode loop."""
        if not self._counter_names:
            return {}
        got = np.asarray(jax.device_get(self._control[_COUNTERS]))
        return dict(zip((*self._counter_names, "steps"), map(float, got)))


@dataclasses.dataclass
class _SlotState:
    """Host mirror of one live slot: enough to detect completion without
    touching the device (the device's budget arithmetic is replayed in
    Python ints, one decrement per decode step)."""

    req: Request
    lease: PageLease
    bucket: int
    want: int
    left: int  # tokens still to emit (device budget mirror)


class ContinuousScheduler:
    """The host loop: queue -> slots -> compiled steps -> results.

    Single-threaded over the engine (the device programs chain donated
    state, so there is exactly one legal caller at a time); thread-safety
    toward producers lives in `RequestQueue`. `run` is the worker-loop
    analogue of batching.serve_forever — stop means DRAIN (admitted and
    queued work completes; new work is refused), `kill` is the chaos hook
    (fail everything in flight, the router resubmits elsewhere)."""

    # tokens an admission's prefill emits (the ``tokens`` of ``sched_fence``)
    prefill_emits = 1

    def __init__(self, engine: SlotEngine, queue: RequestQueue):
        cfg: PagedServeConfig = engine.config
        self.engine = engine
        self.queue = queue
        self.pool = PagePool(cfg.total_pages, cfg.page_size,
                             cfg.pages_per_slot,
                             prefix_sharing=cfg.prefix_sharing)
        self.free_slots: List[int] = list(range(cfg.rows))  # guarded-by: _lock
        self.running: Dict[int, _SlotState] = {}            # guarded-by: _lock
        self.pending: List[Request] = []                    # guarded-by: _lock
        self._t_popped: Dict[int, float] = {}               # guarded-by: _lock
        self.served = 0                                     # guarded-by: _lock
        self.killed = False                                 # guarded-by: _lock
        # serializes step() against kill(): kill runs on the CALLER's
        # thread (InProcessReplica.kill) while the worker is mid-step,
        # and without the lock it races the running/pending iteration
        # (dict changed size) and can double-resolve a request that is
        # completing at the instant of death
        self._lock = named_lock("ContinuousScheduler._lock")
        # max decode steps per fence when nothing is waiting to join
        # (see step()); 1 restores strict fence-per-token behavior
        self.burst_steps = 4
        # prefix-resident admission census (ISSUE 19): how many
        # admissions skipped prefill entirely vs prefilled only a tail
        self.prefill_skips = 0                              # guarded-by: _lock
        self.tail_resumes = 0                               # guarded-by: _lock
        # serial number of the scheduling iteration: what the spans of one
        # `step` share (``iter``), the five phases and their children
        self.iteration = 0                                  # guarded-by: _lock
        # what `_advance` adds to its iteration's ``sched_fence`` span
        self._fence_extra: Dict[str, int] = {}              # guarded-by: _lock

    # -- admission -----------------------------------------------------------

    def _gauges(self) -> None:   # lock-held: _lock
        cfg: PagedServeConfig = self.engine.config
        telemetry.gauge("serving_slot_occupancy",
                        len(self.running) / max(cfg.rows, 1))
        telemetry.gauge("serving_page_pool_free", self.pool.free_pages())
        # the share of the page table that is leased: what the kernel read
        # of the decode step touches (the gather read touches all of it)
        telemetry.gauge(
            "serving_kv_live_page_share",
            sum(st.lease.n_pages for st in self.running.values())
            / max(cfg.rows * cfg.pages_per_slot, 1))
        # the router's load signal: everything accepted but unfinished
        # (HttpReplica.queue_depth scrapes this off /metrics)
        telemetry.gauge("serving_queue_depth",
                        len(self.queue) + len(self.pending)
                        + len(self.running))

    def _try_admit(self, req: Request) -> bool:   # lock-held: _lock
        """One admission attempt: needs a free slot AND a page lease.
        False means 'not now' (the request stays pending) — admission
        pressure is absorbed here, never by a recompile.

        With prefix skip live (fp32 pools, `prefix_skip_enabled`), the
        lease's shared-page count decides the prefill's fate: covered >=
        len(prompt) - 1 positions resident -> NO prefill dispatch at all
        (the slot enters decode at the resumed position; the at-most-one
        uncovered position is the one the first decode step writes
        anyway); partially covered -> a tail-only prefill over just the
        fresh pages. Cold prompts take the classic full prefill."""
        if not self.free_slots:
            return False
        cfg: PagedServeConfig = self.engine.config
        want = cfg.max_new_tokens if req.max_new_tokens is None else \
            min(int(req.max_new_tokens), cfg.max_new_tokens)
        want = max(want, 1)
        slot = self.free_slots[-1]
        who = dict(iter=self.iteration, slot=slot, request=req.id)
        t0 = time.perf_counter()
        lease = self.pool.alloc(req.tokens, len(req.tokens) + want)
        telemetry.span_event("page_alloc", time.perf_counter() - t0,
                             ok=lease is not None, **who)
        if lease is None:
            return False
        if not self._draft_admit(req, lease, want):
            # rollback, NOT release: the lease's fresh pages were
            # hash-registered at alloc time but never prefilled — a
            # plain release would park them as "resident" and a retry
            # of the same prompt would skip-admit onto garbage KV
            self.pool.rollback(lease)
            return False
        self.free_slots.pop()
        t0 = time.perf_counter()
        self.engine.set_page_row(slot, lease.pages)
        telemetry.span_event("page_table_put", time.perf_counter() - t0,
                             at="admit", **who)
        n = len(req.tokens)
        covered = len(lease.shared) * cfg.page_size
        t0 = time.perf_counter()
        skip_ok = getattr(self.engine, "prefix_skip_enabled", False)
        if skip_ok and covered >= n - 1 and covered > 0:
            self.engine.admit_skip(slot, int(req.tokens[-1]), n, want,
                                   req.temperature, req.top_p, req.seed)
            bucket = bucket_for(n, cfg.buckets)
            left = want   # nothing emitted yet: decode emits all `want`
            self.prefill_skips += 1
            telemetry.span_event("prefill_skip", time.perf_counter() - t0,
                                 resident=covered, **who)
        elif skip_ok and covered > 0:
            bucket = self.engine.admit_resume(
                slot, req.tokens, covered, want, req.temperature,
                req.top_p, req.seed)
            left = want - 1
            self.tail_resumes += 1
            telemetry.span_event("prefill", time.perf_counter() - t0,
                                 bucket=bucket, resumed=covered, **who)
        else:
            bucket, left = self._admit_cold(slot, req, want)
            telemetry.span_event("prefill", time.perf_counter() - t0,
                                 bucket=bucket, **who)
        now = time.perf_counter()
        # t_first_token stays None until the NEXT step fence — admission
        # only dispatched device work; step() stamps it once the fence
        # proves token #0 landed. The spans above are the dispatch cost.
        telemetry.span_event(
            "slot_wait", now - self._t_popped.pop(req.id, now),
            request=req.id, slot=slot)
        self.running[slot] = _SlotState(req=req, lease=lease, bucket=bucket,
                                        want=want, left=left)
        self._post_admit(slot, req)
        self._gauges()
        return True

    def _admit_cold(self, slot: int, req: Request,
                    want: int) -> Tuple[int, int]:   # lock-held: _lock
        """Dispatch the whole prompt's prefill; (the bucket served, the
        tokens the slot still has to emit once it has run). The plain
        prefill emits token #0 itself; a block-diffusion engine's emits
        none (serving/block_diffusion.py)."""
        bucket = self.engine.admit(slot, req.tokens, want, req.temperature,
                                   req.top_p, req.seed)
        return bucket, want - 1

    def _first_token_landed(self, st: _SlotState) -> bool:  # lock-held: _lock
        """Whether the fence just passed proves the slot's first token: the
        plain prefill emits it, so any fence after admission does."""
        return True

    def _result_extras(self, st: _SlotState, more) -> dict:
        """Fields of a `Result` beyond the tokens and the kept logits, from
        what else the engine's `fetch_slot` fetched (nothing, here)."""
        return {}

    def _draft_admit(self, req: Request, lease: PageLease,
                     want: int) -> bool:   # lock-held: _lock
        """Speculative hook: lease + prefill the DRAFT pool for this
        request before the target admission commits (False aborts the
        attempt — the target lease is rolled back). The plain scheduler
        has no draft."""
        return True

    def _post_admit(self, slot: int, req: Request) -> None:  # lock-held: _lock
        """Speculative hook: called once the target admission landed in
        ``running`` (the draft engine points its page row here)."""

    def _post_complete(self, slot: int) -> None:   # lock-held: _lock
        """Speculative hook: a slot finished — release its draft lease."""

    def _admit_pending(self) -> int:   # lock-held: _lock
        """Try every pending request once; how many were admitted."""
        still: List[Request] = []
        for req in self.pending:
            if not self._try_admit(req):
                still.append(req)
        admitted = len(self.pending) - len(still)
        self.pending = still
        return admitted

    def _pull(self, timeout: float = 0.005) -> int:   # lock-held: _lock
        """Take requests off the queue onto ``pending``; how many."""
        # keep at most ~2 pool-fulls on deck; never block while slots are
        # actively decoding (the queue wait is for the idle loop only)
        cap = 2 * self.engine.config.rows - len(self.pending)
        if cap <= 0:
            return 0
        got = self.queue.take(cap,
                              timeout=0.0 if self.running else timeout)
        now = time.perf_counter()
        for req in got:
            self._t_popped[req.id] = now
        self.pending.extend(got)
        return len(got)

    # -- the decode hot loop -------------------------------------------------

    def _step_decode_loop(self, n_steps: int) -> None:   # lock-held: _lock
        """``n_steps`` compiled decode steps, mirrors replayed in Python —
        NO host fetch in here (the ``no-host-sync-in-decode`` lint pins
        this function by name). Completion fetches happen afterwards, in
        `_complete`."""
        for _ in range(n_steps):
            self.engine.decode_step()
            for st in self.running.values():
                if st.left > 0:
                    st.left -= 1

    def _advance(self) -> Tuple[int, int, int]:   # lock-held: _lock
        """Advance every live slot: the plain scheduler runs 1..burst
        compiled decode steps (one token each); the speculative scheduler
        (serving/speculative.py) overrides this with one draft-propose +
        verify round (up to K+1 tokens per fence). Either way the caller
        fences afterwards and completes finished slots. Returns (steps
        dispatched, slots that went in with budget left, tokens they
        emit): what the iteration's `sched_dispatch` and `sched_fence`
        spans say."""
        # a slot admitted with a budget of one has emitted it in its
        # prefill and only waits for the fence: no step decrements it
        # (counted for the spans alone, so only while someone records)
        live = sum(st.left > 0 for st in self.running.values()) \
            if telemetry.is_configured() else 0
        steps = 1
        if not self.pending and not len(self.queue):
            steps = max(1, min(min(st.left for st in
                                   self.running.values()),
                               self.burst_steps))
        self._step_decode_loop(steps)
        # how often the sampler's argmax branch is the one the device
        # takes: every slot in `running` is live through the whole burst
        # (steps <= its `left`), so the host's own mirror decides it
        telemetry.counter("serving_decode_steps", steps)
        # positions the steps' reads covered: a slot that has emitted e of
        # its tokens stands at len(prompt) + e - 1 and reads that many
        # cached rows, one more each step of the burst
        telemetry.counter("serving_live_cache_tokens", sum(
            steps * (len(st.req.tokens) + st.want - 1 - st.left - steps)
            + steps * (steps - 1) // 2 for st in self.running.values()))
        if all(st.req.temperature <= 0.0 for st in self.running.values()):
            telemetry.counter("serving_decode_steps_all_greedy", steps)
        return steps, live, live * steps

    def _complete_finished(self) -> int:   # lock-held: _lock
        """Fetch, release and resolve every slot whose budget is spent;
        how many."""
        t0 = time.perf_counter()
        done = [slot for slot, st in self.running.items() if st.left == 0]
        for slot in done:
            st = self.running.pop(slot)
            who = dict(iter=self.iteration, slot=slot, request=st.req.id)
            t_fetch = time.perf_counter()
            toks, last, *more = self.engine.fetch_slot(slot)
            now = time.perf_counter()
            telemetry.span_event("slot_fetch", now - t_fetch, **who)
            first = st.req.t_first_token or t0
            res = Result(tokens=np.asarray(toks[:st.want], np.int32),
                         last_logits=np.asarray(last),
                         bucket=st.bucket,
                         queue_wait_s=max(0.0, first - st.req.t_submit),
                         decode_s=max(0.0, now - first),
                         **self._result_extras(st, more))
            self.pool.release(st.lease)
            t_put = time.perf_counter()
            self.engine.set_page_row(
                slot, np.zeros(self.engine.config.pages_per_slot, np.int32))
            telemetry.span_event("page_table_put",
                                 time.perf_counter() - t_put,
                                 at="complete", **who)
            self._post_complete(slot)
            self.free_slots.append(slot)
            st.req.set_result(res)
            self.served += 1
        if done:
            self._gauges()
        return len(done)

    # -- lifecycle -----------------------------------------------------------

    def step(self) -> bool:
        """One scheduling iteration: pull, admit, decode one token for
        every live slot, complete. Returns whether any work remains in
        flight or pending.

        The fence bounds dispatch depth: Python dispatches faster than
        the device decodes, and without it the queued-step backlog grows
        without bound — every completion fetch then waits behind the
        WHOLE backlog (the donated pool chain serializes), and
        per-request latency balloons with uptime. It must fence on the
        step's own OUTPUT: any earlier buffer was already donated into
        this dispatch and cannot be blocked on. It is a device fence, not
        a host transfer — the per-token no-host-sync contract
        (`_step_decode_loop`) is untouched.

        When NOTHING is waiting to join (queue and pending both empty),
        the loop bursts up to `burst_steps` decode steps before fencing —
        no slot can finish earlier than its remaining budget, so the
        burst never delays a completion, and a request arriving mid-burst
        waits at most `burst_steps` tokens for admission (the
        token-granularity bound, traded explicitly for fewer host-device
        round trips on long decodes).

        The whole iteration runs under the scheduler lock: `kill` (the
        caller-thread chaos hook) waits for the step boundary, so it can
        never mutate running/pending mid-iteration or error a request
        this step is concurrently completing."""
        with self._lock:
            if self.killed:
                return False
            self.iteration += 1
            marks = [time.perf_counter()]
            took = self._pull()
            marks.append(time.perf_counter())
            skips = self.prefill_skips
            admitted = self._admit_pending()
            marks.append(time.perf_counter())
            work = None
            if self.running:
                steps, live, tokens = self._advance()
                marks.append(time.perf_counter())
                jax.block_until_ready(self.engine._control["tok"])
                # the fence proves every dispatched prefill's token #0
                # landed: the honest (if slightly late) TTFT stamp
                now = time.perf_counter()
                for st in self.running.values():
                    if st.req.t_first_token is None \
                            and self._first_token_landed(st):
                        st.req.t_first_token = now
                marks.append(time.perf_counter())
                completed = self._complete_finished()
                marks.append(time.perf_counter())
                # an admission's prefill emits its token #0, and this
                # fence is where it lands; a skip admission's first token
                # is one of the steps' own
                first = (admitted - (self.prefill_skips - skips)) \
                    * self.prefill_emits
                work = (steps, live, tokens + first, completed)
            if telemetry.is_configured():
                self._emit_phases(marks, took, admitted, work)
            return bool(self.running or self.pending)

    def _emit_phases(self, marks: List[float], took: int, admitted: int,
                     work: Optional[Tuple[int, int, int, int]]
                     ) -> None:   # lock-held: _lock
        """The iteration as spans that tile it: ``marks`` are its phase
        boundaries on `time.perf_counter` (three for an iteration with
        nothing running, six for one that advanced, whose ``work`` is
        (steps, live, tokens, completed)), moved onto the wall clock by ONE
        offset, so that each span starts where the one before it ends. An
        idle poll (nothing taken, nothing waiting, nothing running) emits
        nothing: an idle server's stream stays silent."""
        if work is None and not took and not self.pending:
            return
        wall = time.time() - time.perf_counter()
        it = self.iteration
        telemetry.span_event("sched_pull", marks[1] - marks[0],
                             wall + marks[0], iter=it, took=took)
        telemetry.span_event("sched_admit", marks[2] - marks[1],
                             wall + marks[1], iter=it, admitted=admitted,
                             pending=len(self.pending))
        if work is None:
            return
        steps, live, tokens, completed = work
        telemetry.span_event("sched_dispatch", marks[3] - marks[2],
                             wall + marks[2], iter=it, steps=steps,
                             live=live)
        telemetry.span_event("sched_fence", marks[4] - marks[3],
                             wall + marks[3], iter=it, steps=steps,
                             live=live, tokens=tokens, **self._fence_extra)
        telemetry.span_event("sched_complete", marks[5] - marks[4],
                             wall + marks[4], iter=it, completed=completed)

    def run(self, stop: threading.Event, log=None) -> int:
        """Serve until ``stop`` is set AND everything accepted has
        completed (stop = drain, the SIGTERM contract). Returns requests
        served."""
        # unlocked reads of killed/running/pending/served below are the
        # worker's OWN loop control + post-mortem logging: killed is a
        # monotonic flag step() re-checks under the lock before touching
        # anything, and after kill() the collections are already cleared
        while not self.killed:  # analysis: disable=guarded-by
            if stop.is_set():
                self.queue.close()
            busy = self.step()
            if stop.is_set() and not busy and not len(self.queue):
                break
        if self.killed and log is not None:  # analysis: disable=guarded-by
            log("serving: scheduler killed with "
                f"{len(self.running) + len(self.pending)} in flight")  # analysis: disable=guarded-by
        return self.served  # analysis: disable=guarded-by

    def drain(self, log=None) -> int:
        """Finish everything queued + in flight, then return — wrapped in
        the ``drain`` span like the forward engine's `batching.drain`."""
        stop = threading.Event()
        stop.set()
        # span attrs are a racy diagnostic snapshot, deliberately taken
        # without stalling the worker's step for it
        with telemetry.span("drain",
                            pending=len(self.queue) + len(self.pending),  # analysis: disable=guarded-by
                            running=len(self.running)):  # analysis: disable=guarded-by
            return self.run(stop, log=log)

    def kill(self, err: Optional[BaseException] = None) -> List[Request]:
        """Chaos hook: fail every in-flight, pending, AND still-queued
        request (the injected replica death). Returns the failed requests
        — the router resubmits them to surviving replicas.

        Runs under the scheduler lock, so the death lands at a step
        boundary: requests the in-flight step already completed are out
        of `running` (resolved exactly once, as results), everything
        else fails here exactly once."""
        with self._lock:
            self.killed = True
            err = err or RuntimeError("replica died")
            failed: List[Request] = []
            for st in self.running.values():
                st.req.set_error(err)
                failed.append(st.req)
            for req in self.pending:
                req.set_error(err)
                failed.append(req)
            # accepted-but-unpulled requests die with the replica too:
            # left parked in the closed queue they would hang their
            # waiters forever (no worker remains to pull them)
            self.queue.close()
            for req in self.queue.take(len(self.queue) + 1, timeout=0.0):
                req.set_error(err)
                failed.append(req)
            self.running.clear()
            self.pending.clear()
            return failed


# the scheduler that drives an engine's step: each engine class names its
# own (`SpeculativeEngine`, `BlockDiffusionEngine`), and whoever starts a
# worker loop over an engine asks the engine (`InProcessReplica`, the CLI)
SlotEngine.scheduler_cls = ContinuousScheduler


def serve_continuous(engine: SlotEngine, queue: RequestQueue,
                     stop: threading.Event, log=None) -> int:
    """The token server's worker loop, with the signature of the forward
    engine's ``batching.serve_forever`` (`serving smoke` runs either)."""
    return engine.scheduler_cls(engine, queue).run(stop, log=log)

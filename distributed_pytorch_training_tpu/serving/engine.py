"""The inference engine: manifest-verified checkpoints -> served tokens.

This is the serving half of the training stack, built from parts that
already exist rather than a parallel implementation:

* **Weights** come through ``training/checkpoint.py::restore_latest`` —
  the SAME manifest-verified restore training resumes from, against a
  template built by ``Trainer.init_state`` (so replicated, zero1, and
  fsdp-flat checkpoint layouts all load; fsdp-flat unflattens through the
  trainer's own template). The engine records which label it serves and
  its manifest ``tree_digest`` — served bytes are provenanced.
* **Shapes** come from the bucket ladder (``data/pack.py``): one compiled
  program per (rows, bucket) pair, assembled once and reused for every
  request — the zero-recompiles-within-a-bucket contract the engine's
  ``compiles`` counter lets tests pin (the compile-count census).
* **Numerics** are the eval forward's. fp32 serving is BITWISE the eval
  forward: prefill logits are literally the same computation (the cache
  fill is a side output), and the KV-cache decode step is pinned
  bitwise-equal to the full-context forward on the CPU mesh
  (models/layers.py ``decode_dot_product_attention`` explains the one
  formulation choice that makes this true). int8 serving reuses the
  gradient-wire codec grid (per-row max-abs scales, ``max(amax,1e-30)/127``,
  round/clip — ``parallel/grad_sync.py``) on the weights, dequantized at
  the matmul inputs inside the compiled forward (XLA fuses the scale
  multiply into the consumer): at-rest weight bytes drop ~4x, and the
  error model is the wire codec's one-shot bound (PARITY.md).

The decode hot loop (``generate``) is host-dispatch only: every per-step
value (next token, positions) chains device-to-device through the compiled
step, the KV cache is DONATED (``donate_argnums``) so each step updates in
place, and the single host fetch happens after the last step. The
``no-host-sync-in-decode`` AST rule and the ``serving_decode`` HLO contract
(analysis/) keep it that way.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..data.pack import bucket_for, pack_token_rows, unpack_token_rows
from ..parallel.mesh import batch_shard_count
from ..parallel.sharding import batch_sharding, replicated, shard_batch
from .batching import Result

SERVE_DTYPES = ("fp32", "bf16", "int8")


@dataclasses.dataclass
class ServeConfig:
    """Engine knobs (CLI-facing; serving/__main__.py mirrors them)."""

    # Prompt-length bucket ladder (sorted ascending). One compiled
    # prefill+decode pair exists per rung; a request pays padding at most
    # to the next rung and NEVER a compile.
    buckets: Tuple[int, ...] = (32, 64, 128)
    # Batch rows per engine cycle — the static row dimension of every
    # compiled program. Must divide by the mesh's batch-shard count.
    rows: int = 8
    # Greedy-decode budget per request; the KV cache is sized
    # bucket + max_new_tokens.
    max_new_tokens: int = 16
    # fp32: bitwise the eval forward. bf16: the model's compute dtype
    # (build the model with dtype=bf16 — the --amp convention). int8:
    # weights quantized at rest through the wire-codec grid, dequantized
    # at the matmul inputs in-kernel.
    serve_dtype: str = "fp32"
    pad_id: int = 0
    # int8: only quantize leaves with >= this many elements (tiny tensors
    # — biases, layernorms — are all error and no memory win).
    quantize_min_elements: int = 4096

    def __post_init__(self):
        if self.serve_dtype not in SERVE_DTYPES:
            raise ValueError(f"serve_dtype {self.serve_dtype!r} is not one "
                             f"of {SERVE_DTYPES}")
        if not self.buckets:
            raise ValueError("at least one bucket is required")
        self.buckets = tuple(sorted(int(b) for b in self.buckets))
        if self.rows < 1:
            raise ValueError(f"rows must be >= 1, got {self.rows}")


@flax.struct.dataclass
class QuantizedLeaf:
    """An int8-at-rest parameter leaf: s8 codes in the original shape plus
    one fp32 scale per trailing-axis row (the wire codec's per-row grid,
    ``grad_sync._quantize_int8_rows``). Dequantizes as ``q * scale`` —
    a multiply XLA fuses into the consuming matmul/gather."""

    q: jnp.ndarray
    scale: jnp.ndarray


def quantize_params(params: Any, min_elements: int = 4096,
                    fused: Optional[bool] = None) -> Any:
    """int8-quantize the weight tree for serving: every leaf with ndim >= 2
    and >= ``min_elements`` elements becomes a `QuantizedLeaf` (per-row
    scales over the trailing axis, leading axes collapsed — embeddings get
    one scale per vocab row, kernels one per input row); everything else
    (biases, layernorm scales, tiny tensors) stays exact fp32. The grid is
    the gradient-wire codec's, by construction: same absmax, same
    ``max(amax, 1e-30) * (1/127)`` scale, same round/clip — so the serve
    error model IS the wire codec's one-shot bound (PARITY.md)."""
    from ..parallel.grad_sync import _quantize_int8_rows

    def one(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim < 2 or leaf.size < min_elements:
            return leaf
        rows = leaf.astype(jnp.float32).reshape(-1, leaf.shape[-1])
        q, scales = _quantize_int8_rows(rows, fused=fused)
        return QuantizedLeaf(
            q=q.reshape(leaf.shape),
            scale=scales.reshape(leaf.shape[:-1]))

    return jax.tree_util.tree_map(one, params)


def dequantize_params(served: Any, like_dtype=jnp.float32) -> Any:
    """Inverse of `quantize_params`, traced inside the compiled forwards:
    codes x per-row scales, cast to the parameter dtype. Exact-fp32 leaves
    pass through untouched."""

    def one(leaf):
        if isinstance(leaf, QuantizedLeaf):
            return (leaf.q.astype(jnp.float32)
                    * leaf.scale[..., None]).astype(like_dtype)
        return leaf

    return jax.tree_util.tree_map(
        one, served, is_leaf=lambda x: isinstance(x, QuantizedLeaf))


def int8_weight_bytes(served: Any) -> Dict[str, int]:
    """At-rest byte accounting of a served tree: {quantized, exact} bytes —
    the serving analogue of grad_sync's wire accounting."""
    quantized = exact = 0
    for leaf in jax.tree_util.tree_leaves(
            served, is_leaf=lambda x: isinstance(x, QuantizedLeaf)):
        if isinstance(leaf, QuantizedLeaf):
            quantized += leaf.q.size + 4 * leaf.scale.size
        else:
            exact += leaf.size * leaf.dtype.itemsize
    return {"quantized_bytes": int(quantized), "exact_bytes": int(exact)}


class InferenceEngine:
    """Compiled batched inference over one (model, mesh, config) triple.

    ``serve_tokens`` is the request-facing entry (the batching layer calls
    it); ``lower_prefill``/``lower_decode`` expose the lowered steps to the
    analysis contract checker; ``compiles`` counts every XLA compile the
    engine ever triggered — the census the zero-recompile contract reads.
    """

    # whether this engine's step commits a block of positions: what a model
    # that generates by blocks (``block_length`` > 1) asks of its engine
    serves_blocks = False

    def __init__(self, model, mesh, config: ServeConfig, params,
                 batch_stats: Any = None, rules=None):
        from ..parallel.mesh import MODEL

        block = int(getattr(model, "block_length", 1))
        if block > 1 and not self.serves_blocks:
            raise ValueError(
                f"{type(self).__name__} emits a token a step and this model "
                f"generates by blocks of {block}: "
                "serving.block_diffusion.BlockDiffusionEngine serves it "
                "(serving.build.build_slot_engine picks it; ROADMAP R18)")
        self.model = model
        self.mesh = mesh
        self.config = config
        n_shards = batch_shard_count(mesh)
        model_n = dict(mesh.shape).get(MODEL, 1)
        if model_n > 1 and rules is None:
            raise ValueError(
                f"mesh has model={model_n} but the engine was given no "
                "partition rules — serving shards weights over the model "
                "axis via the model's GSPMD rules (tp_fsdp_rules); pass "
                "rules= (serving.build.build_serving_engine does)")
        if model_n > 1 and config.serve_dtype == "int8":
            raise ValueError(
                "--serve-dtype int8 on a model-axis mesh is not supported "
                "yet: the per-row quantized codes carry their own layout "
                "(serve fp32/bf16 with --mesh model>1, or int8 on a 1-D "
                "mesh)")
        self._validate_rows(n_shards)
        # three serve modes: causal LM (prefill + KV-cache decode), token
        # batch (bert — one bucketed forward, logits/embeddings out), image
        # batch (resnet/vit — fixed-shape forward via serve_images)
        self.is_lm = hasattr(model, "init_cache")
        self.is_token = hasattr(model, "vocab_size")
        top = max(config.buckets) + config.max_new_tokens
        if self.is_lm and top > model.max_position:
            raise ValueError(
                f"largest bucket + max_new_tokens = {top} exceeds the "
                f"model's max_position {model.max_position}")
        self._batch_stats = batch_stats if batch_stats is not None else {}
        rep = replicated(mesh)
        if config.serve_dtype == "int8":
            served = quantize_params(
                params, min_elements=config.quantize_min_elements)
        else:
            served = jax.tree_util.tree_map(jnp.asarray, params)
        if model_n > 1:
            # multi-chip serving of big models (ISSUE 13 satellite): the
            # served weights shard per the model's GSPMD rules — XLA
            # inserts the TP collectives into the compiled forwards;
            # per-device weight residency divides by the model axis
            from ..parallel.sharding import shard_pytree

            self._served = shard_pytree(served, mesh, rules)
        else:
            self._served = jax.device_put(served, rep)
        if jax.tree_util.tree_leaves(self._batch_stats):
            self._batch_stats = jax.device_put(self._batch_stats, rep)
        self._param_dtype = jnp.result_type(
            jax.tree_util.tree_leaves(params)[0])
        # compiled executables, keyed ("prefill"|"decode"|"forward", bucket)
        self._compiled: Dict[Tuple[str, int], Any] = {}
        self.compiles = 0
        # provenance of the served weights (from_checkpoint fills this)
        self.checkpoint_info: Optional[dict] = None

    def _validate_rows(self, n_shards: int) -> None:
        """Dense engine: the row dimension shards over the mesh's batch
        shards, so rows must divide. The slot engine overrides (its state
        is replicated — slot count is a scheduling knob, not a layout)."""
        if self.config.rows % n_shards:
            raise ValueError(
                f"rows={self.config.rows} must divide over the mesh's "
                f"{n_shards} batch shards — every compiled program's row "
                "dimension is sharded over them")

    # -- checkpoint loading -------------------------------------------------

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, model, mesh,
                        config: ServeConfig, tx, sample_input,
                        train_config=None, rules=None,
                        task=None) -> "InferenceEngine":
        """Restore the newest manifest-verified checkpoint and build an
        engine serving it. ``tx`` and ``train_config`` reconstruct the
        checkpoint's TrainState TEMPLATE (the restore contract: orbax needs
        the full structure — same optimizer family and the same
        zero1/fsdp/wire mode flags the training run used; the CLI exposes
        them). Torn checkpoints are skipped exactly as a training resume
        would skip them; serving a checkpoint nobody could resume from is
        the same bug twice."""
        from ..training import TrainConfig, Trainer
        from ..training.checkpoint import CheckpointManager
        from ..training.tasks import LanguageModelingTask

        train_config = train_config or TrainConfig(seed=0)
        trainer = Trainer(task or LanguageModelingTask(), mesh, train_config,
                          rules=rules)
        template = trainer.init_state(model, sample_input, tx,
                                      jax.random.PRNGKey(0))
        ckpt = CheckpointManager(ckpt_dir)
        try:
            try:
                restored = ckpt.restore_latest(template)
            except (ValueError, TypeError) as e:
                # orbax's structure-mismatch errors dump the whole tree;
                # name the actual knob before the dump scrolls it away
                raise ValueError(
                    "checkpoint restore failed against the serving "
                    "template — the template's TrainState structure must "
                    "match the training run's exactly: same optimizer "
                    "chain (--optimizer/--momentum/--weight-decay; "
                    "train.py's default is sgd) and the same "
                    "--zero1/--fsdp-explicit/--wire-dtype/--bucket-cap-mb "
                    f"flags. Original error: {type(e).__name__}: {e}"
                ) from e
            if restored is None:
                raise FileNotFoundError(
                    f"no restorable checkpoint under {ckpt_dir} "
                    f"(skipped as torn: {ckpt.last_skipped or 'none'})")
            state, _epoch, _step_in_epoch = restored
            label = ckpt.last_restored
            manifest = ckpt.manifest(label) if label is not None else None
            params = (trainer._fsdp_unflatten(state.params)
                      if trainer._fsdp else state.params)
            engine = cls(model, mesh, config, params,
                         batch_stats=state.batch_stats, rules=rules)
            engine.checkpoint_info = {
                "dir": str(ckpt_dir),
                "label": label,
                "step": int(jax.device_get(state.step)),
                "tree_digest": (manifest or {}).get("tree_digest"),
                "verified": manifest is not None,
            }
            return engine
        finally:
            ckpt.close()

    # -- compiled programs --------------------------------------------------

    def _apply_vars(self, params) -> dict:
        variables = {"params": params}
        if jax.tree_util.tree_leaves(self._batch_stats):
            variables["batch_stats"] = self._batch_stats
        return variables

    def _dequant(self, served):
        return dequantize_params(served, like_dtype=self._param_dtype)

    def _make_prefill(self, bucket: int) -> Callable:
        rows, cache_len = self.config.rows, bucket + self.config.max_new_tokens

        def prefill(served, ids, lengths):
            params = self._dequant(served)
            cache0 = self.model.init_cache(rows, cache_len)
            logits, cache = self.model.apply(
                self._apply_vars(params), ids, train=False, cache=cache0)
            # greedy first token from the last REAL prompt position; filler
            # rows (length 0) read row 0 — their outputs are never unpacked
            last_pos = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(
                logits, last_pos[:, None, None], axis=1)[:, 0]
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return logits, last, cache, tok, lengths.astype(jnp.int32)

        return prefill

    def _make_decode(self, bucket: int) -> Callable:
        def decode(served, cache, tok, positions):
            params = self._dequant(served)
            logits, new_cache = self.model.apply(
                self._apply_vars(params), tok[:, None], train=False,
                cache=cache, cache_positions=positions)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return new_cache, nxt, positions + 1

        return decode

    def _make_forward(self, bucket: int) -> Callable:
        def forward(served, ids, lengths):
            params = self._dequant(served)
            logits = self.model.apply(
                self._apply_vars(params), ids, train=False)
            last_pos = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(
                logits, last_pos[:, None, None], axis=1)[:, 0]
            return logits, last

        return forward

    def _aval(self, shape, dtype) -> jax.ShapeDtypeStruct:
        """Input aval with the batch sharding over the leading (row) dim —
        AOT compilation binds shardings, and the call sites always pass
        `shard_batch`-placed arrays."""
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=batch_sharding(self.mesh, len(shape)))

    def _cache_avals(self, bucket: int):
        """The model's own dense cache (`init_cache`: k and v a block for
        GPT-2, a latent and a rotary key for latent attention) as avals."""
        cache = jax.eval_shape(lambda: self.model.init_cache(
            self.config.rows, bucket + self.config.max_new_tokens))
        return jax.tree_util.tree_map(
            lambda z: self._aval(z.shape, z.dtype), cache)

    def _out_batch_shardings(self, tree_like):
        """Pin every output's sharding to batch-over-rows so the prefill
        outputs land exactly in the layout the decode step was compiled
        for (AOT executables reject sharding mismatches at call time)."""
        return jax.tree_util.tree_map(
            lambda x: batch_sharding(self.mesh, len(x.shape)), tree_like)

    def lower_prefill(self, bucket: int):
        """The lowered (uncompiled) prefill step — the contract checker's
        read, and the AOT source `_executable` compiles."""
        rows = self.config.rows
        ids = self._aval((rows, bucket), jnp.int32)
        lengths = self._aval((rows,), jnp.int32)
        vocab = self.model.padded_vocab
        outs = (self._aval((rows, bucket, vocab), jnp.float32),   # logits
                self._aval((rows, vocab), jnp.float32),           # last
                self._cache_avals(bucket),                        # cache
                self._aval((rows,), jnp.int32),                   # tok
                self._aval((rows,), jnp.int32))                   # positions
        return jax.jit(
            self._make_prefill(bucket),
            out_shardings=self._out_batch_shardings(outs),
        ).lower(self._served, ids, lengths)

    def lower_decode(self, bucket: int):
        """The lowered decode step. The cache argument is DONATED: the step
        updates the (rows, bucket + max_new, heads, head_dim) k/v buffers
        in place — without donation every decode step would copy the whole
        cache (the `serving_decode` HLO contract pins the alias table)."""
        if not self.is_lm:
            raise ValueError("decode exists only for causal-LM models")
        rows = self.config.rows
        cache = self._cache_avals(bucket)
        tok = self._aval((rows,), jnp.int32)
        pos = self._aval((rows,), jnp.int32)
        outs = (cache, tok, pos)
        return jax.jit(
            self._make_decode(bucket), donate_argnums=(1,),
            out_shardings=self._out_batch_shardings(outs),
        ).lower(self._served, cache, tok, pos)

    def _executable(self, kind: str, bucket: int):
        key = (kind, bucket)
        if key not in self._compiled:
            if kind == "prefill":
                lowered = self.lower_prefill(bucket)
            elif kind == "decode":
                lowered = self.lower_decode(bucket)
            else:
                rows = self.config.rows
                vocab = self.model.padded_vocab
                outs = (self._aval((rows, bucket, vocab), jnp.float32),
                        self._aval((rows, vocab), jnp.float32))
                lowered = jax.jit(
                    self._make_forward(bucket),
                    out_shardings=self._out_batch_shardings(outs),
                ).lower(self._served,
                        self._aval((rows, bucket), jnp.int32),
                        self._aval((rows,), jnp.int32))
            # the cold-vs-warm instrument: with the persistent compile
            # cache on (DPT_COMPILE_CACHE / enable_persistent_compile_
            # cache), a restarted/autoscaled engine's spans collapse from
            # full-compile to cache-load time — the restart-downtime win,
            # measurable per program in the stream
            # attr named `program`, not `kind`: the recorder's emit() owns
            # the `kind` parameter (event kind), attrs must not shadow it
            with telemetry.span("compile", program=kind, bucket=bucket):
                self._compiled[key] = lowered.compile()
            self.compiles += 1
        return self._compiled[key]

    def warmup(self) -> int:
        """Compile every bucket's programs up front (the bench does this
        before the timed window); returns the engine's compile count.
        Image models compile lazily in `serve_images` (their one shape is
        the image's, not a bucket's)."""
        if self.is_token:
            for b in self.config.buckets:
                self._executable("prefill" if self.is_lm else "forward", b)
                if self.is_lm:
                    self._executable("decode", b)
        return self.compiles

    def kv_cache_bytes(self, bucket: Optional[int] = None) -> int:
        """At-rest bytes of this engine's dense KV cache at ``bucket``
        (default: the top rung — the engine's HBM ceiling). The baseline
        the paged engine's >= 3x int8 cut is measured against
        (`SlotEngine.dense_baseline_bytes`; bench serving records both)."""
        if not self.is_lm:
            return 0
        b = max(self.config.buckets) if bucket is None else int(bucket)
        return sum(int(z.size) * z.dtype.itemsize
                   for z in jax.tree_util.tree_leaves(self._cache_avals(b)))

    # -- serving ------------------------------------------------------------

    def serve_tokens(self, seqs: Sequence[np.ndarray],
                     max_new_tokens: Optional[int] = None,
                     return_prompt_logits: bool = False) -> List[Result]:
        """Serve one ragged group of token prompts: bucket, pack, prefill,
        greedy-decode, unpack. All prompts must fit ONE bucket (the
        batching layer groups by bucket before calling)."""
        if not seqs:
            return []
        if not self.is_token:
            raise ValueError(
                "serve_tokens needs a token model (gpt2/bert); image "
                "models serve through serve_images")
        cfg = self.config
        bucket = max(bucket_for(len(s), cfg.buckets) for s in seqs)
        ids, lengths, _w = pack_token_rows(seqs, bucket, cfg.rows,
                                           pad_id=cfg.pad_id)
        batch_ids = shard_batch(ids, self.mesh)
        batch_len = shard_batch(lengths, self.mesh)

        if not self.is_lm:
            t0 = time.perf_counter()
            fwd = self._executable("forward", bucket)
            logits, last = fwd(self._served, batch_ids, batch_len)
            # the (rows, bucket, vocab) per-position logits cross to the
            # host only when asked for — the default embedding serve
            # fetches just the (rows, vocab) last-position rows
            fetched = jax.device_get((last, logits) if return_prompt_logits
                                     else (last,))
            last_h = fetched[0]
            prefill_s = time.perf_counter() - t0
            telemetry.span_event("prefill", prefill_s, bucket=bucket,
                                 rows=len(seqs))
            per_req = (unpack_token_rows(fetched[1], lengths, len(seqs))
                       if return_prompt_logits else [None] * len(seqs))
            return [Result(tokens=np.zeros((0,), np.int32),
                           last_logits=last_h[i],
                           prompt_logits=per_req[i],
                           bucket=bucket, prefill_s=prefill_s)
                    for i in range(len(seqs))]

        new_tokens = (cfg.max_new_tokens if max_new_tokens is None
                      else min(int(max_new_tokens), cfg.max_new_tokens))
        t0 = time.perf_counter()
        pre = self._executable("prefill", bucket)
        logits, last, cache, tok, positions = pre(self._served, batch_ids,
                                                  batch_len)
        prefill_s = time.perf_counter() - t0
        telemetry.span_event("prefill", prefill_s, bucket=bucket,
                             rows=len(seqs))
        t0 = time.perf_counter()
        toks, cache = self.generate(bucket, cache, tok, positions,
                                    new_tokens)
        # ONE host fetch for the whole batch, after the last decode step
        fetch = [toks, last]
        if return_prompt_logits:
            fetch.append(logits)
        fetched = jax.device_get(fetch)
        toks_h, last_h = fetched[0], fetched[1]
        decode_s = time.perf_counter() - t0
        telemetry.span_event("decode", decode_s, bucket=bucket,
                             steps=max(new_tokens - 1, 0), rows=len(seqs))
        if return_prompt_logits:
            per_req = unpack_token_rows(fetched[2], lengths, len(seqs))
        else:
            per_req = [None] * len(seqs)
        return [Result(tokens=toks_h[i, :new_tokens],
                       last_logits=np.asarray(last_h[i]),
                       prompt_logits=per_req[i],
                       bucket=bucket, prefill_s=prefill_s,
                       decode_s=decode_s)
                for i in range(len(seqs))]

    def generate(self, bucket: int, cache, tok, positions,
                 new_tokens: int):
        """The decode hot loop: ``new_tokens`` compiled steps, cache donated
        and updated in place, every chained value (token, positions) staying
        on device — NO host fetch inside the loop (the
        ``no-host-sync-in-decode`` lint pins this function). Returns the
        (rows, new_tokens) generated-token matrix (stacked on device) and
        the final cache."""
        dec = self._executable("decode", bucket)
        out = []
        for k in range(new_tokens):
            out.append(tok)
            if k + 1 < new_tokens:  # K tokens need K-1 steps: the first
                cache, tok, positions = dec(  # token comes from prefill
                    self._served, cache, tok, positions)
        stacked = jnp.stack(out, axis=1) if out else \
            jnp.zeros((self.config.rows, 0), jnp.int32)
        return stacked, cache

    def serve_images(self, images: np.ndarray, mean: Sequence[float],
                     std: Sequence[float]) -> np.ndarray:
        """Batched image classification: normalize exactly like the eval
        task (data/augment.normalize_images — fp32 serve logits are the
        eval forward's bitwise) and forward. Returns (n, classes) logits
        for the real rows."""
        from ..data.augment import normalize_images

        cfg = self.config
        n = images.shape[0]
        if n > cfg.rows:
            raise ValueError(f"{n} images exceed rows={cfg.rows}")
        padded = np.zeros((cfg.rows,) + images.shape[1:], images.dtype)
        padded[:n] = images
        # mean/std are closed over the compiled program — they must key
        # the cache too, or a later call with different normalization
        # would silently reuse the first call's constants
        key = ("image", images.shape[1:], tuple(mean), tuple(std))
        if key not in self._compiled:
            def forward(served, imgs):
                params = self._dequant(served)
                x = normalize_images(imgs, mean, std,
                                     dtype=getattr(self.model, "dtype",
                                                   jnp.float32))
                return self.model.apply(self._apply_vars(params), x,
                                        train=False)
            self._compiled[key] = jax.jit(forward).lower(
                self._served, shard_batch(padded, self.mesh)).compile()
            self.compiles += 1
        t0 = time.perf_counter()
        logits = self._compiled[key](self._served,
                                     shard_batch(padded, self.mesh))
        logits = jax.device_get(logits)
        telemetry.span_event("prefill", time.perf_counter() - t0,
                             rows=n, image=True)
        return logits[:n]

"""What every serving engine holds, and the forward engine.

`ServedModel` is the part the engines share, built from parts that already
exist rather than a parallel implementation:

* **Weights** come through ``training/checkpoint.py::restore_latest`` —
  the SAME manifest-verified restore training resumes from, against a
  template built by ``Trainer.init_state`` (so replicated, zero1, and
  fsdp-flat checkpoint layouts all load; fsdp-flat unflattens through the
  trainer's own template). The engine records which label it serves and
  its manifest ``tree_digest`` — served bytes are provenanced.
* **Placement**: replicated on a pure-DP mesh, sharded by the model's GSPMD
  rules on a mesh with a model axis. int8 serving reuses the
  gradient-wire codec grid (per-row max-abs scales, ``max(amax,1e-30)/127``,
  round/clip — ``parallel/grad_sync.py``) on the weights, dequantized at
  the matmul inputs inside the compiled forward (XLA fuses the scale
  multiply into the consumer): at-rest weight bytes drop ~4x, and the
  error model is the wire codec's one-shot bound (PARITY.md).
* **The compile census**: every program an engine runs is compiled once,
  under a ``compile`` span, and counted (``compiles``) — the
  zero-recompiles contract tests pin.

Two engines stand on it. A causal LM is served by the token server
(`serving/continuous.py::SlotEngine` and its scheduler, built by
`serving.build.build_slot_engine`). `InferenceEngine`, here, is the forward
engine for models WITHOUT a cache: one bucketed forward a rung of the
ladder (``data/pack.py``) for a token model (BERT: logits and last-position
rows out, no tokens generated) and ``serve_images`` for ResNet / ViT. fp32
served logits are BITWISE the eval forward's.
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

    # Prompt-length bucket ladder (sorted ascending). One compiled forward
    # (the token engine: one prefill) exists per rung; a request pays
    # padding at most to the next rung and NEVER a compile.
    buckets: Tuple[int, ...] = (32, 64, 128)
    # The static row dimension of every compiled program: batch rows per
    # cycle of the forward engine (must divide by the mesh's batch-shard
    # count), slots of the token engine.
    rows: int = 8
    # Most tokens a request of the token engine may ask for; its cache is
    # sized bucket + max_new_tokens a slot (serving/paged.py).
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


class ServedModel:
    """A model's weights placed on a mesh for serving, and the census of
    the programs compiled over them: what the forward engine
    (`InferenceEngine`) and the token server's engine
    (`serving/continuous.py::SlotEngine`) both stand on. ``compiles``
    counts every XLA compile the engine ever triggered — the census the
    zero-recompile contract reads."""

    def __init__(self, model, mesh, config: ServeConfig, params,
                 batch_stats: Any = None, rules=None):
        from ..parallel.mesh import MODEL

        self.model = model
        self.mesh = mesh
        self.config = config
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
        # compiled executables, keyed (program kind, bucket)
        self._compiled: Dict[Tuple[str, int], Any] = {}
        self.compiles = 0
        # provenance of the served weights (from_checkpoint fills this)
        self.checkpoint_info: Optional[dict] = None

    # -- checkpoint loading -------------------------------------------------

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, model, mesh,
                        config: ServeConfig, tx, sample_input,
                        train_config=None, rules=None,
                        task=None) -> "ServedModel":
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

    def _compile(self, kind: str, bucket: int, lowered, **attrs) -> None:
        """Compile one lowered program, keep it under (kind, bucket) and
        count it. The ``compile`` span is the cold-vs-warm instrument: with
        the persistent compile cache on, a restarted or autoscaled engine's
        spans collapse from full-compile to cache-load time, per program.
        ``attrs`` ride the span; the program's kind is the attr ``program``
        because the recorder's emit() owns ``kind`` (the event's)."""
        with telemetry.span("compile", program=kind, bucket=bucket,
                            **attrs):
            self._compiled[(kind, bucket)] = lowered.compile()
        self.compiles += 1


class InferenceEngine(ServedModel):
    """The forward engine, for models without a cache: one compiled
    bucketed forward a rung for a token model (BERT) behind
    ``serve_tokens`` (the batching layer's `serve_forever` calls it), and
    ``serve_images`` for image models. A causal LM is not served here: the
    token server is its one server."""

    def __init__(self, model, mesh, config: ServeConfig, params,
                 batch_stats: Any = None, rules=None):
        if hasattr(model, "init_cache"):
            raise ValueError(
                f"{type(model).__name__} is a causal LM (it has a cache): "
                "the token server serves it — build its engine with "
                "serving.build.build_slot_engine and drive it with "
                "serving.continuous.serve_continuous or a Router; "
                "InferenceEngine is the forward engine of models without "
                "a cache (BERT, image models)")
        n_shards = batch_shard_count(mesh)
        if config.rows % n_shards:
            raise ValueError(
                f"rows={config.rows} must divide over the mesh's "
                f"{n_shards} batch shards — every compiled program's row "
                "dimension is sharded over them")
        super().__init__(model, mesh, config, params,
                         batch_stats=batch_stats, rules=rules)
        # token batch (bert — one bucketed forward, logits/embeddings out)
        # or image batch (resnet/vit — fixed-shape forward, serve_images)
        self.is_token = hasattr(model, "vocab_size")

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

    def _executable(self, bucket: int):
        """The compiled forward of one rung (outputs pinned
        batch-over-rows like the inputs)."""
        key = ("forward", bucket)
        if key not in self._compiled:
            rows = self.config.rows
            vocab = self.model.padded_vocab
            out_shardings = (batch_sharding(self.mesh, 3),   # logits
                             batch_sharding(self.mesh, 2))   # last
            lowered = jax.jit(
                self._make_forward(bucket), out_shardings=out_shardings,
            ).lower(self._served,
                    self._aval((rows, bucket), jnp.int32),
                    self._aval((rows,), jnp.int32))
            self._compile("forward", bucket, lowered)
        return self._compiled[key]

    def warmup(self) -> int:
        """Compile every bucket's forward up front; returns the engine's
        compile count. Image models compile lazily in `serve_images`
        (their one shape is the image's, not a bucket's)."""
        if self.is_token:
            for b in self.config.buckets:
                self._executable(b)
        return self.compiles

    # -- serving ------------------------------------------------------------

    def serve_tokens(self, seqs: Sequence[np.ndarray],
                     return_prompt_logits: bool = False) -> List[Result]:
        """Serve one ragged group of token sequences: bucket, pack,
        forward, unpack. All of them must fit ONE bucket (the batching
        layer groups by bucket before calling). No tokens are generated:
        a `Result` carries the last-position logits, and the per-position
        logits when asked for."""
        if not seqs:
            return []
        if not self.is_token:
            raise ValueError(
                "serve_tokens needs a token model (bert); image "
                "models serve through serve_images")
        cfg = self.config
        bucket = max(bucket_for(len(s), cfg.buckets) for s in seqs)
        ids, lengths, _w = pack_token_rows(seqs, bucket, cfg.rows,
                                           pad_id=cfg.pad_id)
        batch_ids = shard_batch(ids, self.mesh)
        batch_len = shard_batch(lengths, self.mesh)

        t0 = time.perf_counter()
        fwd = self._executable(bucket)
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

"""GPT-2 355M (medium) — the "GPT-2 355M multi-host v4-32 pod (scaling
experiment)" flagship config (BASELINE.json:12).

HF-equivalent architecture: learned token + position embeddings, 24 pre-LN
blocks (1024 wide, 16 heads, MLP 4096, GELU), final LN, LM head tied to the
token embedding. Parity anchor: HF ``GPT2LMHeadModel(gpt2-medium)`` has
354,823,168 params — checked in tests/test_models.py.

Long-context: the attention implementation is pluggable; pass
``ops.ring_attention.make_ring_attention(mesh)`` to shard the sequence over
the mesh ``seq`` axis (context parallelism, SURVEY.md §5).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.collectives import (
    TpShardedLogits,
    copy_to_tp,
    reduce_from_tp,
)
from ..parallel.sharding import PartitionRules
from .layers import (
    PagedRead,
    TransformerBlock,
    VocabPaddingMixin,
    causal_mask,
    dot_product_attention,
    mask_vocab_padding,
    tp_fsdp_rules,
)
from .registry import register_model


class GPT2LMHead(VocabPaddingMixin, nn.Module):
    vocab_size: int = 50257
    hidden_dim: int = 1024
    depth: int = 24
    num_heads: int = 16
    max_position: int = 1024
    dropout_rate: float = 0.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    layernorm_epsilon: float = 1e-5
    attention_fn: Callable = dot_product_attention
    remat: bool = False  # jax.checkpoint each block: HBM for recompute FLOPs
    # Megatron-style vocab padding for TP (VERDICT r4 weak #4): pad the
    # embedding rows to a multiple so the (vocab, d) table — the largest
    # param — shards over the `model` axis instead of degrading to
    # replication. Padded logit columns are masked to the fp32 min, so the
    # loss is identical to the unpadded head. 0 = exact HF shapes.
    pad_vocab_to_multiple_of: int = 0
    # Explicit tensor parallelism (ISSUE 13): tp_size > 1 runs the
    # megatron column/row-split forward with `tp_axis` bound by the
    # enclosing shard_map (training/loop.py's explicit TP x FSDP step).
    # When the padded vocab divides by tp_size, the (vocab, d) embedding —
    # the largest tensor — is vocab-split too: lookups psum the per-shard
    # partial rows, and the tied head returns its LOCAL logit columns as a
    # `TpShardedLogits` — the task layer computes Megatron's
    # parallel-vocab cross-entropy from two (B, S)-sized model-axis stats
    # instead of gathering the (B, S, vocab) logits. Indivisible vocab
    # degrades the embedding to model-replicated with a warning — the
    # blocks still split.
    tp_size: int = 1
    tp_axis: Optional[str] = None

    @property
    def tp_vocab(self) -> bool:
        """Whether the explicit-TP forward vocab-splits the embedding."""
        return self.tp_size > 1 and self.padded_vocab % self.tp_size == 0

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = False,
                 cache=None, cache_positions=None):
        """Causal LM forward. Three modes, selected by ``cache``:

        * ``cache=None`` (training/eval): the original forward, byte-
          identical HLO to the pre-cache module (the lowering pin in
          tests/test_serving.py) — the cache plumbing contributes ZERO ops
          when off.
        * prefill (``cache`` given, ``cache_positions=None``): the same
          causal forward over the (padded) prompt, additionally returning
          the per-block (k, v) caches filled at slots [0, S). Attention
          runs over the fresh k/v, so prefill logits ARE the eval
          forward's logits bit-for-bit (PARITY.md "Serving shares
          training numerics").
        * decode (``cache`` + ``cache_positions`` (B,) int32): S new
          tokens per row starting at that row's own position — per-row
          cache scatter, per-row position embedding, attention over cache
          slots ``<= position + j`` for window row j. Returns
          (B, S, vocab) logits for the NEXT token at each window offset.
          S == 1 is the classic decode step; S == K+1 is the speculative
          verify window (serving/speculative.py), whose row j is bitwise
          the s=1 step at that position. Rows at different prompt lengths
          decode in one batch with no recompile (the positions are traced
          values).

        With a cache the return value is ``(logits, new_cache)`` where
        ``new_cache`` matches `init_cache`'s structure. One more decode
        form: ``cache`` a `layers.PagedRead` (S == 1 only) reads the paged
        pool in place through `ops.paged_attention` instead of dense views;
        ``new_cache`` is then each block's fresh (k, v) rows, (B, H*D)
        each, for the caller to scatter into the pool.
        """
        b, s = input_ids.shape
        decoding = cache is not None and cache_positions is not None
        tp = self.tp_size
        if tp > 1 and cache is not None:
            raise ValueError(
                "explicit TP has no KV-cache path — serve TP checkpoints "
                "via the GSPMD rules (models/layers.py MultiHeadAttention "
                "documents the restriction)")
        vocab_rows = (self.padded_vocab // tp if self.tp_vocab
                      else self.padded_vocab)
        wte = nn.Embed(vocab_rows, self.hidden_dim, dtype=self.dtype,
                       param_dtype=self.param_dtype,
                       embedding_init=nn.initializers.normal(stddev=0.02),
                       name="wte")
        if self.tp_vocab:
            # vocab-parallel lookup: this shard owns rows
            # [shard * rows, (shard+1) * rows); out-of-range ids contribute
            # exact zeros and the per-shard partials psum to the full
            # embedding row (`reduce_from_tp`: backward is identity, so
            # each shard's table gets exactly its own rows' cotangents)
            shard = jax.lax.axis_index(self.tp_axis)
            local_ids = input_ids - shard * vocab_rows
            valid = (local_ids >= 0) & (local_ids < vocab_rows)
            rows = wte(jnp.clip(local_ids, 0, vocab_rows - 1))
            x = reduce_from_tp(
                jnp.where(valid[..., None], rows, 0.0), self.tp_axis)
        else:
            x = wte(input_ids)
        # Decode position ids: s == 1 is the classic one-token step; s > 1
        # is the speculative verify window — row j sits at absolute
        # position cache_positions + j (clipped into the wpe table: the
        # overflow rows past a slot's page span are write-dropped and
        # never sampled, they only need to stay finite).
        if decoding and s == 1:
            pos_ids = cache_positions[:, None]
        elif decoding:
            pos_ids = jnp.minimum(
                cache_positions[:, None] + jnp.arange(s)[None, :],
                self.max_position - 1)
        else:
            pos_ids = jnp.arange(s)[None, :]
        x = x + nn.Embed(self.max_position, self.hidden_dim, dtype=self.dtype,
                         param_dtype=self.param_dtype,
                         embedding_init=nn.initializers.normal(stddev=0.01),
                         name="wpe")(pos_ids)

        # Kernel attention paths (flash/ring) own the causal structure, so
        # they get ONLY the padding mask (flash applies it inside the
        # blocks; ring/ulysses raise — their adapters need the XLA path).
        # The XLA einsum path takes the combined causal & padding mask.
        # Decode attends over the cache: slot j is visible iff j <= this
        # row's position (later slots are unwritten or prefill pad — both
        # must stay invisible).
        uses_kernel = self.attention_fn is not dot_product_attention
        paged = isinstance(cache, PagedRead)
        if paged:
            # the pool read in place (S == 1): `ops.paged_attention` owns
            # the visibility rule, positions < the row's own from its
            # pages and the fresh row at its own
            if not decoding or s != 1:
                raise ValueError(
                    "a PagedRead cache serves the S=1 decode step only — "
                    "prefill and windows take dense views "
                    "(layers.gather_paged_kv)")
            mask = None
        elif decoding and s == 1:
            t = cache[0][0].shape[1]
            mask = (jnp.arange(t)[None, :]
                    <= cache_positions[:, None])[:, None, None, :]
        elif decoding:
            # verify window: row j of the window attends cache slots
            # <= cache_positions + j — each row's visibility is exactly
            # the s=1 decode step's at that position, so the masked-out
            # later window rows (scattered but not yet committed) weigh
            # exactly 0.0 in its softmax (the bitwise argument).
            t = cache[0][0].shape[1]
            win = cache_positions[:, None] + jnp.arange(s)[None, :]
            mask = (jnp.arange(t)[None, None, :]
                    <= win[:, :, None])[:, None, :, :]
        elif uses_kernel:
            mask = (attention_mask[:, None, None, :].astype(bool)
                    if attention_mask is not None else None)
        else:
            mask = causal_mask(s)
            if attention_mask is not None:
                mask = mask & attention_mask[:, None, None, :].astype(bool)

        new_cache = []
        block_cls = nn.remat(TransformerBlock) if self.remat else TransformerBlock
        for i in range(self.depth):
            block = block_cls(
                num_heads=self.num_heads,
                head_dim=self.hidden_dim // self.num_heads,
                mlp_dim=4 * self.hidden_dim, dtype=self.dtype,
                param_dtype=self.param_dtype,
                dropout_rate=self.dropout_rate,
                layernorm_epsilon=self.layernorm_epsilon,
                attention_fn=self.attention_fn,
                tp_size=tp, tp_axis=self.tp_axis,
                name=f"block{i}",
            )
            if cache is None:
                x = block(x, mask=mask, deterministic=not train)
            else:
                x, c = block(x, mask=mask, deterministic=not train,
                             cache=(cache.replace(layer=i) if paged
                                    else cache[i]),
                             cache_positions=cache_positions)
                new_cache.append(c)

        x = nn.LayerNorm(epsilon=self.layernorm_epsilon, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="ln_f")(x)
        if self.tp_vocab:
            # vocab-parallel tied head, Megatron parallel-vocab CE form:
            # the local logit columns STAY sharded — no vocab-scale
            # model-axis gather; the loss layer psums two (B, S)-sized
            # stats instead (collectives.tp_parallel_cross_entropy).
            # `copy_to_tp` at the matmul input so ln_f and the residual
            # stream see the full summed cotangent. Padded columns are
            # masked per shard (global column = shard * rows + j), so the
            # sharded head is column-for-column the masked gathered one.
            local = wte.attend(copy_to_tp(x, self.tp_axis)).astype(
                jnp.float32)
            cols = (jax.lax.axis_index(self.tp_axis) * vocab_rows
                    + jnp.arange(vocab_rows))
            local = jnp.where(cols < self.vocab_size, local,
                              jnp.finfo(jnp.float32).min)
            return TpShardedLogits(local, self.tp_axis, vocab_rows,
                                   self.vocab_size)
        # tied LM head (HF ties wte <-> lm_head). flax files `attend` under
        # `wte`; `head` around it tells the vocab-wide matmul from the lookup
        with jax.named_scope("head"):
            logits = wte.attend(x)
        logits = mask_vocab_padding(logits.astype(jnp.float32),
                                    self.vocab_size)
        return logits if cache is None else (logits, tuple(new_cache))

    def init_cache(self, batch: int, max_len: int):
        """Zero-filled per-block (k, v) cache: ``depth`` pairs of
        (batch, max_len, heads, head_dim) arrays in the compute dtype.
        ``max_len`` = prompt bucket + max new tokens (serving/engine.py)."""
        z = jnp.zeros((batch, max_len, self.num_heads,
                       self.hidden_dim // self.num_heads), self.dtype)
        return tuple((z, z) for _ in range(self.depth))

    def init_paged_pool(self, n_pages: int, page_size: int,
                        quantized: bool = False):
        """Zero-filled paged KV pool: ONE `layers.PagedKV` stacked over all
        ``depth`` blocks — (depth, n_pages, page_size, heads * head_dim)
        lane-dense pages (int8 codes + per-row fp32 scales when
        ``quantized`` — the wire-codec grid). The paged serving engine
        (serving/continuous.py) either gathers per-slot pages into the
        SAME dense cache shape `init_cache` produces, so the decode forward
        above runs unchanged (the reference read: paging is then a storage
        layout, not a numerics change), or hands the pool itself to the
        S=1 decode step as a `layers.PagedRead` (the kernel read, a TPU's;
        PARITY.md has both exactness models)."""
        from .layers import init_paged_kv

        return init_paged_kv(self.depth, n_pages, page_size,
                             self.num_heads,
                             self.hidden_dim // self.num_heads,
                             dtype=self.dtype, quantized=quantized)

    def paged_read_supports(self, page_size: int) -> bool:
        """Whether the decode step's kernel read (`ops.paged_attention`) can
        take this model's pool: pages of whole tiles."""
        from ..ops.paged_attention import paged_attention_supports

        return paged_attention_supports(page_size, self.hidden_dim,
                                        self.dtype)

    @staticmethod
    def partition_rules() -> PartitionRules:
        return tp_fsdp_rules()


@register_model("gpt2_355m")
def gpt2_355m(**kw) -> GPT2LMHead:
    """GPT-2 medium (355M). Config values are defaults — callers (tests,
    dry-runs) may override any of them."""
    cfg = dict(hidden_dim=1024, depth=24, num_heads=16)
    cfg.update(kw)
    return GPT2LMHead(**cfg)


@register_model("gpt2_124m")
def gpt2_124m(**kw) -> GPT2LMHead:
    """GPT-2 small — CPU-testable sibling of the 355M flagship."""
    cfg = dict(hidden_dim=768, depth=12, num_heads=12)
    cfg.update(kw)
    return GPT2LMHead(**cfg)

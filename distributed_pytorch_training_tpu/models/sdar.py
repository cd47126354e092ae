"""SDAR-30B-A3B-Chat (``model_type: sdar_moe``,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): a grouped-query expert
transformer that generates by DIFFUSION OVER BLOCKS. Published sizes are the
defaults; ``depth`` cuts it to one pipeline stage (PERF.md section 4): every
expert of a layer, the whole router, all heads and the whole vocabulary stay.

The equations (``h`` the residual stream, `RMSNorm` eps 1e-6 in float32 with
a weight initialised 1, no projection has a bias, the head is untied):

* layer: ``h += attention(norm(h)); h += moe(norm(h))``; logits
  ``= norm(h) W_head``.
* `GroupedQueryAttention`: ``q = x W_q`` as 32 heads of 128, ``k = x W_k``,
  ``v = x W_v`` as 4 heads of 128; ``q`` and ``k`` normalised over a head's
  128 numbers (one weight vector for all query heads, one for the key
  heads); rotary over all 128 dims, theta 1e6, dim i paired with dim i + 64;
  query head ``n`` reads key head ``n // 8``; scores ``q . k / sqrt(128)``,
  softmax in float32.
* the expert layer is `models.moe.HeldExpertsMoe` with all 128 experts held:
  softmax over 128 in float32, the 8 largest, renormalised to sum 1, experts
  of width 768 with gated SiLU, no shared expert.

Which keys a query sees is BLOCK-causal, ``B = block_length``: key ``j`` is
visible to query ``i`` iff ``j // B <= i // B`` (both directions open inside
a block). The logits at a position are for the token AT that position.

Three call modes, `GPT2LMHead.__call__`'s protocol with a window in place of
the one-token step (serving/block_diffusion.py is the caller):

* ``cache=None``: the plain forward under the block mask;
* ``cache`` from `init_cache`, no positions: the prefill, returning
  ``(logits, per-layer (k, v))`` with the fresh rows in positions [0, S);
* ``cache`` with ``cache_positions`` (rows,): the WINDOW step. ``input_ids``
  is (rows, W), row r's W positions start at ``cache_positions[r]``; every
  query sees the row's cached positions below its window's start and all W
  fresh keys. ``cache`` is a `layers.PagedRead` (the pool read in place by
  `ops.paged_attention`, W queries of 32 heads over 4 key heads) or
  per-layer dense views (rows, T, 4, 128) (the gather read, the reference).
  Either way NOTHING is written: the new cache is the layer's fresh rows,
  ``(rows, W, 4 * 128)`` in the pool's dtype, and the caller scatters them
  when a block commits. The two reads agree within rounding, not bitwise
  (PARITY.md).

Scope names the benchmark reads device time by
(``benchmark/layer_metrics/_sdar_regions.py``): ``attn_proj``, ``attn``,
``moe_route``, ``moe_dispatch``, ``moe_experts``, ``embed``, ``final_norm``,
``head``. Left out, as PARITY.md records: the router's balance loss
(training's).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import (
    flash_backend_supported,
    make_flash_attention_fn,
)
from ..ops.paged_attention import paged_attention, paged_attention_supports
from .deepseek_v2 import RMSNorm, rotary
from .layers import (
    PagedRead,
    VocabPaddingMixin,
    dot_product_attention,
    init_paged_kv,
    mask_vocab_padding,
)
from .moe import HeldExpertsMoe
from .registry import register_model

Dtype = Any
_INIT = nn.initializers.normal(stddev=0.02)


def block_causal_mask(seq_len: int, block: int) -> jnp.ndarray:
    """(1, 1, S, S) True=attend: key j visible to query i iff
    ``j // block <= i // block``."""
    at = np.arange(seq_len) // block
    return jnp.asarray(at[None, :] <= at[:, None])[None, None]


def _default_attention_fn(block: int) -> Callable:
    """The block-causal attention where the caller names none: the flash
    forward kernel in a one-device program on a TPU, the XLA form everywhere
    else (`models/deepseek_v2.py::_default_attention_fn` has the reasons)."""
    if flash_backend_supported() and jax.device_count() == 1:
        return make_flash_attention_fn(causal=True, causal_block=block)
    return dot_product_attention


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    block_length: int
    rope_theta: float
    rms_norm_eps: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions, cache=None, window: bool = False):
        b, s, hidden = x.shape
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, kernel_init=_INIT, name=name, **kw)
        norm = lambda name: RMSNorm(self.rms_norm_eps, name=name, **kw)  # noqa: E731
        inv_freq = (1.0 / self.rope_theta ** (
            np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)

        with jax.named_scope("attn_proj"):
            q = dense(hq * d, "q_proj")(x).reshape(b, s, hq, d)
            k = dense(hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
            v = dense(hkv * d, "v_proj")(x).reshape(b, s, hkv, d)
        with jax.named_scope("attn"):
            q = rotary(norm("q_norm")(q), positions, inv_freq)
            k = rotary(norm("k_norm")(k), positions, inv_freq)
            new_cache = None
            if window:
                out, new_cache = self._attend_window(q, k, v, cache,
                                                     positions[:, 0])
            else:
                attend = self.attention_fn \
                    or _default_attention_fn(self.block_length)
                kernel = attend is not dot_product_attention
                # each of a key head's 8 query heads reads the same key
                wide = lambda t: jnp.repeat(t, hq // hkv, axis=2)  # noqa: E731
                out = attend(q, wide(k), wide(v), dtype=self.dtype,
                             mask=None if kernel else block_causal_mask(
                                 s, self.block_length))
                if cache is not None:
                    # prefill: the S fresh rows fill positions [0, S)
                    new_cache = tuple(
                        jax.lax.dynamic_update_slice(
                            held, fresh.astype(held.dtype), (0, 0, 0, 0))
                        for held, fresh in zip(cache, (k, v)))
        with jax.named_scope("attn_proj"):
            out = dense(hidden, "o_proj")(out.reshape(b, s, hq * d))
        return out if cache is None else (out, new_cache)

    def _attend_window(self, q, k, v, cache, start):
        """W queries a row over the row's cached positions below ``start``
        and the W fresh keys, every fresh key visible to every query.
        Returns (rows, W, Hq, D) and the fresh rows as the pool stores
        them."""
        rows, w, hq, d = q.shape
        hkv = self.num_kv_heads
        if isinstance(cache, PagedRead):
            pool = cache.pool
            k_rows = k.reshape(rows, w, hkv * d).astype(pool.k.dtype)
            v_rows = v.reshape(rows, w, hkv * d).astype(pool.v.dtype)
            out = paged_attention(
                q.reshape(rows, w, hq * d), k_rows, v_rows, pool.k, pool.v,
                cache.page_table, cache.live, layer=cache.layer,
                num_heads=hq, num_kv_heads=hkv)
            return out.reshape(q.shape).astype(self.dtype), (k_rows, v_rows)
        return attend_window_views(q, k, v, cache, start, self.dtype)


def attend_window_views(q, k, v, views, start, dtype):
    """The window step's gather read: ``q`` (rows, W, Hq, D) and the fresh
    ``k``, ``v`` (rows, W, Hkv, D) over dense views (rows, T, Hkv, D) of the
    row's pages: one softmax over [the views below ``start`` | the W fresh
    keys]. The fresh rows pass through the views' dtype, as the kernel
    read's pass through the pool's. Returns (rows, W, Hq, D) and the fresh
    rows as the pool stores them, (rows, W, Hkv * D)."""
    rows, w, hq, d = q.shape
    k_view, v_view = views
    hkv, t = k_view.shape[2], k_view.shape[1]
    k = k.astype(k_view.dtype)
    v = v.astype(v_view.dtype)
    grouped = q.reshape(rows, w, hkv, hq // hkv, d)
    scale = np.float32(1.0 / math.sqrt(d))
    held = jnp.einsum("bwkgd,btkd->bkgwt", grouped, k_view).astype(
        jnp.float32) * scale
    below = jnp.arange(t)[None, :] < start[:, None]
    held = jnp.where(below[:, None, None, None, :], held,
                     jnp.finfo(jnp.float32).min)
    fresh = jnp.einsum("bwkgd,bukd->bkgwu", grouped, k).astype(
        jnp.float32) * scale
    weights = jax.nn.softmax(jnp.concatenate([held, fresh], -1),
                             axis=-1).astype(dtype)
    out = jnp.einsum("bkgwt,btkd->bwkgd", weights[..., :t], v_view) \
        + jnp.einsum("bkgwu,bukd->bwkgd", weights[..., t:], v)
    return out.reshape(q.shape).astype(dtype), (
        k.reshape(rows, w, hkv * d), v.reshape(rows, w, hkv * d))


class SDARLayer(nn.Module):
    """One decoder layer; the fields are `SDARLMHead`'s of the same names."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    block_length: int
    rope_theta: float
    rms_norm_eps: float
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    router_init_std: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, h, positions, cache=None, window: bool = False):
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(self.rms_norm_eps, name=name, **kw)  # noqa: E731
        mixed = GroupedQueryAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            self.block_length, self.rope_theta, self.rms_norm_eps,
            attention_fn=self.attention_fn, name="attn", **kw)(
                norm("input_norm")(h), positions, cache, window)
        new_cache = None
        if cache is not None:
            mixed, new_cache = mixed
        h = h + mixed
        h = h + HeldExpertsMoe(
            self.num_experts, self.num_experts, self.num_experts_per_tok,
            self.moe_intermediate_size, norm_topk_prob=self.norm_topk_prob,
            router_init_std=self.router_init_std, name="moe", **kw)(
                norm("post_norm")(h))
        return h if cache is None else (h, new_cache)


class SDARLMHead(VocabPaddingMixin, nn.Module):
    vocab_size: int = 151936
    hidden_dim: int = 2048
    depth: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    # the router's initial scale (PERF.md section 2 has what it does to a
    # comparison in bf16; the experts' is 0.02 always)
    router_init_std: float = 0.02
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position: int = 32768
    # generation by diffusion over blocks of this many positions: what an
    # engine reads to serve the model a block, not a token, a step
    # (serving/build.py). The id that stands at a masked position is the
    # tokenizer's ``<|MASK|>``.
    block_length: int = 4
    mask_token_id: int = 151669
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # None = chosen where the block-causal forward is traced
    attention_fn: Optional[Callable] = None
    pad_vocab_to_multiple_of: int = 128

    # what `HeldExpertsMoe` sows, for an engine that keeps step counters
    step_counters = ("moe_held_assignments", "moe_dropped_assignments",
                     "moe_expert_load_max_over_mean")

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = False,
                 cache=None, cache_positions=None):
        if attention_mask is not None:
            raise ValueError("sdar takes unpadded sequences (the block "
                             "mask has no padding form here)")
        b, s = input_ids.shape
        window = cache is not None and cache_positions is not None
        paged = isinstance(cache, PagedRead)
        if paged and not window:
            raise ValueError("a PagedRead serves the window step only")
        positions = cache_positions[:, None] + jnp.arange(s)[None, :] \
            if window else jnp.arange(s)[None, :]
        h = nn.Embed(self.padded_vocab, self.hidden_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, embedding_init=_INIT,
                     name="embed")(input_ids)
        new_cache = []
        for i in range(self.depth):
            layer = SDARLayer(
                **{f.name: getattr(self, f.name)
                   for f in dataclasses.fields(SDARLayer)
                   if f.name not in ("parent", "name")}, name=f"layer{i}")
            if cache is None:
                h = layer(h, positions)
            else:
                h, c = layer(h, positions,
                             cache.replace(layer=i) if paged else cache[i],
                             window)
                new_cache.append(c)
        h = RMSNorm(self.rms_norm_eps, self.dtype, self.param_dtype,
                    name="final_norm")(h)
        logits = nn.Dense(self.padded_vocab, use_bias=False, dtype=self.dtype,
                          param_dtype=self.param_dtype, kernel_init=_INIT,
                          name="head")(h)
        logits = mask_vocab_padding(logits.astype(jnp.float32),
                                    self.vocab_size)
        return logits if cache is None else (logits, tuple(new_cache))

    def init_cache(self, batch: int, max_len: int):
        """Zero-filled per-layer cache: ``depth`` pairs of (batch, max_len,
        4, 128) keys and values in the compute dtype."""
        shape = (batch, max_len, self.num_kv_heads, self.head_dim)
        return tuple((jnp.zeros(shape, self.dtype),
                      jnp.zeros(shape, self.dtype))
                     for _ in range(self.depth))

    def init_paged_pool(self, n_pages: int, page_size: int,
                        quantized: bool = False):
        """Zero-filled paged pool, ONE `layers.PagedKV` stacked over all
        ``depth`` layers: 4 key/value heads of 128 a position a layer."""
        return init_paged_kv(self.depth, n_pages, page_size,
                             self.num_kv_heads, self.head_dim,
                             dtype=self.dtype, quantized=quantized)

    def paged_read_supports(self, page_size: int) -> bool:
        """Whether the window step's kernel read can take this pool."""
        return paged_attention_supports(
            page_size, self.num_kv_heads * self.head_dim, self.dtype,
            window=self.block_length, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads)

    def expert_path(self, tokens: int) -> str:
        """How a program over ``tokens`` positions multiplies its experts:
        `HeldExpertsMoe.expert_path` of a layer's own module."""
        layer = HeldExpertsMoe(
            self.num_experts, self.num_experts, self.num_experts_per_tok,
            self.moe_intermediate_size, dtype=self.dtype, parent=None)
        return layer.expert_path(tokens, self.hidden_dim)


@register_model("sdar_30b_a3b_chat")
def sdar_30b_a3b_chat(**kw) -> SDARLMHead:
    """SDAR-30B-A3B-Chat at its published sizes; ``depth`` cuts it to a
    pipeline stage. Weights rest in the compute dtype unless ``param_dtype``
    says otherwise: at 2 B a parameter six layers fit a chip."""
    kw.setdefault("param_dtype", kw.get("dtype", jnp.float32))
    return SDARLMHead(**kw)

"""DeepSeek-V2 (236B, 21B active): latent attention (MLA) and group-limited
routing over 160 experts with two shared experts
(https://huggingface.co/deepseek-ai/DeepSeek-V2, ``model_type: deepseek_v2``,
arXiv:2405.04434). Published sizes are the defaults; ``depth``,
``num_experts_held`` / ``first_expert`` and ``vocab_size`` cut it to one
chip's share of a deployment (PERF.md section 4): the router stays 160 wide
in 8 groups, the chip computes its own experts' part of each layer's result.

The equations (``h`` the residual stream, `RMSNorm` eps 1e-6 in float32, no
projection has a bias):

* layer ``i``: ``h += attention(norm(h)); h += mlp(norm(h))``; the mlp is a
  gated-SiLU MLP of ``intermediate_size`` for ``i < first_k_dense_replace``,
  else `models.moe.HeldExpertsMoe` (softmax over 160, the 3 best of 8 groups
  by their largest probability, the 6 largest remaining, weights ``16 p``,
  not renormalised) plus one gated-SiLU MLP of ``n_shared_experts`` x
  ``moe_intermediate_size`` applied to every token.
* `LatentAttention`: ``c_q = norm(W_dq x)``; ``[q_nope | q_pe] = W_uq c_q``
  per head (128 + 64); ``[c_kv | k_pe] = W_dkv x`` (512 + 64), ``c =
  norm(c_kv)``, ``k_pe`` ONE rotary key shared by every head; ``[k_nope | v]
  = W_ukv c`` per head (128 + 128). Rotary on ``q_pe`` and ``k_pe`` only,
  YaRN frequencies (`yarn_inv_freq`), dim i paired with dim i + 32. Scores
  ``scale * (q_nope . k_nope + q_pe . k_pe)``, ``scale = 192^-0.5 *
  mscale^2`` (`softmax_scale`), causal softmax in float32.

  Two forms of the same attention. **Expanded** (no cache, and prefill): the
  keys of 192 and values of 128 are built for every position and handed to
  ``attention_fn``; prefill besides returns the LATENT rows ``[c | k_pe]``,
  576 numbers a token, which is all the cache holds. **Absorbed** (the S=1
  decode step): ``q_lat = W_uk^T q_nope`` (512 a head), scores ``q_lat . c +
  q_pe . k_pe`` against the cached rows, ``o = W_uv (sum_j p_j c_j)``: one
  576-wide key and one 512-wide value shared by all heads, never expanded.
  The absorbed read is `ops.mla_paged_attention` over the pool in place
  when the cache is a `layers.PagedRead`, and a masked softmax over the
  gathered dense view otherwise (the reference read). The products are
  reassociated, so the two forms agree within rounding, not bitwise
  (PARITY.md).

The cache protocol is `GPT2LMHead`'s (serving/continuous.py): `init_cache`
gives per-layer tuples of leaves, prefill returns them filled, a decode step
over dense views returns the views with the fresh row written, a decode step
over a `PagedRead` returns the fresh rows; `init_paged_pool` says what a row
is. Here a layer's tuple is (c, k_pe): (batch, positions, 512) and
(batch, positions, 64).

Left out, as PARITY.md records: ``seq_aux`` and the balance losses
(training's). Scope names the benchmark reads device time by
(``benchmark/layer_metrics/_dsv2_regions.py``): ``mla_proj``, ``mla_attn``,
``dense_mlp``, ``moe_route``, ``moe_dispatch``, ``moe_experts``,
``shared_expert``, ``embed``, ``final_norm``, ``head``.
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
from ..ops.mla_paged_attention import (
    mla_paged_attention,
    mla_paged_attention_supports,
)
from .layers import (
    PagedRead,
    VocabPaddingMixin,
    causal_mask,
    dot_product_attention,
    init_paged_latent,
    mask_vocab_padding,
)
from .moe import HeldExpertsMoe
from .registry import register_model

Dtype = Any
_INIT = nn.initializers.normal(stddev=0.02)


def _dense(features: int, name: str, dtype, param_dtype) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=param_dtype, kernel_init=_INIT, name=name)


class RMSNorm(nn.Module):
    epsilon: float = 1e-6
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (y * w.astype(jnp.float32)).astype(self.dtype)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN: a pair that turns more
    than ``beta_fast`` times over the original context keeps its frequency,
    one that turns fewer than ``beta_slow`` times has it divided by
    ``factor``, a linear ramp over the pair indices between."""
    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(x, positions, inv_freq: np.ndarray, table_scale: float = 1.0):
    """Rotate the last axis of x (B, S, ..., D) by position (``positions``
    (B or 1, S)), dim i paired with dim i + D / 2, in float32."""
    angle = positions.astype(jnp.float32)[..., None] * inv_freq  # (B, S, D/2)
    shape = angle.shape[:2] + (1,) * (x.ndim - 3) + angle.shape[2:]
    cos = (jnp.cos(angle) * table_scale).reshape(shape)
    sin = (jnp.sin(angle) * table_scale).reshape(shape)
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


class KvUp(nn.Module):
    """``W_ukv``: the latent to every head's [key-nope | value]. Used whole
    by the expanded form and in its two halves by the absorbed one."""

    heads: int
    nope: int
    dv: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, c, absorbed: bool = False):
        kernel = self.param(
            "kernel", _INIT, (c.shape[-1], self.heads * (self.nope + self.dv)),
            self.param_dtype).astype(self.dtype)
        if absorbed:
            w = kernel.reshape(c.shape[-1], self.heads, self.nope + self.dv)
            return w[..., :self.nope], w[..., self.nope:]
        out = jnp.dot(c, kernel).reshape(
            c.shape[:-1] + (self.heads, self.nope + self.dv))
        return out[..., :self.nope], out[..., self.nope:]


@dataclasses.dataclass(frozen=True)
class LayerSizes:
    """What a layer needs of the model's fields (same names, same values)."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    n_shared_experts: int
    num_experts_held: int
    first_expert: int
    router_init_std: float
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_original_max_position: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    dtype: Dtype
    param_dtype: Dtype
    attention_fn: Optional[Callable]

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


class LatentAttention(nn.Module):
    cfg: LayerSizes

    @nn.compact
    def __call__(self, x, positions, cache=None, decoding: bool = False):
        c_ = self.cfg
        b, s, hidden = x.shape
        h, nope, rope = (c_.num_heads, c_.qk_nope_head_dim,
                         c_.qk_rope_head_dim)
        dv, rank = c_.v_head_dim, c_.kv_lora_rank
        kw = dict(dtype=c_.dtype, param_dtype=c_.param_dtype)
        dense = lambda n, name: _dense(n, name, **kw)  # noqa: E731
        norm = lambda name: RMSNorm(c_.rms_norm_eps, name=name, **kw)  # noqa: E731
        inv_freq = yarn_inv_freq(
            rope, c_.rope_theta, c_.rope_factor,
            c_.rope_original_max_position, c_.rope_beta_fast,
            c_.rope_beta_slow)
        table_scale = yarn_mscale(c_.rope_factor, c_.rope_mscale) \
            / yarn_mscale(c_.rope_factor, c_.rope_mscale_all_dim)
        turn = lambda t: rotary(t, positions, inv_freq, table_scale)  # noqa: E731
        kv_up = KvUp(h, nope, dv, name="kv_b_proj", **kw)

        with jax.named_scope("mla_proj"):
            c_q = norm("q_a_norm")(dense(c_.q_lora_rank, "q_a_proj")(x))
            q = dense(h * (nope + rope), "q_b_proj")(c_q).reshape(
                b, s, h, nope + rope)
            q_nope, q_pe = q[..., :nope], turn(q[..., nope:])
            kv = dense(rank + rope, "kv_a_proj")(x)
            c = norm("kv_a_norm")(kv[..., :rank])
            k_pe = turn(kv[..., rank:])              # ONE rotary key a token

        if decoding:
            # the absorbed form, S == 1: W_uk folded into the query, W_uv
            # applied to the weighted sum of latents
            with jax.named_scope("mla_proj"):
                w_uk, w_uv = kv_up(c, absorbed=True)
                q_c = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk)
            with jax.named_scope("mla_attn"):
                if isinstance(cache, PagedRead):
                    pool = cache.pool
                    new_cache = (c[:, 0].astype(pool.c.dtype),
                                 k_pe[:, 0].astype(pool.pe.dtype))
                    o_lat = mla_paged_attention(
                        q_c, q_pe[:, 0], *new_cache, pool.c, pool.pe,
                        cache.page_table, cache.live, layer=cache.layer,
                        sm_scale=c_.softmax_scale)
                else:
                    o_lat, new_cache = _attend_view(
                        q_c, q_pe[:, 0], c[:, 0], k_pe[:, 0], cache,
                        positions[:, 0], c_.softmax_scale, c_.dtype)
            with jax.named_scope("mla_proj"):
                out = jnp.einsum("bhc,chd->bhd", o_lat.astype(c_.dtype),
                                 w_uv).reshape(b, 1, h * dv)
                return dense(hidden, "o_proj")(out), new_cache

        # the expanded form: every head's key and value at every position
        with jax.named_scope("mla_proj"):
            k_nope, v = kv_up(c)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None, :], (b, s, h, rope))], -1)
            # ``attention_fn`` scales by (nope + rope)^-0.5; YaRN's mscale^2
            # rides the query
            extra = c_.softmax_scale * math.sqrt(nope + rope)
            q = (jnp.concatenate([q_nope, q_pe], -1).astype(jnp.float32)
                 * extra).astype(c_.dtype)
        with jax.named_scope("mla_attn"):
            attend = c_.attention_fn or _default_attention_fn()
            kernel = attend is not dot_product_attention
            out = attend(q, k, v, mask=None if kernel else causal_mask(s),
                         dtype=c_.dtype)
        with jax.named_scope("mla_proj"):
            out = dense(hidden, "o_proj")(out.reshape(b, s, h * dv))
        if cache is None:
            return out
        # prefill: the S fresh rows fill positions [0, S) of the cache
        return out, tuple(
            jax.lax.dynamic_update_slice(held, fresh.astype(held.dtype),
                                         (0, 0, 0))
            for held, fresh in zip(cache, (c, k_pe)))


def _attend_view(q_c, q_pe, c_row, pe_row, views, position, scale: float,
                 dtype):
    """The absorbed read over dense views (B, T, rank) and (B, T, rope):
    the fresh row is written at each row's own position, every position up
    to it is attended. Returns the weighted sum of ``c`` and the views."""
    c_view, pe_view = views
    at = jnp.arange(c_view.shape[1])[None, :]
    here = (at == position[:, None])[:, :, None]
    c_view = jnp.where(here, c_row[:, None, :].astype(c_view.dtype), c_view)
    pe_view = jnp.where(here, pe_row[:, None, :].astype(pe_view.dtype),
                        pe_view)
    scores = (jnp.einsum("bhc,btc->bht", q_c, c_view)
              + jnp.einsum("bhd,btd->bht", q_pe, pe_view)).astype(
                  jnp.float32) * scale
    scores = jnp.where((at <= position[:, None])[:, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bht,btc->bhc", weights, c_view), (c_view, pe_view)


def _default_attention_fn() -> Callable:
    """The expanded form's attention where the caller names none: the flash
    kernel in a one-device program on a TPU (at 128 heads and thousands of
    positions the XLA form's scores do not fit a chip), the XLA form
    everywhere else (GSPMD cannot partition a Mosaic kernel: a caller with a
    mesh passes `make_flash_attention_fn(mesh=mesh)` itself)."""
    if flash_backend_supported() and jax.device_count() == 1:
        return make_flash_attention_fn(causal=True)
    return dot_product_attention


class GatedMlp(nn.Module):
    width: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: _dense(  # noqa: E731
            n, name, self.dtype, self.param_dtype)
        mid = nn.silu(dense(self.width, "gate")(x)) \
            * dense(self.width, "up")(x)
        return dense(x.shape[-1], "down")(mid)


class DeepSeekV2Layer(nn.Module):
    dense: bool
    cfg: LayerSizes

    @nn.compact
    def __call__(self, h, positions, cache=None, decoding: bool = False):
        c = self.cfg
        kw = dict(dtype=c.dtype, param_dtype=c.param_dtype)
        norm = lambda name: RMSNorm(c.rms_norm_eps, name=name, **kw)  # noqa: E731
        mixed = LatentAttention(c, name="attn")(
            norm("input_norm")(h), positions, cache, decoding)
        new_cache = None
        if cache is not None:
            mixed, new_cache = mixed
        h = h + mixed
        x = norm("post_norm")(h)
        if self.dense:
            h = h + GatedMlp(c.intermediate_size, name="dense_mlp", **kw)(x)
        else:
            routed = HeldExpertsMoe(
                c.n_routed_experts, c.num_experts_held, c.num_experts_per_tok,
                c.moe_intermediate_size, c.first_expert, n_group=c.n_group,
                topk_group=c.topk_group, norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor,
                router_init_std=c.router_init_std, name="moe", **kw)(x)
            shared = GatedMlp(c.n_shared_experts * c.moe_intermediate_size,
                              name="shared_expert", **kw)(x)
            h = h + routed + shared
        return h if cache is None else (h, new_cache)


class DeepSeekV2LMHead(VocabPaddingMixin, nn.Module):
    vocab_size: int = 102400
    hidden_dim: int = 5120
    depth: int = 60
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 160
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    n_shared_experts: int = 2
    # the chip's share of the experts: which it holds of each layer's 160
    num_experts_held: int = 160
    first_expert: int = 0
    # the router's initial scale: normal(0.02) on a 5120-wide unit-RMS input
    # gives router logits of std 1.4 (PERF.md section 2 has what that does
    # to a comparison in bf16)
    router_init_std: float = 0.02
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # the source's ``rope_scaling`` group (``type: yarn``)
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    max_position: int = 163840
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    # None = chosen where the expanded form is traced
    # (`_default_attention_fn`)
    attention_fn: Optional[Callable] = None
    pad_vocab_to_multiple_of: int = 128

    # what `HeldExpertsMoe` sows, for an engine that keeps step counters
    step_counters = ("moe_held_assignments", "moe_dropped_assignments",
                     "moe_expert_load_max_over_mean")

    @property
    def sizes(self) -> LayerSizes:
        return LayerSizes(**{f.name: getattr(self, f.name)
                             for f in dataclasses.fields(LayerSizes)})

    @property
    def softmax_scale(self) -> float:
        return self.sizes.softmax_scale

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = False,
                 cache=None, cache_positions=None):
        """Causal LM forward, `GPT2LMHead.__call__`'s modes: ``cache=None``
        the plain forward; ``cache`` from `init_cache` and no positions the
        prefill, returning ``(logits, per-layer (latent rows,))``; ``cache``
        with ``cache_positions`` (B,) the S=1 decode step (module note)."""
        if attention_mask is not None:
            raise ValueError("deepseek_v2 takes unpadded sequences (the "
                             "causal kernel path has no padding mask here)")
        b, s = input_ids.shape
        decoding = cache is not None and cache_positions is not None
        paged = isinstance(cache, PagedRead)
        if (decoding and s != 1) or (paged and not decoding):
            raise ValueError(
                "a latent cache serves the S=1 decode step; windows "
                "(resume, speculative verify) are K/V-only (ROADMAP R5)")
        positions = cache_positions[:, None] if decoding \
            else jnp.arange(s)[None, :]
        h = nn.Embed(self.padded_vocab, self.hidden_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, embedding_init=_INIT,
                     name="embed")(input_ids)
        new_cache = []
        for i in range(self.depth):
            layer = DeepSeekV2Layer(i < self.first_k_dense_replace,
                                    self.sizes, name=f"layer{i}")
            if cache is None:
                h = layer(h, positions)
            else:
                h, c = layer(h, positions,
                             cache.replace(layer=i) if paged else cache[i],
                             decoding)
                new_cache.append(c)
        h = RMSNorm(self.rms_norm_eps, self.dtype, self.param_dtype,
                    name="final_norm")(h)
        logits = _dense(self.padded_vocab, "head", self.dtype,
                        self.param_dtype)(h)
        logits = mask_vocab_padding(logits.astype(jnp.float32),
                                    self.vocab_size)
        return logits if cache is None else (logits, tuple(new_cache))

    def init_cache(self, batch: int, max_len: int):
        """Zero-filled per-layer cache: ``depth`` pairs of (batch, max_len,
        512) compressed key-values and (batch, max_len, 64) rotary keys in
        the compute dtype."""
        c = jnp.zeros((batch, max_len, self.kv_lora_rank), self.dtype)
        pe = jnp.zeros((batch, max_len, self.qk_rope_head_dim), self.dtype)
        return tuple((c, pe) for _ in range(self.depth))

    def init_paged_pool(self, n_pages: int, page_size: int,
                        quantized: bool = False):
        """Zero-filled paged latent pool, ONE `layers.PagedLatent` stacked
        over all ``depth`` layers: 576 numbers a token a layer."""
        if quantized:
            raise ValueError("a latent pool has no int8 form")
        return init_paged_latent(self.depth, n_pages, page_size,
                                 self.kv_lora_rank, self.qk_rope_head_dim,
                                 dtype=self.dtype)

    def paged_read_supports(self, page_size: int) -> bool:
        """Whether the decode step's kernel read can take this pool."""
        return mla_paged_attention_supports(
            page_size, self.kv_lora_rank, self.qk_rope_head_dim, self.dtype)

    def expert_path(self, tokens: int) -> str:
        """How a program over ``tokens`` positions multiplies its experts:
        `HeldExpertsMoe.expert_path` of a layer's own module."""
        layer = HeldExpertsMoe(
            self.n_routed_experts, self.num_experts_held,
            self.num_experts_per_tok, self.moe_intermediate_size,
            self.first_expert, dtype=self.dtype, parent=None)
        return layer.expert_path(tokens, self.hidden_dim)


@register_model("deepseek_v2_236b_a21b")
def deepseek_v2_236b_a21b(**kw) -> DeepSeekV2LMHead:
    """DeepSeek-V2 at its published sizes; ``depth``, ``num_experts_held``
    (with ``first_expert``) and ``vocab_size`` cut it to a chip's share.
    Weights rest in the compute dtype unless ``param_dtype`` says otherwise:
    at 2 B a parameter the share fits a chip, at 4 it does not."""
    kw.setdefault("param_dtype", kw.get("dtype", jnp.float32))
    return DeepSeekV2LMHead(**kw)

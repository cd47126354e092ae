"""Qwen3-Next-80B-A3B: Gated DeltaNet layers 3:1 with gated softmax
attention, every layer followed by a dropless top-10-of-512 expert block with
one shared expert (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct,
``model_type: qwen3_next``). Published sizes are the defaults; ``depth``,
``num_experts_held`` / ``first_expert`` and ``vocab_size`` cut it to one
chip's share of a deployment (PERF.md section 4): the router stays 512 wide
and top-10, the chip computes its own experts' part of each layer's result.

The equations (``h`` the residual stream, no projection has a bias):

* norm: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``, ``w`` starting at zero,
  computed in float32 (`ZeroCentredRMSNorm`).
* layer ``i``: ``h += mixer(norm(h)); h += experts(norm(h))``; the mixer is
  `GatedAttention` where ``(i + 1) % 4 == 0``, else `GatedDeltaNet`.
* `GatedAttention`: 16 query heads over 2 key-value heads of 256, the query
  projection twice as wide (a query and an output gate per head), QK-norm,
  rotary on the first 64 of 256 dims (theta 1e7, rotate-half pairing),
  causal softmax, ``o_proj(attn * sigmoid(gate))``. Key and value heads are
  repeated to 16 before ``attention_fn`` (the flash kernel folds one head
  count).
* `GatedDeltaNet`: one projection to q, k (16 heads of 128), v, z (32 heads
  of 128), one to the per-head write strength and decay inputs; then, all
  inside `ops.gated_delta_rule.gated_delta_mixer`: a depthwise causal
  convolution of 4 and SiLU over [q, k, v], q and k l2-normalised, each key
  head serving two value heads, the rule under scope ``gdn_rule``, a gated
  RMSNorm per head (``w`` starting at one, times ``silu(z)``); ``out_proj``.
* expert block: `models.moe.HeldExpertsMoe` plus `SharedExpert` behind a
  sigmoid gate; the block's output is their sum.

Left out, as PARITY.md records: the multi-token-prediction module, any
auxiliary balance loss. Scope names the benchmark reads device time by
(``benchmark/layer_metrics/_hybrid_regions.py``): ``gdn``, ``gdn_rule``,
``gated_attn``, ``moe_route``, ``moe_dispatch``, ``moe_experts``,
``shared_expert``, ``embed``, ``final_norm``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUAL_NAMES
from ..ops.gated_delta_rule import gated_delta_mixer
from ..ops.gdn_rule_kernels import RESIDUAL_NAMES as RULE_RESIDUAL_NAMES
from ..parallel.mesh import FSDP, MODEL
from ..parallel.sharding import PartitionRules
from .layers import (
    VocabPaddingMixin,
    causal_mask,
    dot_product_attention,
    mask_vocab_padding,
)
from .moe import ROUTE_NAMES, HeldExpertsMoe
from .registry import register_model

Dtype = Any
_INIT = nn.initializers.normal(stddev=0.02)
# the delta rule takes its value heads this many at a time, each block's
# forward rematerialised: with all 32 at once one sequence of 8,192 does not
# fit a 16 GB chip (18.0 GB against 13.4, fit_check_lm, PR 27)
RULE_HEAD_BLOCK = 8
# what a rematerialised layer keeps from the step's forward to its backward:
# what its kernels wrote (the rule's output, chunk-start states and inverses,
# 402 MB a layer at 8,192; the attention layer's output and log-sum-exp, 68
# MB) and the integers of the router's sorts (1.3 MB), each named where it
# is made. So the backward's second pass over a layer runs neither
# `gdn_rule_fwd` nor `flash_fwd` nor a sort again (20 ms of a 245 ms step
# for 0.92 GB more at the peak, of the 2.7 the step had free: PERF.md
# section 6, PR 50); everything else it makes again as before. Which of
# these a traced program contains decides what is kept: one that takes the
# rule's XLA form keeps nothing of the rule
LAYER_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    *RULE_RESIDUAL_NAMES, *FLASH_RESIDUAL_NAMES, *ROUTE_NAMES)


def _dense(features: int, name: str, dtype, param_dtype) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=param_dtype, kernel_init=_INIT, name=name)


class ZeroCentredRMSNorm(nn.Module):
    epsilon: float = 1e-6
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (y * (1.0 + w.astype(jnp.float32))).astype(self.dtype)


def rotary(x, positions, rotary_dim: int, theta: float):
    """Rotate the first ``rotary_dim`` dims of x (B, S, H, D) by position,
    pairing dim i with dim i + rotary_dim / 2; the rest pass."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x32 = x.astype(jnp.float32)
    x1, x2, rest = (x32[..., :half], x32[..., half:rotary_dim],
                    x32[..., rotary_dim:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1).astype(x.dtype)


class GatedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    epsilon: float
    attention_fn: Callable = dot_product_attention
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        dense = lambda n, name: _dense(  # noqa: E731
            n, name, self.dtype, self.param_dtype)
        norm = lambda name: ZeroCentredRMSNorm(  # noqa: E731
            self.epsilon, self.dtype, self.param_dtype, name=name)
        qg = dense(h * 2 * d, "q_proj")(x).reshape(b, s, h, 2, d)
        q, gate = qg[..., 0, :], qg[..., 1, :]
        k = dense(kv * d, "k_proj")(x).reshape(b, s, kv, d)
        v = dense(kv * d, "v_proj")(x).reshape(b, s, kv, d)
        positions = jnp.arange(s)
        q = rotary(norm("q_norm")(q), positions, self.rotary_dim,
                   self.rope_theta)
        k = rotary(norm("k_norm")(k), positions, self.rotary_dim,
                   self.rope_theta)
        # each key-value head serves h / kv query heads: repeated here, the
        # kernel folds (batch, heads) with one head count
        k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
        kernel = self.attention_fn is not dot_product_attention
        out = self.attention_fn(q, k, v, mask=None if kernel else
                                causal_mask(s), dtype=self.dtype)
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            self.dtype)
        return dense(hidden, "o_proj")(out.reshape(b, s, h * d))


class GatedDeltaNet(nn.Module):
    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_kernel: int
    epsilon: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        hk, hv = self.num_k_heads, self.num_v_heads
        dk, dv = self.head_k_dim, self.head_v_dim
        key_dim, value_dim = hk * dk, hv * dv
        dense = lambda n, name: _dense(  # noqa: E731
            n, name, self.dtype, self.param_dtype)
        # columns: [q | k | v | z] and [b | a], the program's own order
        qkvz = dense(2 * key_dim + 2 * value_dim, "in_proj_qkvz")(x)
        ba = dense(2 * hv, "in_proj_ba")(x).astype(jnp.float32)
        # torch's Conv1d default for a depthwise kernel of 4: U(+-1/2);
        # tap j weighs the input 3 - j back
        conv_w = self.param(
            "conv1d", lambda key, shape, dt: jax.random.uniform(
                key, shape, dt, -0.5, 0.5),
            (self.conv_kernel, 2 * key_dim + value_dim), self.param_dtype)
        a_log = self.param(
            "A_log", lambda key, shape, dt: jnp.log(jax.random.uniform(
                key, shape, dt, 1e-3, 16.0)), (hv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                             jnp.float32)
        norm_w = self.param("norm", nn.initializers.ones, (dv,),
                            self.param_dtype)

        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        # the depthwise causal convolution of q | k | v with its SiLU, the l2
        # norm of q and k, each key head for its hv / hk value heads, the
        # rule (scope ``gdn_rule``), the norm gated by z: one call, which on
        # a TPU's own program reads qkvz where it stands and writes between
        # it and ``out_proj`` one table, the convolution's
        o = gated_delta_mixer(qkvz, conv_w, g, beta, norm_w, self.epsilon,
                              key_heads=hk, head_block=RULE_HEAD_BLOCK)
        return dense(hidden, "out_proj")(o)


class SharedExpert(nn.Module):
    expert_dim: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: _dense(  # noqa: E731
            n, name, self.dtype, self.param_dtype)
        mid = nn.silu(dense(self.expert_dim, "gate")(x)) \
            * dense(self.expert_dim, "up")(x)
        opened = jax.nn.sigmoid(
            dense(1, "shared_gate")(x).astype(jnp.float32))
        return dense(x.shape[-1], "down")(mid) * opened.astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class LayerSizes:
    """What a layer needs of the model's fields (same names, same values)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts_held: int
    first_expert: int
    rms_norm_eps: float
    dtype: Dtype
    param_dtype: Dtype
    attention_fn: Callable


class HybridLayer(nn.Module):
    """``h += mixer(norm(h)); h += experts(norm(h)) + shared(norm(h))``."""

    full_attention: bool
    cfg: LayerSizes

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        kw = dict(dtype=c.dtype, param_dtype=c.param_dtype)
        norm = lambda name: ZeroCentredRMSNorm(  # noqa: E731
            c.rms_norm_eps, name=name, **kw)
        x = norm("input_norm")(h)
        if self.full_attention:
            mixed = GatedAttention(
                c.num_heads, c.num_kv_heads, c.head_dim,
                int(c.head_dim * c.partial_rotary_factor), c.rope_theta,
                c.rms_norm_eps, c.attention_fn, name="gated_attn", **kw)(x)
        else:
            mixed = GatedDeltaNet(
                c.linear_num_key_heads, c.linear_num_value_heads,
                c.linear_key_head_dim, c.linear_value_head_dim,
                c.linear_conv_kernel_dim, c.rms_norm_eps, name="gdn", **kw)(x)
        h = h + mixed
        x = norm("post_norm")(h)
        routed = HeldExpertsMoe(
            c.num_experts, c.num_experts_held, c.num_experts_per_tok,
            c.moe_intermediate_size, c.first_expert, name="moe", **kw)(x)
        shared = SharedExpert(c.shared_expert_intermediate_size,
                              name="shared_expert", **kw)(x)
        return h + routed + shared


class Qwen3NextLMHead(VocabPaddingMixin, nn.Module):
    vocab_size: int = 151936
    hidden_dim: int = 2048
    depth: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    # the chip's share of the experts: which it holds of each layer's 512
    num_experts_held: int = 512
    first_expert: int = 0
    rms_norm_eps: float = 1e-6
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    attention_fn: Callable = dot_product_attention
    remat: bool = False  # jax.checkpoint each layer (LAYER_REMAT_POLICY)
    # the token table's rows and the head's columns lane-aligned (151,936 is
    # a multiple already; a slice of the vocabulary need not be)
    pad_vocab_to_multiple_of: int = 128

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = False):
        if attention_mask is not None:
            raise ValueError("qwen3_next takes unpadded sequences: neither "
                             "the delta rule nor the causal kernel path has "
                             "a padding mask here")
        h = nn.Embed(self.padded_vocab, self.hidden_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, embedding_init=_INIT,
                     name="embed")(input_ids)
        sizes = LayerSizes(**{f.name: getattr(self, f.name)
                              for f in dataclasses.fields(LayerSizes)})
        layer_cls = nn.remat(HybridLayer, policy=LAYER_REMAT_POLICY) \
            if self.remat else HybridLayer
        for i in range(self.depth):
            h = layer_cls((i + 1) % self.full_attention_interval == 0, sizes,
                          name=f"layer{i}")(h)
        h = ZeroCentredRMSNorm(self.rms_norm_eps, self.dtype,
                               self.param_dtype, name="final_norm")(h)
        logits = _dense(self.padded_vocab, "head", self.dtype,
                        self.param_dtype)(h)
        return mask_vocab_padding(logits.astype(jnp.float32),
                                  self.vocab_size)

    @staticmethod
    def partition_rules() -> PartitionRules:
        """Megatron TP over ``model`` on the head / neuron dim with FSDP
        over ``fsdp`` on the other, as `layers.tp_fsdp_rules` does for the
        GPT-2 family. The held experts stay whole on every device of the
        mesh: the `expert` axis and its exchange are ROADMAP's."""
        return PartitionRules([
            (r"(q_proj|k_proj|v_proj|in_proj_qkvz)/kernel", P(FSDP, MODEL)),
            (r"(o_proj|out_proj)/kernel", P(MODEL, FSDP)),
            (r"shared_expert/(gate|up)/kernel", P(FSDP, MODEL)),
            (r"shared_expert/down/kernel", P(MODEL, FSDP)),
            (r"embed/embedding", P(MODEL, FSDP)),
            (r"head/kernel", P(FSDP, MODEL)),
        ])


@register_model("qwen3_next_80b_a3b")
def qwen3_next_80b_a3b(**kw) -> Qwen3NextLMHead:
    """Qwen3-Next-80B-A3B at its published sizes; ``depth``,
    ``num_experts_held`` (with ``first_expert``) and ``vocab_size`` cut it to
    a chip's share."""
    return Qwen3NextLMHead(**kw)

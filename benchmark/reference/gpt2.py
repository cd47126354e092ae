"""Plain GPT-2: the forward pass and the next-token loss, as published.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes): no kernel, no
cache, no batching tricks, none of the program's modules. Radford et al. 2019,
the HF ``GPT2LMHeadModel`` equations: learned token + position embeddings,
pre-LN blocks (LN, fused qkv, causal softmax attention scaled by
1/sqrt(head), output projection, LN, 4x MLP with the tanh GELU
``gelu_new``), a final LN, and the head tied to the token table.

The one thing taken from the program is the *layout of its weights*
(`from_program_params`), because the comparison needs the same numbers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def from_program_params(params) -> dict:
    """The program's flax tree -> this file's flat layout, in float32.
    qkv kernel (h, 3, heads, d) and out kernel (heads, d, h) are the
    program's head-split form of GPT-2's (h, 3h) and (h, h) matrices."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    blocks = []
    i = 0
    while f"block{i}" in params:
        b = params[f"block{i}"]
        blocks.append({
            "ln1": (f32(b["ln1"]["scale"]), f32(b["ln1"]["bias"])),
            "qkv_w": f32(b["attn"]["qkv"]["kernel"]),
            "qkv_b": f32(b["attn"]["qkv"]["bias"]),
            "out_w": f32(b["attn"]["out"]["kernel"]),
            "out_b": f32(b["attn"]["out"]["bias"]),
            "ln2": (f32(b["ln2"]["scale"]), f32(b["ln2"]["bias"])),
            "fc1_w": f32(b["mlp"]["fc1"]["kernel"]),
            "fc1_b": f32(b["mlp"]["fc1"]["bias"]),
            "fc2_w": f32(b["mlp"]["fc2"]["kernel"]),
            "fc2_b": f32(b["mlp"]["fc2"]["bias"]),
        })
        i += 1
    return {"wte": f32(params["wte"]["embedding"]),
            "wpe": f32(params["wpe"]["embedding"]),
            "blocks": blocks,
            "ln_f": (f32(params["ln_f"]["scale"]),
                     f32(params["ln_f"]["bias"]))}


def _layer_norm(x, scale_bias, eps):
    scale, bias = scale_bias
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(ref_params: dict, ids, vocab_size: int, eps: float = 1e-5):
    """(B, S) int token ids -> (B, S, vocab_size) float32 logits."""
    with jax.default_matmul_precision("highest"):
        b, s = ids.shape
        x = ref_params["wte"][ids] + ref_params["wpe"][jnp.arange(s)][None]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for blk in ref_params["blocks"]:
            h = _layer_norm(x, blk["ln1"], eps)
            qkv = jnp.einsum("bsh,hcnd->cbnsd", h, blk["qkv_w"]) \
                + blk["qkv_b"][:, None, :, None, :]
            q, k, v = qkv[0], qkv[1], qkv[2]
            scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) \
                / math.sqrt(q.shape[-1])
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            att = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bnqk,bnkd->bqnd", att, v)
            x = x + jnp.einsum("bqnd,ndh->bqh", ctx, blk["out_w"]) \
                + blk["out_b"]
            h = _layer_norm(x, blk["ln2"], eps)
            h = _gelu_new(h @ blk["fc1_w"] + blk["fc1_b"])
            x = x + h @ blk["fc2_w"] + blk["fc2_b"]
        x = _layer_norm(x, ref_params["ln_f"], eps)
        return (x @ ref_params["wte"].T)[..., :vocab_size]


def next_token_loss(ref_params: dict, ids, vocab_size: int,
                    eps: float = 1e-5):
    """Mean cross-entropy of token t+1 given tokens <= t."""
    logits = forward(ref_params, ids, vocab_size, eps)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()

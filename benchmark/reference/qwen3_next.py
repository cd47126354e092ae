"""Plain Qwen3-Next: the forward pass and the next-token loss of one chip's
share, as published (``model_type: qwen3_next``; the Gated DeltaNet rule of
arXiv:2412.06464).

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes): the delta rule
position by position, attention as one softmax per head, the experts as a
loop over the held experts with a mask, no kernel, no chunking, no sorting,
none of the program's modules. ``sizes`` is the ``published`` group of the
configuration file; ``share`` says what this chip holds: ``first_expert``,
``num_experts_held`` and ``vocab_size`` (ids and logits are over the slice).
What the absent experts would add is left out here as in the program.

The one thing taken from the program is the *layout of its weights*
(`from_program_params`): names, the fused projections' column order
([q | k | v | z], [b | a], a query and its gate side by side per head), and
that value head j of the delta rule reads key head j // 2.

For the chip check at S=8192 the work is cut into blocks so that it fits:
one sequence at a time (`lax.map`), one attention head at a time, one expert
at a time. Gradients are ``jax.grad`` of `next_token_loss`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def from_program_params(params) -> dict:
    """The program's flax tree in float32, names unchanged."""
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                  params)


def for_config(config: dict):
    """``logits_fn(program params, ids)`` over the share of the model that a
    configuration file runs: what ``benchmark/drivers/train_lm.py`` asks of
    a family's reference."""
    sizes, cut = config["published"], config.get("model_overrides", {})
    share = {"first_expert": cut.get("first_expert", 0),
             "num_experts_held": cut.get("num_experts_held",
                                         sizes["num_experts"]),
             "vocab_size": cut.get("vocab_size", sizes["vocab_size"])}
    return lambda params, ids: forward(from_program_params(params), ids,
                                       sizes, share)


def _rms(x, w, eps):
    """The zero-centred norm: the weight is stored as its offset from 1."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _rotate(x, sizes):
    """x: (S, heads, D). The first ``partial_rotary_factor * D`` dims turn by
    position, dim i paired with dim i + half; the others pass."""
    s, _, d = x.shape
    rot = int(d * sizes["partial_rotary_factor"])
    half = rot // 2
    freq = 1.0 / sizes["rope_theta"] ** (jnp.arange(0, rot, 2) / rot)
    angle = jnp.arange(s)[:, None] * freq[None, :]            # (S, half)
    cos = jnp.cos(jnp.concatenate([angle, angle], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([angle, angle], -1))[:, None, :]
    turned, passed = x[..., :rot], x[..., rot:]
    swapped = jnp.concatenate([-turned[..., half:], turned[..., :half]], -1)
    return jnp.concatenate([turned * cos + swapped * sin, passed], -1)


def gated_attention(p, x, sizes):
    """x: (S, hidden) of one sequence -> (S, hidden)."""
    s = x.shape[0]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    qg = (x @ p["q_proj"]["kernel"]).reshape(s, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]["kernel"]).reshape(s, kv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(s, kv, d)
    q = _rotate(_rms(q, p["q_norm"]["weight"], eps), sizes)
    k = _rotate(_rms(k, p["k_norm"]["weight"], eps), sizes)
    allowed = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(i):
        scores = q[:, i] @ k[:, i // (heads // kv)].T / jnp.sqrt(1.0 * d)
        scores = jnp.where(allowed, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[:, i // (heads // kv)]

    out = jax.lax.map(one_head, jnp.arange(heads))            # (heads, S, D)
    out = jnp.swapaxes(out, 0, 1) * _sigmoid(gate)
    return out.reshape(s, heads * d) @ p["o_proj"]["kernel"]


def delta_rule(q, k, v, g, beta):
    """The gated delta rule of one sequence, position by position. q, k:
    (S, heads, dk); v: (S, heads, dv); g (log decay, <= 0) and beta (write
    strength): (S, heads). The state is (heads, dk, dv), zero at the start."""

    def position(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = state * jnp.exp(g_t)[:, None, None]
        held = jnp.einsum("hkv,hk->hv", state, k_t)
        write = (v_t - held) * beta_t[:, None]
        state = state + k_t[:, :, None] * write[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    start = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]))
    return jax.lax.scan(position, start, (q, k, v, g, beta))[1]


def gated_delta_net(p, x, sizes):
    """x: (S, hidden) of one sequence -> (S, hidden)."""
    s = x.shape[0]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps = sizes["linear_conv_kernel_dim"]
    mixed = x @ p["in_proj_qkvz"]["kernel"]
    qkv, z = mixed[:, :2 * hk * dk + hv * dv], mixed[:, 2 * hk * dk + hv * dv:]
    ba = x @ p["in_proj_ba"]["kernel"]
    b, a = ba[:, :hv], ba[:, hv:]
    # depthwise causal convolution: y_t = sum_j w[j] * x_{t - (taps-1) + j}
    before = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv], 0)
    conv = jnp.zeros_like(qkv)
    for j in range(taps):
        conv = conv + before[j:j + s] * p["conv1d"][j]
    qkv = _silu(conv)
    q = qkv[:, :hk * dk].reshape(s, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / jnp.sqrt(1.0 * dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    key_head = jnp.arange(hv) // (hv // hk)
    q, k = q[:, key_head], k[:, key_head]                     # (S, hv, dk)
    beta = _sigmoid(b)
    g = -jnp.exp(p["A_log"]) * _softplus(a + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)                          # (S, hv, dv)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + sizes["rms_norm_eps"])
    o = o * p["norm"] * _silu(z.reshape(s, hv, dv))
    return o.reshape(s, hv * dv) @ p["out_proj"]["kernel"]


def routed_experts(p, x, sizes, first_expert: int, num_held: int):
    """The part of the routed experts' result that experts ``first_expert ..
    first_expert + num_held - 1`` give, for x: (S, hidden). ``p`` holds the
    router over all experts and the HELD experts' weights, stacked."""
    top = sizes["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)          # (S, experts)
    kth = jnp.sort(probs, axis=-1)[:, -top][:, None]
    kept = jnp.where(probs >= kth, probs, 0.0)
    if sizes.get("norm_topk_prob", True):
        kept = kept / kept.sum(-1, keepdims=True)

    def one_expert(i, total):
        mid = _silu(x @ p["gate"][i]) * (x @ p["up"][i])
        weight = jax.lax.dynamic_index_in_dim(kept, first_expert + i, 1)
        return total + (mid @ p["down"][i]) * weight

    return jax.lax.fori_loop(0, num_held, one_expert, jnp.zeros_like(x))


def shared_expert(p, x):
    mid = _silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
    return (mid @ p["down"]["kernel"]) * _sigmoid(x @ p["shared_gate"]["kernel"])


def sparse_block(moe, shared, x, sizes, first_expert: int, num_held: int):
    return routed_experts(moe, x, sizes, first_expert, num_held) \
        + shared_expert(shared, x)


def forward_one(ref_params: dict, ids, sizes: dict, share: dict):
    """(S,) token ids of one sequence -> (S, share["vocab_size"]) logits."""
    eps = sizes["rms_norm_eps"]
    h = ref_params["embed"]["embedding"][ids]
    i = 0
    while f"layer{i}" in ref_params:
        layer = ref_params[f"layer{i}"]
        x = _rms(h, layer["input_norm"]["weight"], eps)
        if (i + 1) % sizes["full_attention_interval"] == 0:
            h = h + gated_attention(layer["gated_attn"], x, sizes)
        else:
            h = h + gated_delta_net(layer["gdn"], x, sizes)
        x = _rms(h, layer["post_norm"]["weight"], eps)
        h = h + sparse_block(layer["moe"], layer["shared_expert"], x, sizes,
                             share["first_expert"], share["num_experts_held"])
        i += 1
    h = _rms(h, ref_params["final_norm"]["weight"], eps)
    return (h @ ref_params["head"]["kernel"])[:, :share["vocab_size"]]


def forward(ref_params: dict, ids, sizes: dict, share: dict):
    """(B, S) int token ids -> (B, S, vocab) float32 logits, a sequence at
    a time."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: forward_one(ref_params, row, sizes, share), ids)


def next_token_loss(ref_params: dict, ids, sizes: dict, share: dict):
    """Mean cross-entropy of token t+1 given tokens <= t."""
    logits = forward(ref_params, ids, sizes, share)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()

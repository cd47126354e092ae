"""Operations of the Qwen3-Next family, in closed form from the sizes.

The benchmark's own numerators: no jaxpr walk, no XLA count. A later PR may
not move them. ``config`` is a configuration file: its ``published`` group
(the source's keys) and its ``model_overrides`` (what this chip runs of them:
``depth``, ``num_experts_held``, ``vocab_size``). `train_flops_per_token` and
`train_shape` are what ``benchmark/drivers/train_lm.py`` asks of a family.
"""

from __future__ import annotations


def _cut(config: dict):
    sizes, cut = config["published"], config.get("model_overrides", {})
    depth = cut.get("depth", sizes["num_hidden_layers"])
    full = depth // sizes["full_attention_interval"]
    return (sizes, depth, full, depth - full,
            cut.get("num_experts_held", sizes["num_experts"]),
            cut.get("vocab_size", sizes["vocab_size"]))


def matmul_weights_per_token(config: dict) -> float:
    """Weights that multiply one token's activation in a forward pass, by
    expectation where routing decides.

    A Gated DeltaNet layer: in_proj_qkvz h x (2 key + 2 value), in_proj_ba
    h x 2 value-heads, out_proj value x h. A full-attention layer: q_proj
    (query and gate) h x 2*heads*d, k_proj and v_proj h x kv*d each, o_proj
    heads*d x h. Every layer: the router h x experts, the shared expert
    3 x h x width and its gate h x 1, and of the routed experts
    ``top_k * held / experts`` of 3 x h x width: the share of a token's
    top-k assignments that land on this chip under balanced routing. The
    head: h x vocabulary rows held (padding columns are not model work).
    The token lookup, the norms and the depthwise convolution multiply no
    matrix."""
    s, depth, full, linear, held, vocab = _cut(config)
    h = s["hidden_size"]
    key = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    value = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    gdn = h * (2 * key + 2 * value) + h * 2 * s["linear_num_value_heads"] \
        + value * h
    qd = s["num_attention_heads"] * s["head_dim"]
    kvd = s["num_key_value_heads"] * s["head_dim"]
    attn = h * 2 * qd + 2 * h * kvd + qd * h
    expert = 3 * h * s["moe_intermediate_size"]
    sparse = h * s["num_experts"] \
        + 3 * h * s["shared_expert_intermediate_size"] + h \
        + s["num_experts_per_tok"] * held / s["num_experts"] * expert
    return linear * gdn + full * attn + depth * sparse + h * vocab


def delta_rule_flops_per_token(config: dict) -> float:
    """Forward FLOPs of the recurrence itself, as the equations state it: per
    value head and position three products with the (key x value) state,
    ``S^T k``, ``k u^T`` and ``S^T q``, 2 * key * value each. The chunked
    form's extra arithmetic (the solve, the in-chunk scores) is the
    algorithm's, like a flash kernel's recomputation, and is not counted."""
    s, _, _, linear, _, _ = _cut(config)
    return linear * s["linear_num_value_heads"] * 3 * 2.0 \
        * s["linear_key_head_dim"] * s["linear_value_head_dim"]


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward per trained token: 6 per matmul weight, 3 x the
    delta rule's forward, and causal attention of the full layers as
    ``benchmark/flops/gpt2.py`` counts it (QK^T and PV over the causal half,
    x3 with the backward: 6 * S * heads * head_dim a layer). Recomputation
    (remat, the flash backward) is never counted."""
    s, _, full, _, _, _ = _cut(config)
    return 6.0 * matmul_weights_per_token(config) \
        + 3.0 * delta_rule_flops_per_token(config) \
        + 6.0 * full * seq_len * s["num_attention_heads"] * s["head_dim"]


def train_shape(config: dict, batch: int, seq_len: int,
                attention: str) -> dict:
    """The flash calls of one step, for the three flash readers: the full
    layers only, key-value heads repeated to the query heads' count."""
    s, _, full, _, _, _ = _cut(config)
    return dict(batch=batch, seq_len=seq_len, heads=s["num_attention_heads"],
                head_dim=s["head_dim"], layers=full, attention=attention)


def moe_assignments_per_token(config: dict) -> int:
    """Top-k assignments a token makes over all layers here (held or not)."""
    s, depth, _, _, _, _ = _cut(config)
    return depth * s["num_experts_per_tok"]

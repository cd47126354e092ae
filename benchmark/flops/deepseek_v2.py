"""Operations and bytes of the DeepSeek-V2 family, in closed form from the
sizes.

The benchmark's own numerators: no jaxpr walk, no XLA count. A later PR may
not move them. ``config`` is a configuration file: its ``published`` group
(the source's keys) and its ``model_overrides`` (what this chip runs of them:
``depth``, ``num_experts_held``, ``vocab_size``).
"""

from __future__ import annotations


def _cut(config: dict):
    sizes, cut = config["published"], config.get("model_overrides", {})
    depth = cut.get("depth", sizes["num_hidden_layers"])
    dense = min(depth, sizes["first_k_dense_replace"])
    return (sizes, depth, dense, depth - dense,
            cut.get("num_experts_held", sizes["n_routed_experts"]),
            cut.get("vocab_size", sizes["vocab_size"]))


def attention_weights(sizes: dict) -> int:
    """W_dq, W_uq, W_dkv, W_ukv, W_o of one layer."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rq, rkv = (sizes["v_head_dim"], sizes["q_lora_rank"],
                   sizes["kv_lora_rank"])
    return h * rq + rq * heads * (nope + rope) + h * (rkv + rope) \
        + rkv * heads * (nope + dv) + heads * dv * h


def parameters(config: dict) -> int:
    """Parameters at rest on this chip (norm weights left out)."""
    s, depth, dense, sparse, held, vocab = _cut(config)
    h, expert = s["hidden_size"], 3 * s["hidden_size"] \
        * s["moe_intermediate_size"]
    return depth * attention_weights(s) \
        + dense * 3 * h * s["intermediate_size"] \
        + sparse * (h * s["n_routed_experts"]
                    + s["n_shared_experts"] * expert + held * expert) \
        + 2 * vocab * h


def matmul_weights_per_token(config: dict) -> float:
    """Weights that multiply one token's activation in a forward pass, by
    expectation where routing decides: of the routed experts
    ``num_experts_per_tok * held / n_routed_experts`` of one expert's three
    matrices (the share of a token's assignments that land on this chip
    under balanced routing); the head over the vocabulary rows held; the
    token lookup and the norms multiply no matrix."""
    s, depth, dense, sparse, held, vocab = _cut(config)
    h, expert = s["hidden_size"], 3 * s["hidden_size"] \
        * s["moe_intermediate_size"]
    routed = s["num_experts_per_tok"] * held / s["n_routed_experts"] * expert
    return depth * attention_weights(s) \
        + dense * 3 * h * s["intermediate_size"] \
        + sparse * (h * s["n_routed_experts"]
                    + s["n_shared_experts"] * expert + routed) \
        + vocab * h


def prefill_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward FLOPs a prompt token of a ``seq_len`` prompt costs: 2 per
    matmul weight, plus the expanded causal attention over half the square:
    ``heads * (nope + rope + v) * seq_len`` a layer."""
    s, depth, *_ = _cut(config)
    per_score = s["qk_nope_head_dim"] + s["qk_rope_head_dim"] \
        + s["v_head_dim"]
    return 2.0 * matmul_weights_per_token(config) \
        + depth * s["num_attention_heads"] * per_score * seq_len


def mla_decode_call_cost(sizes: dict, rows: int, live_tokens: float,
                         bytes_per_el: int = 2) -> dict:
    """What one call of the absorbed decode read (`ops.mla_paged_attention`,
    one layer, one step) needs at least, for ``rows`` slot rows holding
    ``live_tokens`` cached positions in all: per cached position and head a
    score over the 576-wide row and a weighted sum over its first 512, 2
    FLOPs each a number; every live row read once, every head's query read
    and output written once."""
    heads = sizes["num_attention_heads"]
    width = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    value = sizes["kv_lora_rank"]
    return {"flops": 2.0 * heads * (width + value) * live_tokens,
            "bytes": (live_tokens * width
                      + rows * heads * (width + value)) * bytes_per_el}


def moe_assignments_per_token(config: dict) -> int:
    """Top-k assignments a token makes over all layers here (held or not)."""
    s, _, _, sparse, _, _ = _cut(config)
    return sparse * s["num_experts_per_tok"]

"""Operations and bytes of the SDAR family, in closed form from the sizes.

The benchmark's own numerators: no jaxpr walk, no XLA count. A later PR may
not move them. ``config`` is a configuration file: its ``published`` group
(the source's keys) and its ``model_overrides`` (what this chip runs of them:
``depth``).
"""

from __future__ import annotations


def _depth(config: dict) -> int:
    return config.get("model_overrides", {}).get(
        "depth", config["published"]["num_hidden_layers"])


def attention_weights(sizes: dict) -> int:
    """W_q, W_k, W_v, W_o of one layer."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    return h * heads * d + 2 * h * kv_heads * d + heads * d * h


def layer_weights(sizes: dict) -> int:
    """One layer at rest: attention, the router, every expert (the norms'
    weight vectors left out)."""
    h = sizes["hidden_size"]
    return attention_weights(sizes) + h * sizes["num_experts"] \
        + sizes["num_experts"] * 3 * h * sizes["moe_intermediate_size"]


def parameters(config: dict) -> int:
    """Parameters at rest on this chip (norm weights left out): ``depth``
    whole layers, the embedding and the untied head."""
    sizes = config["published"]
    return _depth(config) * layer_weights(sizes) \
        + 2 * sizes["vocab_size"] * sizes["hidden_size"]


def matmul_weights_per_token(config: dict) -> int:
    """Weights that multiply one position's activation in a forward pass:
    attention, the router, ``num_experts_per_tok`` experts' three matrices,
    and the head over the whole vocabulary."""
    sizes = config["published"]
    h = sizes["hidden_size"]
    return _depth(config) * (
        attention_weights(sizes) + h * sizes["num_experts"]
        + sizes["num_experts_per_tok"] * 3 * h
        * sizes["moe_intermediate_size"]) + sizes["vocab_size"] * h


def window_attention_call_cost(sizes: dict, rows: int, window: int,
                               live_tokens: float,
                               bytes_per_el: int = 2) -> dict:
    """What one call of the window read (`ops.paged_attention` with W =
    ``window`` query positions a row, one layer, one step) needs at least,
    whichever form computes it, for ``rows`` slot rows holding
    ``live_tokens`` committed positions in all: per query position and query
    head a score over a head's ``head_dim`` numbers and a weighted sum over
    as many, 2 FLOPs each a number, against every committed position of its
    row and the window's own ``window`` fresh ones; every committed
    position's key and value row (all key/value heads) read once a row, not
    once a query position; every query read and every answer written once;
    the fresh rows read once."""
    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    seen = window * live_tokens + rows * window * window
    return {"flops": 2.0 * 2.0 * heads * d * seen,
            "bytes": (2.0 * live_tokens * kv_heads * d
                      + rows * window * 2 * heads * d
                      + rows * window * 2 * kv_heads * d) * bytes_per_el}


def moe_assignments_per_token(config: dict) -> int:
    """Top-k assignments a position makes over all layers here."""
    return _depth(config) * config["published"]["num_experts_per_tok"]

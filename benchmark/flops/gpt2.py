"""Operations and bytes of the GPT-2 family, in closed form from the sizes.

The benchmark's own numerators: no jaxpr walk, no XLA count. A later PR may
not move them. ``sizes`` is the ``published`` group of a configuration file.
"""

from __future__ import annotations


def _dims(sizes: dict):
    h = sizes["n_embd"]
    inner = sizes.get("n_inner") or 4 * h
    return h, sizes["n_layer"], sizes["n_head"], inner, sizes["vocab_size"]


def matmul_weights(sizes: dict) -> int:
    """Every weight that multiplies an activation: per layer qkv (3h^2),
    attention output (h^2) and the two MLP matrices (2*h*inner), plus the
    tied head (vocab*h). Embedding lookups, biases and norms do none."""
    h, layers, _, inner, vocab = _dims(sizes)
    return layers * (4 * h * h + 2 * h * inner) + vocab * h


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward + backward per trained token: 6 per matmul weight, plus
    causal attention. QK^T and PV are 2*2*S*h per token per layer over the
    full square; causal needs half: 2*S*h forward, x3 with the backward =
    6*L*S*h. Recomputation (flash backward, remat) is never counted."""
    h, layers, _, _, _ = _dims(sizes)
    return 6.0 * matmul_weights(sizes) + 6.0 * layers * seq_len * h


def flash_call_cost(batch: int, seq_len: int, heads: int, head_dim: int,
                    backward: bool, bytes_per_el: int = 2) -> dict:
    """What one causal flash-attention call needs at least.

    forward: QK^T and PV over the causal half: 2 * 2*B*H*S*S*D / 2 FLOPs;
    reads q, k, v and writes o once: 4*B*S*H*D elements. backward: dq, dk,
    dv need 4 matmuls of that size plus the recomputed QK^T is NOT counted
    (recomputation): 2x the forward's FLOPs; reads q, k, v, o, do and writes
    dq, dk, dv: 8*B*S*H*D elements."""
    per_matmul = 2.0 * batch * heads * seq_len * seq_len * head_dim / 2.0
    tensor = batch * seq_len * heads * head_dim * bytes_per_el
    if backward:
        return {"flops": 4.0 * per_matmul, "bytes": 8.0 * tensor}
    return {"flops": 2.0 * per_matmul, "bytes": 4.0 * tensor}


def decode_step_bytes(sizes: dict, rows: int, cache_len: int,
                      kv_bytes_per_el: int, weight_bytes_per_el: int) -> float:
    """Least bytes one decode step over a full cache must read: every
    weight once and every row's K and V once."""
    h, layers, _, _, _ = _dims(sizes)
    kv = 2.0 * layers * rows * cache_len * h * kv_bytes_per_el
    return matmul_weights(sizes) * weight_bytes_per_el + kv

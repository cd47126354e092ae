"""Plain SDAR (``model_type: sdar_moe``,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): the forward pass under the
block mask, and generation by diffusion over blocks, as published.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes): grouped-query
attention over the whole sequence with an explicit (S, S) mask, the top-8
rule written out with ``jnp`` alone, the experts as a loop over all 128 with
a mask; no kernel, no cache, no batching, none of the program's modules.
``sizes`` is the ``published`` group of the configuration file; ``share`` says
what this chip holds of a layer, which is all of it (`share_of`): one chip is
a pipeline stage and shares no layer.

The mask (``B = block_length``): key ``j`` is visible to query ``i`` iff
``j // B <= i // B``. The logits at a position are for the token AT that
position. `generate` is the family's static low-confidence schedule at
temperature 0: a block opens holding what is known of it (the prompt's
remainder in the first) and MASK elsewhere; a denoise step forwards the whole
sequence so far and, among the positions still masked, the ``B / T`` of the
highest confidence ``max softmax(logits_i)`` (ties to the lower position)
take ``argmax logits_i``; when none is masked the block is final and the next
opens. Whether a position is masked is a bit beside the ids, never ``id ==
MASK``: a prompt may hold that id.

Departures from the source, each listed in the configuration's ``assumed``:

* ``block_length`` 4 and the schedule are the family's convention (the
  catalog's row gives neither);
* the router's balance loss is training's and is left out;
* one thing is taken from the program, the *layout of its weights*
  (`from_program_params`): names and the column order of the projections
  (query heads side by side, head n reading key head ``n // 8``).

For the chip check the work is cut so that it fits beside the served state:
an expert at a time, and `layer_by_layer` takes one layer's weights at a time
so that a caller can cast them up from bf16 one layer at a time (one layer's
128 experts are 2.4 GB in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def from_program_params(params) -> dict:
    """The program's flax tree in float32, names unchanged."""
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                  params)


def share_of(config: dict) -> dict:
    """The whole layer: every expert, every head, the whole vocabulary."""
    sizes = config["published"]
    return {"num_experts_held": sizes["num_experts"],
            "vocab_size": sizes["vocab_size"]}


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotate(x, positions, theta: float):
    """x: (S, heads, D), dim i paired with dim i + D / 2, all D dims."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq      # (S, D/2)
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    swapped = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + swapped * jnp.sin(angle)


def visible(seq_len: int, block_length: int):
    """(S, S): may query i (rows) see key j (columns)?"""
    at = jnp.arange(seq_len) // block_length
    return at[None, :] <= at[:, None]


def attention(p, x, sizes, block_length: int):
    """x: (S, hidden) of one sequence -> (S, hidden)."""
    s = x.shape[0]
    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    positions = jnp.arange(s)
    q = (x @ p["q_proj"]["kernel"]).reshape(s, heads, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(s, kv_heads, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(s, kv_heads, d)
    q = _rotate(_rms(q, p["q_norm"]["weight"], eps), positions,
                sizes["rope_theta"])
    k = _rotate(_rms(k, p["k_norm"]["weight"], eps), positions,
                sizes["rope_theta"])
    # query head n reads key head n // (heads / kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(visible(s, block_length)[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, heads * d) @ p["o_proj"]["kernel"]


def routing_weights(probs, sizes):
    """(S, experts) router probabilities -> (S, experts) weights: zero
    outside a token's ``num_experts_per_tok`` largest (a tie to the lower
    id: the first occurrence of the maximum, taken one at a time), the
    chosen ones renormalised to sum 1 (``norm_topk_prob``)."""
    def pick(_, state):
        left, chosen = state
        best = jnp.argmax(left, axis=-1)
        hit = jnp.arange(probs.shape[-1])[None, :] == best[:, None]
        return jnp.where(hit, -jnp.inf, left), chosen | hit

    chosen = jax.lax.fori_loop(0, sizes["num_experts_per_tok"], pick,
                               (probs, jnp.zeros(probs.shape, bool)))[1]
    weights = jnp.where(chosen, probs, 0.0)
    if sizes.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    return weights


def experts(p, x, sizes):
    """The expert layer on x: (S, hidden). ``p`` holds the router and the
    experts' weights, stacked; an expert at a time, every token through it,
    its result weighted (zero for the tokens that did not choose it)."""
    weights = routing_weights(jax.nn.softmax(x @ p["router"], axis=-1), sizes)

    def one_expert(i, total):
        # cast up here, an expert at a time: `layer_by_layer` hands the
        # stacked experts over in the program's dtype
        gate, up_, down = (jnp.asarray(p[k][i], jnp.float32)
                           for k in ("gate", "up", "down"))
        mid = _silu(x @ gate) * (x @ up_)
        weight = jax.lax.dynamic_index_in_dim(weights, i, 1)
        return total + (mid @ down) * weight

    return jax.lax.fori_loop(0, p["gate"].shape[0], one_expert,
                             jnp.zeros_like(x))


def layer(p, h, sizes, block_length: int):
    """``h += attention(norm(h)); h += experts(norm(h))`` on (S, hidden)."""
    eps = sizes["rms_norm_eps"]
    h = h + attention(p["attn"], _rms(h, p["input_norm"]["weight"], eps),
                      sizes, block_length)
    return h + experts(p["moe"], _rms(h, p["post_norm"]["weight"], eps),
                       sizes)


HEAD_CHUNKS = 8


def head(ref_params: dict, h, sizes: dict):
    """The final norm and the untied head; the head's columns an eighth at a
    time, each cast up where it is used (`layer_by_layer` hands the kernel
    over in the program's dtype: whole in float32 it is 1.2 GB)."""
    h = _rms(h, ref_params["final_norm"]["weight"], sizes["rms_norm_eps"])
    kernel = ref_params["head"]["kernel"]
    width = kernel.shape[1]
    if width % HEAD_CHUNKS:
        return (h @ jnp.asarray(kernel, jnp.float32))[:, :sizes["vocab_size"]]
    chunk = width // HEAD_CHUNKS

    def some(i, out):
        columns = jnp.asarray(jax.lax.dynamic_slice_in_dim(
            kernel, i * chunk, chunk, 1), jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(out, h @ columns,
                                                   i * chunk, 1)

    out = jax.lax.fori_loop(0, HEAD_CHUNKS, some,
                            jnp.zeros((h.shape[0], width), jnp.float32))
    return out[:, :sizes["vocab_size"]]


def forward(ref_params: dict, ids, masked_bits, sizes: dict,
            block_length: int, mask_id: int):
    """(S,) token ids and (S,) bits of one sequence -> (S, vocab) logits
    under the block mask; a position whose bit is set holds MASK."""
    with jax.default_matmul_precision("highest"):
        h = ref_params["embed"]["embedding"][
            jnp.where(masked_bits, mask_id, ids)]
        i = 0
        while f"layer{i}" in ref_params:
            h = layer(ref_params[f"layer{i}"], h, sizes, block_length)
            i += 1
        return head(ref_params, h, sizes)


_USED = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "rope_theta", "num_experts_per_tok", "norm_topk_prob",
         "vocab_size")


@functools.lru_cache(maxsize=None)
def _programs(used: tuple, block_length: int, mask_id: int):
    """`layer_by_layer`'s three jitted calls, made once for a set of sizes:
    a check replays dozens of steps through them."""
    sizes = dict(zip(_USED, used))

    def up(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32), tree)

    @jax.jit
    def embed(table, ids, masked_bits):
        # the rows first, then the cast: the same numbers, no float32 table
        return up(table[jnp.where(masked_bits, mask_id, ids)])

    @jax.jit
    def run_layer(p, h):
        stacked = {k: v for k, v in p["moe"].items() if k != "router"}
        p = up({**p, "moe": {"router": p["moe"]["router"]}})
        p["moe"].update(stacked)         # cast up an expert at a time
        with jax.default_matmul_precision("highest"):
            return layer(p, h, sizes, block_length)

    @functools.partial(jax.jit, static_argnames=("count",))
    def run_head(norm, kernel, h, start, count):
        if count is not None:
            h = jax.lax.dynamic_slice_in_dim(h, start, count, 0)
        with jax.default_matmul_precision("highest"):
            return head({"final_norm": up(norm), "head": kernel}, h, sizes)

    return embed, run_layer, run_head


def layer_by_layer(program_params, ids, masked_bits, sizes: dict,
                   block_length: int, mask_id: int, rows=None):
    """`forward` for a caller that cannot hold the float32 tree: each
    layer's weights are cast up from the program's tree, used by one jitted
    call and dropped (the stacked experts an expert at a time). ``rows`` =
    (start, count) returns only those positions' logits. What is computed is
    `forward`'s, operation for operation."""
    embed, run_layer, run_head = _programs(
        tuple(sizes.get(k, True) for k in _USED), block_length, mask_id)
    h = embed(program_params["embed"]["embedding"], jnp.asarray(ids),
              jnp.asarray(masked_bits))
    i = 0
    while f"layer{i}" in program_params:
        h = run_layer(program_params[f"layer{i}"], h)
        i += 1
    start, count = (0, None) if rows is None else (rows[0], int(rows[1]))
    return run_head(program_params["final_norm"], program_params["head"], h,
                    jnp.int32(start), count)


def choose(logits, masked, count: int):
    """One denoise step's choice over a block: ``logits`` (B, vocab),
    ``masked`` (B,) bits. Returns (the positions that are unmasked now, a
    (B,) bool; every position's argmax; every position's confidence ``max
    softmax``): the ``count`` masked positions of the highest confidence,
    ties to the lower position."""
    logits = np.asarray(logits, np.float64)
    shifted = logits - logits.max(-1, keepdims=True)
    confidence = 1.0 / np.exp(shifted).sum(-1)
    order = sorted((i for i in range(len(masked)) if masked[i]),
                   key=lambda i: (-confidence[i], i))
    chosen = np.zeros(len(masked), bool)
    chosen[order[:count]] = True
    return chosen, logits.argmax(-1), confidence


def generate(ref_params: dict, prompt, want: int, block_length: int,
             denoising_steps: int, sizes: dict, mask_id: int):
    """Point by point what the configuration's ``assumed`` says. Returns
    (the ``want`` tokens after the prompt, and for each the denoise step of
    its block, from 0, at which it was unmasked). Every step is one full
    forward over everything so far (`layer_by_layer`: `forward`'s operations,
    its three programs compiled once a width; the sequence is padded to a
    width of whole 64s with positions no earlier block can see)."""
    b, prompt = block_length, np.asarray(prompt, np.int32)
    per_step = b // denoising_steps
    known = (len(prompt) // b) * b
    ids = prompt.copy()
    tokens, steps = [], []
    while len(tokens) < want:
        start = len(ids) if tokens else known
        held = ids[start:]                    # the prompt's remainder, once
        ids = np.concatenate([ids[:start], held,
                              np.zeros(b - len(held), np.int32)])
        masked = np.arange(b) >= len(held)
        at_step = np.full(b, -1, np.int64)
        step = 0
        width = -(-len(ids) // 64) * 64
        while masked.any():
            padded = np.zeros(width, np.int32)
            bits = np.zeros(width, bool)
            padded[:len(ids)], bits[start:start + b] = ids, masked
            logits = np.asarray(layer_by_layer(
                ref_params, padded, bits, sizes, b, mask_id,
                rows=(start, b)))
            chosen, best, _ = choose(logits, masked, per_step)
            ids[start:][chosen] = best[chosen]
            at_step[chosen] = step
            masked &= ~chosen
            step += 1
        tokens.extend(int(t) for t in ids[start + len(held):])
        steps.extend(int(s) for s in at_step[len(held):])
    return np.asarray(tokens[:want], np.int32), np.asarray(steps[:want])

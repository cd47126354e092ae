"""Plain DeepSeek-V2: the forward pass of one chip's share, as published
(``model_type: deepseek_v2``, arXiv:2405.04434; the modelling code beside the
hub's ``config.json``).

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes): the EXPANDED latent
attention at every position (every head's 128 + 64 wide key and 128 wide value
built from the latent, one softmax a head), the group-limited routing rule
written out with ``jnp`` alone, the experts as a loop over the held experts
with a mask; no kernel, no cache, no batching, no absorbed products, none of
the program's modules. ``sizes`` is the ``published`` group of the
configuration file; ``share`` says what this chip holds: ``first_expert``,
``num_experts_held`` and ``vocab_size`` (ids and logits are over the slice).
What the absent experts would add is left out here as in the program.

Departures from the source, each noted where it is made:

* the rotary pairs: the source stores a head's 64 rotary dims interleaved
  (``x0 y0 x1 y1 ..``) and un-interleaves them before a rotate-half; this
  file and the program keep them in the un-interleaved order (dim i paired
  with dim i + 32), which is the same function of a permuted weight, and
  weights here are random from a seed;
* ``seq_aux`` and the balance losses are training's and are left out;
* one thing is taken from the program, the *layout of its weights*
  (`from_program_params`): names, and the column order of the fused
  projections ([nope | rope] per query head, [latent | rope key],
  [key-nope | value] per head).

For the chip check the work is cut so that it fits: a sequence at a time,
attention in blocks of heads (`HEAD_BLOCK`: 128 heads x 4104^2 x 4 B of
scores would be 8.6 GB), an expert at a time, and `layer_by_layer` takes one
layer's weights at a time so that a caller can cast them up from bf16 one
layer at a time (one expert layer is 2.7 GB in float32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 4


def from_program_params(params) -> dict:
    """The program's flax tree in float32, names unchanged."""
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                  params)


def share_of(config: dict) -> dict:
    sizes, cut = config["published"], config.get("model_overrides", {})
    return {"first_expert": cut.get("first_expert", 0),
            "num_experts_held": cut.get("num_experts_held",
                                        sizes["n_routed_experts"]),
            "vocab_size": cut.get("vocab_size", sizes["vocab_size"])}


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


# -- YaRN ----------------------------------------------------------------------

def yarn_inv_freq(sizes: dict):
    """The 32 rotary frequencies under YaRN: dimension pairs that turn more
    than ``beta_fast`` times over the original context keep their frequency
    (extrapolated), pairs that turn fewer than ``beta_slow`` times have it
    divided by ``factor`` (interpolated), a linear ramp between."""
    rope, dim = sizes["rope_scaling"], sizes["qk_rope_head_dim"]
    base, original = sizes["rope_theta"], \
        rope["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp                    # 1 = extrapolated, 0 = interpolated
    return plain / rope["factor"] * (1.0 - keep) + plain * keep


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(sizes: dict) -> float:
    """``(nope + rope)^-0.5`` times ``mscale(factor, mscale_all_dim)^2``; the
    cos/sin tables carry ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, which is 1 for the published pair 0.707 / 0.707."""
    rope = sizes["rope_scaling"]
    m = yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rotate(x, positions, sizes):
    """x: (S, ..., 64), dim i paired with dim i + 32 (see the module note)."""
    rope = sizes["rope_scaling"]
    table_scale = yarn_mscale(rope["factor"], rope["mscale"]) \
        / yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    angle = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(sizes)
    angle = jnp.concatenate([angle, angle], -1)                # (S, 64)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    cos = (jnp.cos(angle) * table_scale).reshape(shape)
    sin = (jnp.sin(angle) * table_scale).reshape(shape)
    half = x.shape[-1] // 2
    swapped = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + swapped * sin


# -- layers --------------------------------------------------------------------

def latent_attention(p, x, sizes):
    """x: (S, hidden) of one sequence -> (S, hidden), the expanded form."""
    s = x.shape[0]
    heads, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    positions = jnp.arange(s)
    c_q = _rms(x @ p["q_a_proj"]["kernel"], p["q_a_norm"]["weight"], eps)
    q = (c_q @ p["q_b_proj"]["kernel"]).reshape(s, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], positions, sizes)
    kv = x @ p["kv_a_proj"]["kernel"]                          # (S, 512 + 64)
    c = _rms(kv[:, :rank], p["kv_a_norm"]["weight"], eps)
    k_pe = _rotate(kv[:, rank:], positions, sizes)             # ONE rope key
    expanded = (c @ p["kv_b_proj"]["kernel"]).reshape(s, heads, nope + dv)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    allowed = positions[:, None] >= positions[None, :]
    scale = softmax_scale(sizes)

    block = min(HEAD_BLOCK, heads)
    if heads % block:
        raise ValueError(f"{heads} heads are not whole blocks of {block}")

    def head_block(first):
        take = lambda t: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, first, block, 1)
        scores = (jnp.einsum("shd,thd->hst", take(q_nope), take(k_nope))
                  + jnp.einsum("shd,td->hst", take(q_pe), k_pe)) * scale
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), take(v))

    out = jax.lax.map(head_block, jnp.arange(0, heads, block))
    out = jnp.moveaxis(out, 0, 1).reshape(s, heads, dv)   # (S, blocks, b, dv)
    return out.reshape(s, heads * dv) @ p["o_proj"]["kernel"]


def gated_mlp(p, x):
    mid = _silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
    return mid @ p["down"]["kernel"]


def routing_weights(probs, sizes):
    """(S, experts) router probabilities -> (S, experts) weights: zero
    outside a token's chosen experts, ``routed_scaling_factor * p`` on them
    (not renormalised: ``norm_topk_prob`` false).

    ``group_limited_greedy``: the experts lie in ``n_group`` groups of
    consecutive ids; a group's score is its largest probability; the
    ``topk_group`` best groups stay and every other group's probabilities
    are set to 0; of what remains the ``num_experts_per_tok`` largest are
    the token's experts. A tie goes to the lower id, at both steps (the
    first occurrence of the maximum, taken one at a time)."""
    s, experts = probs.shape
    groups = sizes["n_group"]

    def largest(values, count):
        """Mask of the ``count`` largest per row, lower index first on ties."""
        def pick(_, state):
            left, chosen = state
            best = jnp.argmax(left, axis=-1)
            hit = jnp.arange(values.shape[-1])[None, :] == best[:, None]
            return jnp.where(hit, -jnp.inf, left), chosen | hit
        return jax.lax.fori_loop(
            0, count, pick, (values, jnp.zeros(values.shape, bool)))[1]

    group_score = probs.reshape(s, groups, experts // groups).max(-1)
    group_stays = largest(group_score, sizes["topk_group"])
    remaining = jnp.where(
        jnp.repeat(group_stays, experts // groups, axis=1), probs, 0.0)
    chosen = largest(remaining, sizes["num_experts_per_tok"])
    weights = jnp.where(chosen, remaining, 0.0)
    if sizes.get("norm_topk_prob", False):
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * sizes["routed_scaling_factor"]


def routed_experts(p, x, sizes, first_expert: int, num_held: int):
    """The part of the routed experts' result that experts ``first_expert ..
    first_expert + num_held - 1`` give, for x: (S, hidden). ``p`` holds the
    router over all experts and the HELD experts' weights, stacked."""
    weights = routing_weights(jax.nn.softmax(x @ p["router"], axis=-1), sizes)

    def one_expert(i, total):
        # cast up here, an expert at a time: `layer_by_layer` hands the
        # stacked experts over in the program's dtype (20 of them are 1.9 GB
        # in float32)
        gate, up_, down = (jnp.asarray(p[k][i], jnp.float32)
                           for k in ("gate", "up", "down"))
        mid = _silu(x @ gate) * (x @ up_)
        weight = jax.lax.dynamic_index_in_dim(weights, first_expert + i, 1)
        return total + (mid @ down) * weight

    return jax.lax.fori_loop(0, num_held, one_expert, jnp.zeros_like(x))


def layer(p, h, sizes, share, dense: bool):
    """One decoder layer on (S, hidden): ``h += attention(norm(h)); h +=
    mlp(norm(h))``, the mlp dense for the leading layers, else the held
    routed experts' part plus the shared experts (one gated MLP of
    ``n_shared_experts * moe_intermediate_size``)."""
    eps = sizes["rms_norm_eps"]
    h = h + latent_attention(
        p["attn"], _rms(h, p["input_norm"]["weight"], eps), sizes)
    x = _rms(h, p["post_norm"]["weight"], eps)
    if dense:
        return h + gated_mlp(p["dense_mlp"], x)
    return h + routed_experts(p["moe"], x, sizes, share["first_expert"],
                              share["num_experts_held"]) \
        + gated_mlp(p["shared_expert"], x)


def head(ref_params: dict, h, sizes: dict, share: dict):
    h = _rms(h, ref_params["final_norm"]["weight"], sizes["rms_norm_eps"])
    return (h @ ref_params["head"]["kernel"])[:, :share["vocab_size"]]


def forward_one(ref_params: dict, ids, sizes: dict, share: dict):
    """(S,) token ids of one sequence -> (S, share["vocab_size"]) logits."""
    h = ref_params["embed"]["embedding"][ids]
    i = 0
    while f"layer{i}" in ref_params:
        h = layer(ref_params[f"layer{i}"], h, sizes, share,
                  dense=i < sizes["first_k_dense_replace"])
        i += 1
    return head(ref_params, h, sizes, share)


def forward(ref_params: dict, ids, sizes: dict, share: dict):
    """(B, S) int token ids -> (B, S, vocab) float32 logits, a sequence at
    a time."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: forward_one(ref_params, row, sizes, share), ids)


def layer_by_layer(program_params, ids, sizes: dict, share: dict,
                   rows=None):
    """`forward_one` for a caller that cannot hold the float32 tree: each
    layer's weights are cast up from the program's tree, used by one jitted
    call and dropped. ``ids`` (S,); ``rows`` = (start, count) returns only
    those positions' logits. What is computed is `forward_one`'s, operation
    for operation."""
    def up(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32), tree)

    @jax.jit
    def embed(table, ids):
        return up(table)[ids]

    def run_layer(p, h, dense):
        stacked = {k: v for k, v in p.get("moe", {}).items() if k != "router"}
        p = up({**p, "moe": {"router": p["moe"]["router"]}} if stacked else p)
        if stacked:
            p["moe"].update(stacked)     # cast up an expert at a time
        with jax.default_matmul_precision("highest"):
            return layer(p, h, sizes, share, dense)

    run = {d: jax.jit(lambda p, h, d=d: run_layer(p, h, d))
           for d in (True, False)}

    @jax.jit
    def run_head(norm, kernel, h):
        with jax.default_matmul_precision("highest"):
            return head({"final_norm": up(norm), "head": up(kernel)}, h,
                        sizes, share)

    h = embed(program_params["embed"]["embedding"], ids)
    i = 0
    while f"layer{i}" in program_params:
        h = run[i < sizes["first_k_dense_replace"]](
            program_params[f"layer{i}"], h)
        i += 1
    if rows is not None:
        h = jax.lax.dynamic_slice_in_dim(h, rows[0], rows[1], 0)
    return run_head(program_params["final_norm"], program_params["head"], h)

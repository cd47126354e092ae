"""The layer this family's cell is for, alone, at the timed shape.

``rule_rel_diff``: the program's chunked gated delta rule, called as
`models/qwen3_next.py` calls it, against the reference's position-by-position
rule (`reference/qwen3_next.py::delta_rule`, float32 at ``highest``) on seeded
inputs of one sequence of the mix's ``seq_len`` with the published value
heads and head sizes, as ``||got - want|| / ||want||``. The decay is WEAK
(g in -0.02..0 a position: the state remembers hundreds of positions). At the
seeded initial weights the model's own decay is e^-1..e^-20 a position and
the state is nearly memoryless, so the whole model's logits cannot tell a
bf16 state in the rule from a float32 one (PERF.md section 2); here they are
a hundred times apart.
"""

from __future__ import annotations


def rule_inputs(sizes: dict, seq_len: int, seed: int):
    """(q, k, v, g, beta) of one sequence as the mixer hands them to the
    rule: q and k l2-normalised (q over sqrt(dk) besides), key heads already
    repeated to the value heads."""
    import jax
    import jax.numpy as jnp

    heads = sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.sqrt((x * x).sum(-1, keepdims=True))  # noqa: E731
    q = unit(jax.random.normal(keys[0], (seq_len, heads, dk))) / dk ** 0.5
    k = unit(jax.random.normal(keys[1], (seq_len, heads, dk)))
    v = jax.random.normal(keys[2], (seq_len, heads, dv))
    g = -0.02 * jax.random.uniform(keys[3], (seq_len, heads))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (seq_len, heads)))
    return q, k, v, g, beta


def layer_checks(config: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.reference.qwen3_next import delta_rule
    from distributed_pytorch_training_tpu.models.qwen3_next import (
        RULE_HEAD_BLOCK,
    )
    from distributed_pytorch_training_tpu.ops.gated_delta_rule import (
        gated_delta_rule,
    )

    @jax.jit
    def rule_rel_diff(*inputs):
        got = gated_delta_rule(*(x[None] for x in inputs),
                               head_block=RULE_HEAD_BLOCK)[0]
        with jax.default_matmul_precision("highest"):
            want = delta_rule(*inputs)
        return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

    inputs = rule_inputs(config["published"], int(traffic["seq_len"]), seed)
    return {"rule_rel_diff": float(rule_rel_diff(*inputs))}

"""`models.moe.take_rows` / `sum_rows`: the 0/1 operator that takes a table's
rows to sorted assignment rows, and its transpose, both as gathers.

Held against the forms they replaced, ``where(in_group, table[index], 0)``
and ``zeros.at[index].add(rows)``, forward and through `jax.vjp` both ways
(each is the other's transpose), over the windows the walk of
`HeldExpertsMoe` makes: the first quarter, a quarter past it (the `lax.cond`'s
other branch, which no cell of the benchmark reaches), no held assignment at
all, every token on one expert, and rows past the last group that hold NaN
(a grouped product leaves them unwritten on a TPU) and must reach neither a
result nor a gradient.

Tolerances. `take_rows` moves rows and rounds nothing: bitwise, any dtype.
`sum_rows` adds a token's rows in float32 and rounds once, where the
scatter-add added them one by one in the rows' dtype: in float32 the two
differ by the order of at most ``top_k`` additions (a few ulp of the largest
partial sum: 4e-6 of the result's scale); in bf16 the operator must lie
within ONE rounding of the exact sum (2**-8 relative to each element's own
scale: |exact| + a term's size), which the scatter-add's up to ``top_k``
roundings do not, so against the scatter-add it is held to ``top_k``
roundings of the largest partial sum.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.models import moe

TOKENS, TOP_K, EXPERTS, HELD, HIDDEN = 48, 4, 16, 4, 24
EVERY = TOKENS * TOP_K
ROWS = EVERY // 4          # the walk's quarter


def routed(case):
    """(order, rank_of, n_held) as `HeldExpertsMoe` makes them, for a
    routing that suits the case."""
    rng = np.random.default_rng(5)
    scores = rng.standard_normal((TOKENS, EXPERTS))
    scores[:, :HELD] -= 1.0         # a share's usual lot: under a quarter
    if case == "later_window":      # held experts take most: n_held > ROWS
        scores[:, :HELD] += 3.0
    elif case == "none_held":
        scores[:, :HELD] -= 50.0
    elif case == "one_expert":      # every token's first choice is expert 1
        scores[:, 1] += 50.0
        scores[:, [0, 2, 3]] -= 50.0
    local = np.argsort(-scores, axis=1)[:, :TOP_K].reshape(EVERY)
    order = np.argsort(local, kind="stable").astype(np.int32)
    n_held = int((local < HELD).sum())
    rank_of = np.argsort(order).astype(np.int32)
    return jnp.asarray(order), jnp.asarray(rank_of), n_held


def window(case, order, n_held):
    start = ROWS if case == "later_window" else 0
    stop = min(start + ROWS, n_held)
    which = order[start:start + ROWS]
    return jnp.int32(start), jnp.int32(stop), which, \
        start + jnp.arange(ROWS) < stop


CASES = ("first_window", "later_window", "none_held", "one_expert",
         "nan_past_the_group")


@pytest.mark.parametrize("table_of", ("rows", "weights"))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16),
                         ids=("float32", "bfloat16"))
@pytest.mark.parametrize("case", CASES)
def test_the_pair_is_the_gather_and_the_scatter_add_it_replaced(
        case, dtype, table_of):
    order, rank_of, n_held = routed(case)
    start, stop, which, in_group = window(case, order, n_held)
    assert {"later_window": n_held > ROWS, "none_held": n_held == 0,
            "one_expert": n_held == TOKENS}.get(case, 0 < n_held < ROWS)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    if table_of == "rows":
        # a table of token rows, every row read TOP_K times
        index, ranks = which // TOP_K, rank_of.reshape(TOKENS, TOP_K).T
        table = jax.random.normal(keys[0], (TOKENS, HIDDEN), dtype)
        sorted_rows = jax.random.normal(keys[1], (ROWS, HIDDEN), dtype)
        terms = TOP_K
    else:
        # the router's weights: one number an assignment, read once
        index, ranks = which, rank_of
        table = jax.random.normal(keys[0], (EVERY,), dtype)
        sorted_rows = jax.random.normal(keys[1], (ROWS,), dtype)
        terms = 1
    keep = in_group.reshape((ROWS,) + (1,) * (table.ndim - 1))
    if case == "nan_past_the_group":
        sorted_rows = jnp.where(keep, sorted_rows, jnp.nan)

    def gather(table):
        return jnp.where(keep, table[index], 0)

    def scatter_add(sorted_rows, dtype=dtype):
        return jnp.zeros(table.shape, dtype).at[index].add(
            jnp.where(keep, sorted_rows, 0).astype(dtype))

    def close_to_the_sum(got, rows_in):
        got = np.asarray(got.astype(jnp.float32))
        assert np.isfinite(got).all()
        want, one_by_one = (
            np.asarray(scatter_add(rows_in, sums_in), np.float32)
            for sums_in in (jnp.float32, dtype))
        largest = float(np.abs(np.asarray(
            jnp.where(keep, rows_in, 0).astype(jnp.float32))).max())
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=4e-6 * max(largest, 1.0))
            np.testing.assert_allclose(got, one_by_one, rtol=0,
                                       atol=4e-6 * max(largest, 1.0))
        else:
            # one rounding of the exact sum ...
            assert (np.abs(got - want) <= 2.0 ** -8 * np.abs(want)
                    + 1e-30).all()
            # ... so no further from the scatter-add than its own roundings
            np.testing.assert_allclose(got, one_by_one, rtol=0,
                                       atol=terms * 2.0 ** -8 * terms
                                       * largest + 1e-30)

    # forward
    got_rows = moe.take_rows(table, index, ranks, start, stop)
    assert got_rows.dtype == table.dtype
    np.testing.assert_array_equal(np.asarray(got_rows, np.float32),
                                  np.asarray(gather(table), np.float32))
    got_sum = moe.sum_rows(sorted_rows, index, ranks, start, stop)
    assert got_sum.dtype == sorted_rows.dtype and got_sum.shape == table.shape
    close_to_the_sum(got_sum, sorted_rows)

    # each is the other's transpose: `take_rows` backward is the sum ...
    cotangent = sorted_rows     # NaN past the group in the last case
    back, = jax.vjp(lambda t: moe.take_rows(t, index, ranks, start, stop),
                    table)[1](cotangent)
    assert back.dtype == table.dtype
    close_to_the_sum(back, cotangent)
    # ... and `sum_rows` backward is the gather, bitwise autodiff's own
    toward = table
    back, = jax.vjp(lambda r: moe.sum_rows(r, index, ranks, start, stop),
                    sorted_rows)[1](toward)
    want, = jax.vjp(scatter_add, jnp.where(keep, sorted_rows, 0))[1](toward)
    np.testing.assert_array_equal(np.asarray(back, np.float32),
                                  np.asarray(jnp.where(keep, want, 0),
                                             np.float32))
    # <G x, r> = <x, G^T r>
    if dtype == jnp.float32 and case != "nan_past_the_group":
        np.testing.assert_allclose(
            float((got_rows * sorted_rows).sum()),
            float((table * got_sum).sum()), rtol=1e-5, atol=1e-5)


def test_a_table_summed_a_block_at_a_time_is_the_same_sum(monkeypatch):
    """`_sum_rows` gathers a block of the table's rows at a time where all
    of them at once would not stay in fast memory: with the size it allows
    set under this table's, three blocks give the one pass's sum, bit for
    bit, and `_blocks` takes the least count of equal blocks that fits."""
    at_once = moe._GATHERED_AT_ONCE
    assert [moe._blocks(48, row) for row in
            (100, at_once // 24, at_once // 2, 2 * at_once)] \
        == [1, 2, 24, 48]
    order, rank_of, n_held = routed("later_window")
    start, stop, which, _ = window("later_window", order, n_held)
    ranks = rank_of.reshape(TOKENS, TOP_K).T
    rows = jax.random.normal(jax.random.PRNGKey(4), (ROWS, HIDDEN),
                             jnp.bfloat16)
    whole = moe._sum_rows(rows, ranks, start, stop)
    monkeypatch.setattr(moe, "_GATHERED_AT_ONCE",
                        TOKENS // 3 * TOP_K * HIDDEN * 2)
    # the undecorated body: `jax.jit` keeps the traced one by its shapes
    in_blocks = moe._sum_rows.__wrapped__(rows, ranks, start, stop)
    assert moe._blocks(TOKENS, TOP_K * HIDDEN * 2) == 3
    np.testing.assert_array_equal(np.asarray(in_blocks, np.float32),
                                  np.asarray(whole, np.float32))


# -- the lowered layer --------------------------------------------------------

def hybrid_gradient_text(layers):
    """`value_and_grad` of ``layers`` of the hybrid cell's expert layer
    (32 of 512 experts held, top-10, 8,192 tokens of 2,048 in bf16, each
    rematerialised as the model's are), lowered for a TPU from here
    (`tests/test_sdar.py::lowered_for_a_tpu`'s recipe)."""
    layer = moe.HeldExpertsMoe(512, 32, 10, 512, 0, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))["params"])

    def loss(all_params, x):
        for p in all_params:
            x = x + jax.checkpoint(lambda p, x: layer.apply(
                {"params": p}, x, mutable=["counters"])[0])(p, x)
        return (x.astype(jnp.float32) ** 2).sum()

    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
            (params,) * layers, x).lower(
                lowering_platforms=("tpu",)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)


def scatter_operands(text):
    """The operand (first argument) type of every scatter in the text."""
    return re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<([^>]*)>', text, re.S)


def pair_bodies_and_calls(text):
    name = r"(?:_take_rows|_sum_rows)\w*"
    return (len(re.findall(rf"func\.func private @{name}\(", text)),
            len(re.findall(rf"call @{name}\(", text)))


def test_the_hybrid_layers_gradient_scatters_onto_no_table():
    """No scatter-add onto the (8192, 2048) token table or onto the
    f32[81920] weights is left in the layer's forward and backward, and the
    pair's bodies are lowered once a program, not once a call site: a
    second layer adds call sites and no body."""
    one, two = hybrid_gradient_text(1), hybrid_gradient_text(2)
    for text in (one, two):
        onto = scatter_operands(text)
        assert not [t for t in onto if t.startswith("8192x2048x")
                    or t.startswith("81920x")], onto
    bodies, calls = pair_bodies_and_calls(one)
    assert 0 < bodies <= calls
    assert pair_bodies_and_calls(two) == (bodies, 2 * calls)

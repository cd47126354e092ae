"""Qwen3-Next through the program against its plain reference
(benchmark/reference/qwen3_next.py), on the CPU at tiny widths, float32, with
EVERY parameter randomised (norm weights, A_log, dt_bias too: a missing
``1 +`` or a sign shows): logits, loss and gradients of the whole model, each
mixer and the sparse block alone, the chunked delta rule against the
position-by-position rule, the 16 shares of an expert layer against the uncut
layer, no drop under the worst routing, and the counters.

Tolerances: both sides are float32 on the CPU (``highest`` matmuls); what is
left is the order of summation (chunks of 64 against single positions, a
grouped product against a masked loop), ~1e-6 relative a product and growing
with depth: 2e-5 of the largest value for one module's result, 2e-4 for the
whole model's logits (four layers, weights five times their initial scale)
and for gradients, which pass through the exponentials of the decays twice.
A wrong equation is off by its whole size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from distributed_pytorch_training_tpu.models import get_model
from distributed_pytorch_training_tpu.models import moe, qwen3_next
from distributed_pytorch_training_tpu.ops import gated_delta_rule as gdr
from distributed_pytorch_training_tpu.ops.flash_attention import (
    make_flash_attention_fn,
)
from distributed_pytorch_training_tpu.ops.gated_delta_rule import (
    gated_delta_rule, gated_delta_rule_stepwise,
)

FWD_TOL, MODEL_TOL, GRAD_TOL = 2e-5, 2e-4, 2e-4

# the published config's keys at tiny sizes (what the reference reads)
SIZES = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    rms_norm_eps=1e-6, full_attention_interval=4, norm_topk_prob=True)
SHARE = dict(first_expert=4, num_experts_held=4, vocab_size=200)
MODEL_KW = dict(
    vocab_size=200, hidden_dim=32, depth=4, num_heads=4, num_kv_heads=2,
    head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, num_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts_held=4, first_expert=4)
SEQ = 70   # one whole chunk of 64 and a part of one


def randomised(params, seed=7):
    """Every leaf redrawn: N(0, 0.3) around its initial value for vectors
    (so a norm weight is no longer 0 or 1 and A_log, dt_bias move), N(0, 0.1)
    for matrices (five times the initial scale, so attention and routing are
    far from uniform; at 0.2 the first layer's gradients lose three more
    digits to float32 on both sides alike)."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [leaf + 0.3 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1
           else 0.1 * jax.random.normal(k, leaf.shape)
           for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, out)


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.fixture(scope="module")
def model():
    return get_model("qwen3_next_80b_a3b", **MODEL_KW)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(3), (2, SEQ), 0, 200)


@pytest.fixture(scope="module")
def params(model, ids):
    return randomised(model.init(jax.random.PRNGKey(1), ids)["params"])


def program_loss(model, params, ids):
    logits = model.apply({"params": params}, ids)[:, :-1, :200]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()


def test_logits_and_loss_match_the_reference(model, params, ids):
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        loss = program_loss(model, params, ids)
    ref = reference.from_program_params(params)
    want = reference.forward(ref, ids, SIZES, SHARE)
    assert got.shape == (2, SEQ, 256)            # 200 padded to 128's
    assert rel(got[..., :200], want) < MODEL_TOL
    assert bool((got[..., 200:] < -1e30).all())  # padding masked out
    want_loss = reference.next_token_loss(ref, ids, SIZES, SHARE)
    assert abs(float(loss) - float(want_loss)) < FWD_TOL * float(want_loss)


def test_gradients_of_every_parameter_match_the_reference(model, params, ids):
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: program_loss(model, p, ids))(params)
    want = jax.grad(lambda p: reference.next_token_loss(
        p, ids, SIZES, SHARE))(reference.from_program_params(params))
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert flat_got.keys() == flat_want.keys()
    for path, g in flat_got.items():
        w = flat_want[path]
        name = jax.tree_util.keystr(path)
        if "embed" in name or "head" in name:
            w = w[:200] if "embed" in name else w[:, :200]
            g = g[:200] if "embed" in name else g[:, :200]
        assert float(jnp.abs(w).max()) > 0, name     # every leaf is reached
        assert rel(g, w) < GRAD_TOL, name


# ---------------------------------------------------------------------------
# the layer's remat (PR 50): `qwen3_next.LAYER_REMAT_POLICY` keeps what the
# kernels and the router's sorts made, by name
# ---------------------------------------------------------------------------


def on_the_kernels(patch):
    """The rule's gates read as on a TPU's own program (the kernels still
    interpreted, here); the attention layer takes the flash kernels."""
    patch.setattr(gdr, "gdn_rule_backend_supported", lambda: True)
    patch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    return dict(attention_fn=make_flash_attention_fn(causal=True))


def all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(inner)


COUNTED = ("gdn_rule_fwd", "flash_fwd", "top_k", "sort")


@pytest.fixture(scope="module")
def gradient_census(params, ids):
    """How often the gradient's jaxpr of the remat'd model holds each of
    COUNTED (a kernel by its name, the router's sorts by their primitive),
    under the model's own policy and under none."""
    def census(policy):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(qwen3_next, "LAYER_REMAT_POLICY", policy)
            remat = get_model("qwen3_next_80b_a3b", remat=True,
                              **on_the_kernels(patch), **MODEL_KW)
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda p: program_loss(remat, p, ids)))(params).jaxpr
        found = [eqn.params["name"] if eqn.primitive.name == "pallas_call"
                 else eqn.primitive.name for eqn in all_eqns(jaxpr)]
        return {name: found.count(name) for name in COUNTED}

    return {"the_layers_policy": census(qwen3_next.LAYER_REMAT_POLICY),
            "no_policy": census(None)}


@pytest.mark.parametrize("counted", COUNTED)
@pytest.mark.parametrize("policy,passes", [("the_layers_policy", 1),
                                           ("no_policy", 2)])
def test_the_remat_runs_a_kernel_and_a_sort_once_a_layer(
        gradient_census, policy, passes, counted):
    """Three Gated DeltaNet layers, one attention layer, four routers with
    one `top_k` and two `argsort`s each (the chip lowers the `top_k` to the
    `sort` its census names). Under the layer's policy the backward's second
    pass over a layer finds each of them kept; with no policy every count is
    twice that, which is what this test would read if the names stopped
    engaging (a jax upgrade, a value renamed)."""
    a_step = {"gdn_rule_fwd": 3, "flash_fwd": 1, "top_k": 4, "sort": 8}
    assert gradient_census[policy][counted] == passes * a_step[counted]


def test_named_top_k_is_lax_top_k_with_its_own_derivative():
    """The router's `top_k` under its names: the same values and indices,
    ties to the lower index included, and the same gradient, bit for bit
    (the cotangent's entries put back at the chosen indices)."""
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (40, 16)))
    probs = probs.at[5].set(probs[5, 0])        # a row of equals
    weight = jax.random.normal(jax.random.PRNGKey(4), (40, 3))
    for got, want in zip(moe.named_top_k(probs, 3), jax.lax.top_k(probs, 3)):
        np.testing.assert_array_equal(got, want)
    grad = lambda top_k: jax.grad(  # noqa: E731
        lambda p: (top_k(p, 3)[0] * weight).sum())(probs)
    np.testing.assert_array_equal(grad(moe.named_top_k), grad(jax.lax.top_k))


@pytest.mark.parametrize("rule", ["kernels", "xla"])
def test_remat_under_the_policy_changes_no_gradient(params, ids, monkeypatch,
                                                    rule):
    """``remat=True`` against ``remat=False``: the same operations on the
    same values, so every parameter's gradient to float32 rounding (two
    compiled programs fuse differently), not to the reference's tolerance.
    The kernel form holds every name the policy lists; the XLA form, what a
    CPU or a multi-device program takes, holds the router's alone."""
    kw = on_the_kernels(monkeypatch) if rule == "kernels" else {}

    def gradients(remat):
        model = get_model("qwen3_next_80b_a3b", remat=remat, **kw, **MODEL_KW)
        return dict(jax.tree_util.tree_leaves_with_path(jax.jit(jax.grad(
            lambda p: program_loss(model, p, ids)))(params)))

    kept, plain = gradients(True), gradients(False)
    assert kept.keys() == plain.keys()
    for path, g in kept.items():
        assert float(jnp.abs(plain[path]).max()) > 0
        assert rel(g, plain[path]) < 1e-6, jax.tree_util.keystr(path)


@pytest.mark.parametrize("layer,mixer", [(0, "gdn"), (3, "gated_attn")])
def test_each_mixer_alone_matches_the_reference(params, layer, mixer):
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 32))
    p = params[f"layer{layer}"][mixer]
    if mixer == "gdn":
        module = qwen3_next.GatedDeltaNet(2, 4, 8, 8, 4, 1e-6)
        plain = reference.gated_delta_net
    else:
        module = qwen3_next.GatedAttention(4, 2, 16, 4, 1e7, 1e-6)
        plain = reference.gated_attention
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": p}, x)
        want = jnp.stack([plain(p, row, SIZES) for row in x])
    assert rel(got, want) < FWD_TOL


def held_layer(first, held):
    return moe.HeldExpertsMoe(16, held, 3, 16, first)


def share_of(p, first, held):
    return {"router": p["router"], **{k: p[k][first:first + held]
                                      for k in ("gate", "up", "down")}}


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut expert layer: all 16 experts' weights, randomised."""
    x = jax.random.normal(jax.random.PRNGKey(11), (2, SEQ, 32))
    p = randomised(held_layer(0, 16).init(jax.random.PRNGKey(2), x)["params"])
    return x, p


def test_sparse_block_alone_matches_the_reference(params, whole_layer):
    x, _ = whole_layer
    layer = params["layer1"]
    with jax.default_matmul_precision("highest"):
        got = held_layer(4, 4).apply({"params": layer["moe"]}, x,
                                     mutable=["counters"])[0] \
            + qwen3_next.SharedExpert(16).apply(
                {"params": layer["shared_expert"]}, x)
        want = jnp.stack([reference.sparse_block(
            layer["moe"], layer["shared_expert"], row, SIZES, 4, 4)
            for row in x])
    assert rel(got, want) < FWD_TOL


def test_the_shares_add_up_to_the_uncut_layer(params, whole_layer):
    """Section 4's test: the routed parts that all the shares give (here 4
    shares of 4 experts and, unevenly, 16 shares of 1), plus the shared
    expert counted once, equal the uncut reference's layer."""
    x, p = whole_layer
    shared = params["layer0"]["shared_expert"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.sparse_block(p, shared, row, SIZES, 0, 16)
                          for row in x])
        once = qwen3_next.SharedExpert(16).apply({"params": shared}, x)
        for held in (4, 1):
            parts = [held_layer(first, held).apply(
                {"params": share_of(p, first, held)}, x,
                mutable=["counters"])[0] for first in range(0, 16, held)]
            assert rel(sum(parts) + once, want) < FWD_TOL
            # and a share is not the whole: it leaves the others' part out
            assert rel(parts[0] + once, want) > 1e-2


def test_no_drop_when_every_token_picks_the_held_experts(whole_layer):
    """The worst routing for a share: a router that sends every token to the
    same three experts, all held here. All 3 * tokens assignments land in
    the share (twelve times the balanced load, past the quarter the short
    path covers), none is dropped, and the result is still the reference's."""
    x, p = whole_layer
    router = jnp.zeros((32, 16)).at[:, 5].set(3.0).at[:, 6].set(2.0) \
        .at[:, 7].set(1.0)
    x = jnp.abs(x)          # so that x @ router orders 5 > 6 > 7 > the rest
    p = {**p, "router": router}
    layer = held_layer(4, 4)
    with jax.default_matmul_precision("highest"):
        got, sown = layer.apply({"params": share_of(p, 4, 4)}, x,
                                mutable=["counters"])
        want = jnp.stack([reference.routed_experts(
            share_of(p, 4, 4), row, SIZES, 4, 4) for row in x])
    counters = {k: float(v[0]) for k, v in sown["counters"].items()}
    assert counters["moe_held_assignments"] == 3 * 2 * SEQ
    assert counters["moe_dropped_assignments"] == 0
    # three of the four held experts take a third each, one takes none
    assert counters["moe_expert_load_max_over_mean"] == pytest.approx(4 / 3)
    assert rel(got, want) < FWD_TOL
    grads = jax.grad(lambda q: layer.apply(
        {"params": q}, x, mutable=["counters"])[0].sum())(share_of(p, 4, 4))
    assert float(jnp.abs(grads["gate"][0]).max()) == 0      # expert 4: unused
    assert float(jnp.abs(grads["gate"][1]).max()) > 0


def test_rows_past_the_last_group_never_reach_a_result_or_a_gradient(
        whole_layer, monkeypatch):
    """On a TPU `lax.ragged_dot` leaves the rows past its last group
    UNWRITTEN, in its transposes too (on the CPU they are zeros, so nothing
    else here can see it; on the chip it was NaN losses within twenty
    steps). Here every such row is poisoned with NaN, forward and backward:
    the layer's result and every gradient must stay finite and unchanged."""
    real = jax.lax.ragged_dot

    def inside(n_rows, sizes):
        return (jnp.arange(n_rows) < sizes.sum())[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        keep = inside(lhs.shape[0], sizes)
        return jnp.where(keep, real(jnp.where(keep, lhs, 0), rhs, sizes),
                         jnp.nan)

    def forward(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def backward(saved, cotangent):
        lhs, rhs, sizes = saved
        keep = inside(lhs.shape[0], sizes)
        _, vjp = jax.vjp(lambda a, w: real(a, w, sizes),
                         jnp.where(keep, lhs, 0), rhs)
        d_lhs, d_rhs = vjp(jnp.where(keep, cotangent, 0))
        return jnp.where(keep, d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(forward, backward)
    x, p = whole_layer
    layer, share = held_layer(4, 4), share_of(p, 4, 4)

    def result_and_grads():
        value = lambda q, xs: layer.apply(  # noqa: E731
            {"params": q}, xs, mutable=["counters"])[0]
        return value(share, x), jax.grad(
            lambda q, xs: (value(q, xs) ** 2).sum(), argnums=(0, 1))(share, x)

    want, want_grads = result_and_grads()
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got, got_grads = result_and_grads()
    assert bool(jnp.isfinite(got).all()) and rel(got, want) < FWD_TOL
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert bool(jnp.isfinite(g).all())
        assert rel(g, w) < GRAD_TOL


def test_an_absent_share_computes_nothing(whole_layer):
    x, p = whole_layer
    router = jnp.zeros((32, 16)).at[:, 0].set(3.0).at[:, 1].set(2.0) \
        .at[:, 2].set(1.0)
    got, sown = held_layer(4, 4).apply(
        {"params": {**share_of(p, 4, 4), "router": router}}, jnp.abs(x),
        mutable=["counters"])
    assert float(jnp.abs(got).max()) == 0
    assert float(sown["counters"]["moe_held_assignments"][0]) == 0


def rule_inputs(length, decay, h=3):
    b, dk, dv = 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(length), 5)
    q = jax.random.normal(ks[0], (b, length, h, dk))
    k = jax.random.normal(ks[1], (b, length, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, length, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, length, h))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h)))
    return q, k, v, g, beta


def blocked_rule(length):
    # the 3 heads one at a time at 200 positions, else in one block of 3
    return lambda *a: gated_delta_rule(
        *a, head_block=1 if length == 200 else 8)


def test_a_head_block_that_does_not_divide_the_heads_is_an_error():
    with pytest.raises(ValueError, match="does not divide the 3 heads"):
        gated_delta_rule(*rule_inputs(64, 0.0), head_block=2)


@pytest.mark.parametrize("length", [64, 100, 192, 200])
@pytest.mark.parametrize("decay", [0.0, 0.05, 30.0],
                         ids=["no_decay", "weak_decay", "strong_decay"])
def test_chunked_rule_matches_the_stepwise_rule(length, decay):
    """Lengths that are and are not multiples of the chunk of 64; no decay
    (the state only grows), weak decay, and decay so strong (g down to -30 a
    position, -1900 over a chunk) that an unmasked exp(g_i - g_j) overflows.
    Under strong decay the differences of cumulative sums near 1e3 cost
    float32 its last digits, hence 2e-5."""
    args = rule_inputs(length, decay)
    got = blocked_rule(length)(*args)
    assert bool(jnp.isfinite(got).all())
    assert rel(got, gated_delta_rule_stepwise(*args)) < FWD_TOL


@pytest.mark.parametrize("length,decay", [(64, 0.0), (100, 30.0),
                                          (200, 0.05)])
def test_chunked_rule_backward_matches_the_stepwise_rule(length, decay):
    """XLA's own transpose of the chunked form (the solve, the scan over
    chunks, the rematerialised head blocks) against that of the recurrence,
    for every input, the decays' included."""
    args = rule_inputs(length, decay)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda *a: (f(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))(*args)
    for x, y in zip(grad(blocked_rule(length)),
                    grad(gated_delta_rule_stepwise)):
        assert bool(jnp.isfinite(x).all())
        assert rel(x, y) < GRAD_TOL


def test_bf16_state_in_the_rule_is_told_from_float32():
    """What the chip check's tolerance has to tell apart, at the rule: with
    weak decay over 512 positions a state rounded to bf16 after every chunk
    is off by over 1e-3, fifty times the float32 rule's distance."""
    b, s, h, dk, dv = 1, 512, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    k = jax.random.normal(ks[0], (b, s, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(ks[1], (b, s, h, dk)) / np.sqrt(dk)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -0.01 * jax.random.uniform(ks[3], (b, s, h))
    beta = jnp.full((b, s, h), 0.5)
    want = gated_delta_rule_stepwise(q, k, v, g, beta)
    as_bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    rough = gated_delta_rule_stepwise(as_bf16(q), as_bf16(k), as_bf16(v), g,
                                      beta)
    assert rel(gated_delta_rule(q, k, v, g, beta, head_block=1),
               want) < FWD_TOL
    assert rel(rough, want) > 50 * FWD_TOL


def test_counters_ride_the_step_metrics_and_reach_telemetry(model, ids):
    """`LanguageModelingTask` folds what the layers sowed into the step's
    metrics (sum over layers; worst layer for the load), `train_epoch` keeps
    them with the loss sums and emits them at a print boundary."""
    from distributed_pytorch_training_tpu import telemetry
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.parallel.sharding import shard_batch
    from distributed_pytorch_training_tpu.training.loop import (
        TrainConfig, Trainer,
    )
    from distributed_pytorch_training_tpu.training.optim import (
        make_optimizer, make_schedule,
    )
    from distributed_pytorch_training_tpu.training.tasks import (
        LanguageModelingTask,
    )

    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    trainer = Trainer(LanguageModelingTask(), mesh,
                      TrainConfig(per_device_batch=2, print_freq=2),
                      rules=type(model).partition_rules())
    state = trainer.init_state(
        model, np.zeros((1, SEQ), np.int32),
        make_optimizer("adamw", make_schedule("constant", 3e-4)),
        jax.random.PRNGKey(0))
    batch = shard_batch({"input_ids": np.asarray(ids),
                         "weight": np.ones(2, np.float32)}, mesh)
    state, metrics = trainer._train_step(state, batch, jax.random.PRNGKey(0))
    assert set(metrics) == {"loss_sum", "correct", "weight", "counters"}
    counters = metrics["counters"]
    assert set(counters) == {"moe_held_assignments",
                             "moe_dropped_assignments",
                             "moe_expert_load_max_over_mean"}
    assert float(counters["moe_dropped_assignments"]) == 0
    assigned = 2 * SEQ * 3 * 4            # tokens x top-k x layers
    assert 0 < float(counters["moe_held_assignments"]) < assigned
    assert float(counters["moe_expert_load_max_over_mean"]) >= 1
    # a sum that starts from zero has no place for them, and says so
    from distributed_pytorch_training_tpu.training.tasks import (
        add_metrics, zero_metrics,
    )
    with pytest.raises(ValueError, match="step counters"):
        add_metrics(zero_metrics(), metrics)
    loss, _ = trainer.evaluate(state, [batch, batch])
    assert np.isfinite(loss)

    seen = []
    recorder = telemetry.configure(None, ring_size=16)
    recorder.add_observer(seen.append)
    try:
        trainer.train_epoch(state, [batch] * 4, 0, 4)
    finally:
        telemetry.reset()
    held = [e for e in seen if e.get("name") == "moe_held_assignments"]
    assert [e["steps"] for e in held] == [2, 2]
    assert all(e["kind"] == "counter" and 0 < e["value"] < 2 * assigned
               for e in held)
    assert [e["value"] for e in seen
            if e.get("name") == "moe_dropped_assignments"] == [0, 0]
    load = [e for e in seen if e.get("name") == "moe_expert_load_max_over_mean"]
    assert len(load) == 2 and all(e["kind"] == "gauge" and e["value"] >= 1
                                  for e in load)


def test_registry_model_has_the_published_counts():
    """By `jax.eval_shape`, nothing allocated: 48 layers of which 36 are
    Gated DeltaNet, 512 experts of 3 x 2048 x 512, 622M in embedding and
    head, 79.7B in all (the source's "80B")."""
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    full = get_model("qwen3_next_80b_a3b")
    shapes = jax.eval_shape(
        lambda k: full.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    layers = [k for k in shapes if k.startswith("layer")]
    assert len(layers) == 48
    assert sum("gdn" in shapes[k] for k in layers) == 36
    assert all(("gated_attn" in shapes[f"layer{i}"]) == ((i + 1) % 4 == 0)
               for i in range(48))
    assert shapes["layer0"]["moe"]["gate"].shape == (512, 2048, 512)
    assert shapes["layer0"]["moe"]["router"].shape == (2048, 512)
    assert count(shapes["embed"]) + count(shapes["head"]) == 2 * 151936 * 2048
    assert 79.0e9 < count(shapes) < 80.5e9


def test_train_cli_builds_and_steps_the_model(tmp_path):
    """``train.py --model qwen3_next_80b_a3b`` with its normal options: the
    task is the plain LM one (the name holds no "moe": no Switch loss), and
    the epoch's loss is a cross-entropy over the vocabulary, finite."""
    import csv

    import train as train_cli

    overrides = ",".join(f"{k}={v}" for k, v in {
        **MODEL_KW, "vocab_size": 50257, "first_expert": 0}.items())
    train_cli.main([
        "--model", "qwen3_next_80b_a3b", "--epochs", "1", "--synthetic",
        "--synthetic-size", "32", "--seq-len", "32", "--batch-size", "1",
        "--optimizer", "adamw", "--print-freq", "2",
        "--output-dir", str(tmp_path), "--model-overrides", overrides])
    with open(tmp_path / "metrics_rank0.csv") as f:
        row = next(csv.DictReader(f))
    assert 0 < float(row["train_loss"]) < np.log(50257) + 0.1

"""The gated delta rule's kernel pair (`ops/gdn_rule_kernels.py`, ISSUE 29),
called directly so that it runs here, in Pallas interpreter mode on the CPU.

Pins, in order:
* `gdn_rule_fwd` against the position-by-position rule over the lengths and
  decays `test_qwen3_next.py` holds the XLA form to, at its tolerance;
* `gdn_rule_bwd`: gradients of all five inputs, the decays' included,
  against the stepwise rule's; more heads than one grid step takes and a
  batch above 1; bf16 ``v`` as the mixer passes it; a bf16 state told from
  float32; the inverse by doubling on a chunk of ONE repeated key, where a
  power series would cancel catastrophically;
* a forward that nothing differentiates writes no residuals;
* which path `gated_delta_rule` takes: the module-level `_chunked_rule`
  wherever the backend is no TPU; on one the kernels, for the shapes they
  were compiled for and in a one-device program (one device, or inside a
  `shard_map`), by what the code observes and no option; lowered for a TPU
  on a two-device mesh, a GSPMD program carries no Mosaic kernel and a
  `shard_map` does; and the lowered text of the tiny hybrid train step on
  the kernel path carries both kernels under ``gdn_rule``.
"""

import functools
import importlib
import inspect
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP
from distributed_pytorch_training_tpu.models import get_model
from test_qwen3_next import FWD_TOL, GRAD_TOL, rel
from test_qwen3_next import rule_inputs as odd_rule_inputs

gdr = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.gated_delta_rule")
kernels = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.gdn_rule_kernels")

ROOT = Path(__file__).resolve().parents[1]


def rule_inputs(length, decay):
    """`test_qwen3_next.py`'s, with four heads for its three: the kernels
    take heads in packs of two."""
    return odd_rule_inputs(length, decay, h=4)


def grads_of(rule, args):
    return jax.grad(lambda *a: (rule(*a).astype(jnp.float32) ** 2).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)


# ---------------------------------------------------------------------------
# the kernels against the stepwise rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [64, 100, 192, 200])
@pytest.mark.parametrize("decay", [0.0, 0.05, 30.0],
                         ids=["no_decay", "weak_decay", "strong_decay"])
def test_forward_kernel_matches_the_stepwise_rule(length, decay):
    args = rule_inputs(length, decay)
    got = kernels.gated_delta_rule_kernels(*args)
    assert got.dtype == jnp.float32 and got.shape == args[2].shape
    assert bool(jnp.isfinite(got).all())
    assert rel(got, gdr.gated_delta_rule_stepwise(*args)) < FWD_TOL


@pytest.mark.parametrize("length,decay", [(64, 0.0), (100, 30.0),
                                          (200, 0.05)])
def test_backward_kernel_matches_the_stepwise_rules_gradients(length, decay):
    args = rule_inputs(length, decay)
    for got, want in zip(
            grads_of(kernels.gated_delta_rule_kernels, args),
            grads_of(gdr.gated_delta_rule_stepwise, args)):
        assert got.shape == want.shape
        assert bool(jnp.isfinite(got).all())
        assert rel(got, want) < GRAD_TOL


def wide_inputs(heads, v_dtype=jnp.float32):
    """A batch of 2 and more heads than one grid step takes."""
    b, s, dk, dv = 2, 130, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(heads), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, heads, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, s, heads, dk)))
    v = jax.random.normal(ks[2], (b, s, heads, dv)).astype(v_dtype)
    g = -0.1 * jax.random.uniform(ks[3], (b, s, heads))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("heads", [kernels.HEADS_PER_STEP * 2,
                                   kernels.HEADS_PER_STEP + 2])
def test_heads_beyond_one_grid_step_and_a_batch(heads):
    """Twice a step's heads (two groups of four packs a batch row) and a
    count that only one pack divides (five groups of one): each head's state
    is its own, and each batch row starts from zero."""
    args = wide_inputs(heads)
    assert kernels._heads_per_step(heads) == (8 if heads == 16 else 2)
    assert rel(kernels.gated_delta_rule_kernels(*args),
               gdr.gated_delta_rule_stepwise(*args)) < FWD_TOL
    for got, want in zip(grads_of(kernels.gated_delta_rule_kernels, args),
                         grads_of(gdr.gated_delta_rule_stepwise, args)):
        assert rel(got, want) < GRAD_TOL


def test_bf16_values_as_the_mixer_passes_them():
    """``v`` arrives in the compute dtype; the kernel casts it up on the
    chip, gives float32 out and hands ``v`` a cotangent of its own dtype."""
    args = wide_inputs(2, jnp.bfloat16)
    up = (*args[:2], args[2].astype(jnp.float32), *args[3:])
    got = kernels.gated_delta_rule_kernels(*args)
    assert got.dtype == jnp.float32
    assert rel(got, gdr.gated_delta_rule_stepwise(*up)) < FWD_TOL
    got_grads = grads_of(kernels.gated_delta_rule_kernels, args)
    assert [x.dtype for x in got_grads] == [x.dtype for x in args]
    for i, (got, want) in enumerate(zip(
            got_grads, grads_of(gdr.gated_delta_rule_stepwise, up))):
        # dv is rounded to bf16 once, at the end
        assert rel(got.astype(jnp.float32), want) < (
            2 ** -8 if i == 2 else GRAD_TOL)


def test_bf16_state_is_told_from_float32_by_the_kernel():
    """`test_bf16_state_in_the_rule_is_told_from_float32`, against the
    kernels: the float32 kernel sits within FWD_TOL of the stepwise rule
    where inputs rounded to bf16 are over fifty times that away."""
    b, s, h, dk, dv = 1, 512, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    k = jax.random.normal(ks[0], (b, s, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(ks[1], (b, s, h, dk)) / np.sqrt(dk)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -0.01 * jax.random.uniform(ks[3], (b, s, h))
    beta = jnp.full((b, s, h), 0.5)
    want = gdr.gated_delta_rule_stepwise(q, k, v, g, beta)
    as_bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    rough = kernels.gated_delta_rule_kernels(as_bf16(q), as_bf16(k),
                                             as_bf16(v), g, beta)
    assert rel(kernels.gated_delta_rule_kernels(q, k, v, g, beta),
               want) < FWD_TOL
    assert rel(rough, want) > 50 * FWD_TOL


def test_a_chunk_of_one_repeated_key_is_solved_not_summed():
    """A run of one token: every key of the chunk the same, beta near 1, no
    decay, so ``A`` is all ones below the diagonal and ``A^n`` reaches
    binomial(63, n) ~ 1e18. The inverse by doubling is substitution and
    stays within float32 of the stepwise rule; forward and backward."""
    b, s, h, dk, dv = 1, 128, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    k = jnp.broadcast_to(jnp.eye(dk)[0], (b, s, h, dk))
    q = jax.random.normal(ks[0], (b, s, h, dk)) / np.sqrt(dk)
    v = jax.random.normal(ks[1], (b, s, h, dv))
    g = jnp.zeros((b, s, h))
    beta = jnp.full((b, s, h), 0.99)
    args = (q, k, v, g, beta)
    assert rel(kernels.gated_delta_rule_kernels(*args),
               gdr.gated_delta_rule_stepwise(*args)) < FWD_TOL
    for got, want in zip(grads_of(kernels.gated_delta_rule_kernels, args),
                         grads_of(gdr.gated_delta_rule_stepwise, args)):
        assert rel(got, want) < GRAD_TOL


def test_an_odd_head_count_is_refused_by_the_kernels_and_the_gate():
    assert not kernels.gdn_rule_supports(3, 128, 128)
    assert kernels.gdn_rule_supports(4, 16, 8)         # the interpreter, here
    with pytest.raises(ValueError, match="3 heads are not whole packs"):
        kernels.gated_delta_rule_kernels(*odd_rule_inputs(64, 0.0))


def test_a_forward_nothing_differentiates_writes_no_residuals():
    """Evaluation and the benchmark's rule check: one output, the rows; the
    differentiated forward adds the chunk-start states and the inverses."""
    args = rule_inputs(128, 0.05)

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner)

    def outputs(f):
        [call] = [e for e in equations(jax.make_jaxpr(f)(*args).jaxpr)
                  if e.primitive.name == "pallas_call"]
        assert call.params["name"] == "gdn_rule_fwd"
        return [v.aval.shape for v in call.outvars]

    assert outputs(kernels.gated_delta_rule_kernels) == [(2, 128, 4 * 8)]
    assert outputs(lambda *a: jax.vjp(
        kernels.gated_delta_rule_kernels, *a)[0]) == [
            (2, 128, 4 * 8), (2, 2, 4, 16, 8), (2, 2, 2, 64, 128)]


# ---------------------------------------------------------------------------
# which path runs where
# ---------------------------------------------------------------------------

def test_off_a_tpu_the_rule_goes_through_the_module_level_chunked_rule(
        monkeypatch):
    args = rule_inputs(64, 0.05)
    assert not kernels.gdn_rule_backend_supported()     # the CPU, here
    plain = gdr.gated_delta_rule(*args, head_block=8)
    exact = gdr._chunked_rule
    monkeypatch.setattr(gdr, "_chunked_rule",
                        lambda *a: 2.0 * exact(*a))
    assert rel(gdr.gated_delta_rule(*args, head_block=8), 2.0 * plain) < 1e-6


@pytest.mark.parametrize("on_tpu", [False, True], ids=["xla", "kernels"])
def test_head_block_that_does_not_divide_is_an_error_on_both_paths(
        monkeypatch, on_tpu):
    monkeypatch.setattr(gdr, "gdn_rule_backend_supported", lambda: on_tpu)
    monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    with pytest.raises(ValueError, match="does not divide the 4 heads"):
        gdr.gated_delta_rule(*rule_inputs(64, 0.0), head_block=3)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The backend reads as a TPU (the kernels still interpreted) and every
    call to either form is counted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.gdn_rule_backend_supported()
    monkeypatch.setattr(kernels, "_interpret", lambda: True)   # still a CPU
    calls = {"kernels": 0, "xla": 0}

    def counted(name, f):
        def call(*a):
            calls[name] += 1
            return f(*a)
        return call

    monkeypatch.setattr(gdr, "gated_delta_rule_kernels",
                        counted("kernels", gdr.gated_delta_rule_kernels))
    monkeypatch.setattr(gdr, "_chunked_rule",
                        counted("xla", gdr._chunked_rule))
    return calls


def test_the_choice_reads_what_the_code_observes_and_no_option(
        as_on_a_tpu, monkeypatch):
    """On a TPU, in a one-device program, the kernels run whatever
    ``head_block`` says; heads that are no whole packs take the XLA form.
    No environment variable, no argument."""
    monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    args = rule_inputs(100, 0.05)
    for head_block in (1, 2, 8):
        got = gdr.gated_delta_rule(*args, head_block=head_block)
        assert rel(got, gdr.gated_delta_rule_stepwise(*args)) < FWD_TOL
    assert as_on_a_tpu == {"kernels": 3, "xla": 0}
    odd = odd_rule_inputs(100, 0.05)
    assert rel(gdr.gated_delta_rule(*odd, head_block=8),
               gdr.gated_delta_rule_stepwise(*odd)) < FWD_TOL
    assert as_on_a_tpu == {"kernels": 3, "xla": 1}
    assert list(inspect.signature(gdr.gated_delta_rule).parameters) == [
        "q", "k", "v", "g", "beta", "head_block"]
    for module in (gdr, kernels):
        assert "environ" not in inspect.getsource(module)
        for gate in ("gdn_rule_backend_supported",
                     "gdn_rule_one_device_trace"):
            assert not inspect.signature(getattr(module, gate)).parameters


def two_device_mesh():
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    return build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])


def sharded_rule(mesh, *, manual):
    """The rule over a batch split across ``mesh``: as a GSPMD program, or
    per shard inside a `shard_map`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_pytorch_training_tpu.parallel.collectives import (
        shard_map,
    )
    from distributed_pytorch_training_tpu.parallel.mesh import BATCH_AXES

    rule = lambda *a: gdr.gated_delta_rule(*a, head_block=8)  # noqa: E731
    spec = P(BATCH_AXES)
    if manual:
        rule = shard_map(rule, mesh, in_specs=(spec,) * 5, out_specs=spec)
    return jax.jit(rule, in_shardings=(NamedSharding(mesh, spec),) * 5,
                   out_shardings=NamedSharding(mesh, spec))


def test_many_devices_take_the_kernels_only_inside_a_shard_map(as_on_a_tpu):
    """The process has eight devices here, so outside a `shard_map` a trace
    may be a multi-device GSPMD program's and keeps the XLA form; inside
    one, every axis manual, the operands are one shard's."""
    assert jax.device_count() > 1
    assert not kernels.gdn_rule_one_device_trace()
    mesh, args = two_device_mesh(), rule_inputs(100, 0.05)
    want = gdr.gated_delta_rule_stepwise(*args)
    assert rel(sharded_rule(mesh, manual=False)(*args), want) < FWD_TOL
    assert as_on_a_tpu == {"kernels": 0, "xla": 1}
    assert rel(sharded_rule(mesh, manual=True)(*args), want) < FWD_TOL
    assert as_on_a_tpu == {"kernels": 1, "xla": 1}


def test_one_device_in_the_process_is_a_one_device_trace(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert kernels.gdn_rule_one_device_trace()


@pytest.mark.parametrize("program", ["gspmd", "shard_map", "gspmd_forced"])
def test_lowered_for_a_tpu_on_two_devices(monkeypatch, program):
    """The lowering a chip would run, Pallas to Mosaic included, from here:
    a two-device GSPMD program lowers, with no Mosaic kernel in it (the XLA
    form: a `while`); the same rule per shard in a `shard_map` lowers with
    both kernels; and the GSPMD program FORCED onto the kernels is the
    lowering error the third gate is there for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    if program == "gspmd_forced":
        monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    b, s, h, d = 2, 128, 2, 128
    args = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in
            [(b, s, h, d)] * 3 + [(b, s, h)] * 2]
    rule = sharded_rule(two_device_mesh(), manual=program == "shard_map")
    step = jax.jit(jax.grad(lambda *a: rule(*a).sum(), argnums=(0, 1, 2, 3,
                                                                4)))

    def lower():
        return step.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    if program == "gspmd_forced":
        with pytest.raises(Exception, match="automatically partitioned"):
            lower()
        return
    text = lower()
    for kernel in ("gdn_rule_fwd", "gdn_rule_bwd"):
        assert (kernel in text) == (program == "shard_map")
    assert ("tpu_custom_call" in text) == (program == "shard_map")
    assert ("stablehlo.while" in text) == (program == "gspmd")


def test_hybrid_train_step_carries_both_kernels_under_gdn_rule(monkeypatch):
    """The tiny hybrid train step, lowered on the kernel path (interpreter
    mode): the forward kernel's scope and the backward's each lie on a path
    whose innermost listed region is ``gdn_rule``, so `hybrid_gdn_rule_ms`
    counts them, and no `lax.map` over head blocks is left around them."""
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

    monkeypatch.setattr(gdr, "gdn_rule_backend_supported", lambda: True)
    monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    config = json.loads(
        (ROOT / "benchmark/configs/qwen3_next_80b_a3b.json").read_text())
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    model = get_model("qwen3_next_80b_a3b", dtype=jnp.bfloat16, remat=True,
                      **config["rehearsal"]["model_overrides"])
    trainer = Trainer(LanguageModelingTask(compute_dtype=jnp.bfloat16), mesh,
                      TrainConfig(per_device_batch=2, bf16=True),
                      rules=type(model).partition_rules())
    state = trainer.init_state(
        model, np.zeros((1, 64), np.int32),
        make_optimizer("adamw", make_schedule("constant", 3e-4)),
        jax.random.PRNGKey(0))
    batch = shard_batch({"input_ids": np.zeros((2, 64), np.int32),
                         "weight": np.ones(2, np.float32)}, mesh)
    lowered = trainer._train_step.lower(state, batch, jax.random.PRNGKey(0))
    paths = set(re.findall(r'loc\("(jit\([^"]*)"',
                           lowered.as_text(debug_info=True)))
    regions = HYBRID_TRAIN_STEP[1]
    # the convolution's pair (PR 41) lies under the module's ``gdn`` and
    # outside the rule's scope: `hybrid_gdn_proj_ms` goes on counting it
    for kernel, region in (("gdn_rule_fwd", "gdn_rule"),
                           ("gdn_rule_bwd", "gdn_rule"),
                           ("gdn_conv_fwd", "gdn"), ("gdn_conv_bwd", "gdn")):
        under = [p for p in paths if f"/{kernel}" in p]
        assert under, kernel
        assert {_regions.region_of(p, regions) for p in under} == {region}
        assert any("transpose" in p for p in under) or "fwd" in kernel
    # what the XLA form's head blocks were: a `while` under the rule's scope
    assert not any("gdn_rule/while" in p for p in paths)


# ---------------------------------------------------------------------------
# the mixer between its projections (PR 39, PR 41): the convolution's kernel
# pair, then l2 norm, query scale and key-head repeat in the rule's kernels'
# prologue, the gated norm in their epilogue, z found by column
# ---------------------------------------------------------------------------

EPSILON = 1e-6


def mixer_inputs(length, decay, *, key_heads, heads, dtype=jnp.float32, b=2,
                 dk=16, dv=8):
    """(the in-projection's output q | k | v | z, the convolution's taps, g,
    beta, the norm's weight) as `GatedDeltaNet` hands them over: q and k at
    ``key_heads`` heads, neither convolved, scaled nor repeated."""
    ks = jax.random.split(jax.random.PRNGKey(length + heads), 5)
    conv_dim = 2 * key_heads * dk + heads * dv
    qkvz = jax.random.normal(
        ks[0], (b, length, conv_dim + heads * dv)).astype(dtype)
    taps = jax.random.uniform(ks[1], (4, conv_dim), jnp.float32, -0.5, 0.5)
    g = -decay * jax.random.uniform(ks[2], (b, length, heads))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, length, heads)))
    norm_w = 1.0 + 0.1 * jax.random.normal(ks[4], (dv,))
    return qkvz, taps, g, beta, norm_w


def stepwise_conv_silu(x, taps):
    """The convolution position by position: output row t is the taps
    times the rows t - (K - 1) .. t, those before row 0 zero."""
    k = taps.shape[0]

    def step(last, row):          # last: (B, K, C), the K rows up to here
        last = jnp.concatenate([last[:, 1:], row[:, None]], axis=1)
        pre = (last * taps).sum(1)
        return last, pre * jax.nn.sigmoid(pre)

    x = x.astype(jnp.float32)
    _, out = jax.lax.scan(
        step, jnp.zeros((x.shape[0], k, x.shape[2])), jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(out, 0, 1)


def stepwise_mixer(qkvz, taps, g, beta, norm_w, *, key_heads,
                   table_dtype=None):
    """The oracle: the convolution position by position (rounded to the
    tables' dtype, as the contract has it: to qkvz's, or ``table_dtype``
    for operands cast up; the rounding passes cotangents as they are),
    then the mixer's norms written out, in float32, around the
    position-by-position rule."""
    b, s, h = g.shape
    dv = norm_w.shape[0]
    conv_dim = taps.shape[1]
    key_dim = (conv_dim - h * dv) // 2
    dk = key_dim // key_heads
    qkv = stepwise_conv_silu(qkvz[..., :conv_dim], taps)
    qkv = qkv + jax.lax.stop_gradient(qkv.astype(
        table_dtype or qkvz.dtype).astype(jnp.float32) - qkv)
    z = qkvz[..., conv_dim:].astype(jnp.float32)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        (x * x).sum(-1, keepdims=True) + 1e-6)
    q = unit(qkv[..., :key_dim].reshape(b, s, key_heads, dk)) * dk ** -0.5
    k = unit(qkv[..., key_dim:2 * key_dim].reshape(b, s, key_heads, dk))
    q, k = (jnp.repeat(t, h // key_heads, axis=2) for t in (q, k))
    o = gdr.gated_delta_rule_stepwise(
        q, k, qkv[..., 2 * key_dim:].reshape(b, s, h, dv), g, beta)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + EPSILON)
    return (o * norm_w * jax.nn.silu(z.reshape(b, s, h, dv))).reshape(
        b, s, h * dv)


def mixer_forms(key_heads):
    """The new entry's three: its kernels (interpreted, here), what
    `gated_delta_mixer` itself runs off a TPU (its XLA form), the oracle."""
    return (lambda *a: kernels.gated_delta_mixer_kernels(
                *a, EPSILON, key_heads=key_heads),
            lambda *a: gdr.gated_delta_mixer(
                *a, EPSILON, key_heads=key_heads, head_block=2),
            lambda *a: stepwise_mixer(*a, key_heads=key_heads))


def out_shape(args):
    (b, s, h), (dv,) = args[2].shape, args[4].shape
    return b, s, h * dv


def mixer_grads(form, args):
    """Gradients of all five operands, of a loss that weighs every output
    differently (a plain sum of squares is blind to the gated norm's
    scale)."""
    weights = jax.random.normal(jax.random.PRNGKey(7), out_shape(args))
    return jax.grad(lambda *a: (form(*a).astype(jnp.float32) * weights).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)


def columns_of(dqkvz, key_heads, dk=16, dv=8):
    """The in-projection's cotangent by its columns: those of q and k (at
    the key heads' width), of v and of z."""
    key_dim = key_heads * dk
    value_dim = (dqkvz.shape[-1] - 2 * key_dim) // 2
    return (dqkvz[..., :key_dim], dqkvz[..., key_dim:2 * key_dim],
            dqkvz[..., 2 * key_dim:2 * key_dim + value_dim],
            dqkvz[..., 2 * key_dim + value_dim:])


@pytest.mark.parametrize("ratio", [2, 1], ids=["two_a_key", "one_a_key"])
@pytest.mark.parametrize("decay", [0.05, 30.0],
                         ids=["weak_decay", "strong_decay"])
@pytest.mark.parametrize("length", [64, 100, 200])
def test_mixer_kernels_match_the_xla_form_and_the_stepwise_rule(
        length, decay, ratio):
    args = mixer_inputs(length, decay, key_heads=4 // ratio, heads=4)
    from_kernels, from_xla, from_steps = (
        form(*args) for form in mixer_forms(4 // ratio))
    assert from_kernels.dtype == args[0].dtype
    assert from_kernels.shape == out_shape(args) == from_xla.shape
    assert bool(jnp.isfinite(from_kernels).all())
    assert rel(from_kernels, from_xla) < FWD_TOL
    assert rel(from_kernels, from_steps) < FWD_TOL


@pytest.mark.parametrize("length,decay,ratio", [
    (64, 0.05, 2), (100, 30.0, 2), (200, 0.05, 2), (100, 0.05, 1),
    (200, 30.0, 1)])
def test_mixer_kernels_gradients_match_both_other_forms(length, decay, ratio):
    """All five operands: the in-projection's output (dq and dk arrive at
    the key heads' width, summed over the value heads a key head served and
    sent back through the l2 norm inside the rule's kernel, then with dv
    through the convolution's; dz in z's own columns beside them), the
    taps, g, beta and the norm's weight."""
    key_heads = 4 // ratio
    args = mixer_inputs(length, decay, key_heads=key_heads, heads=4)
    from_kernels, from_xla, from_steps = (
        mixer_grads(form, args) for form in mixer_forms(key_heads))
    for want in (from_xla, from_steps):
        for got, other in zip(from_kernels, want):
            assert got.shape == other.shape and got.dtype == other.dtype
            assert bool(jnp.isfinite(got).all())
            assert rel(got, other) < GRAD_TOL
        for got, other in zip(columns_of(from_kernels[0], key_heads),
                              columns_of(want[0], key_heads)):
            assert rel(got, other) < GRAD_TOL


@pytest.mark.parametrize("heads,key_heads", [
    (kernels.HEADS_PER_STEP * 2, kernels.HEADS_PER_STEP),
    (kernels.HEADS_PER_STEP + 2, kernels.HEADS_PER_STEP // 2 + 1),
    (kernels.HEADS_PER_STEP * 2, kernels.HEADS_PER_STEP * 2)])
def test_mixer_heads_beyond_one_grid_step_and_a_batch(heads, key_heads):
    """Two grid steps of eight value heads over four key heads each; five
    of two over one; two of eight with a key head each: a step's blocks of
    q and k are found at the key heads' stride, v's and z's at the value
    heads', every batch row starts from zero and the norm weight's
    cotangent is summed over steps and rows."""
    args = mixer_inputs(130, 0.1, key_heads=key_heads, heads=heads)
    from_kernels, _, from_steps = mixer_forms(key_heads)
    assert rel(from_kernels(*args), from_steps(*args)) < FWD_TOL
    for got, want in zip(mixer_grads(from_kernels, args),
                         mixer_grads(from_steps, args)):
        assert rel(got, want) < GRAD_TOL


def test_mixer_bf16_tables_are_cast_up_in_the_kernel_and_rounded_once():
    """q, k, v and z enter as the bf16 the in-projection writes; the
    convolution's table between the two kernel pairs is bf16, rounded once;
    everything else between is float32; the output is rounded to bf16
    once, and so is the projection's cotangent (the taps', g's, beta's and
    the weight's stay float32)."""
    args = mixer_inputs(200, 0.05, key_heads=2, heads=4, dtype=jnp.bfloat16)
    from_kernels, _, from_steps = mixer_forms(2)
    got, want = from_kernels(*args), from_steps(*args)
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), want) < 2 ** -8
    got_grads = mixer_grads(from_kernels, args)
    assert [x.dtype for x in got_grads] == [x.dtype for x in args]
    up = tuple(x.astype(jnp.float32) for x in args)
    from_steps = functools.partial(stepwise_mixer, key_heads=2,
                                   table_dtype=jnp.bfloat16)
    # the bf16 output hands back a bf16 cotangent: every gradient carries
    # that rounding, the table's and the projection's their own besides
    for got, want in zip(got_grads, mixer_grads(from_steps, up)):
        assert rel(got.astype(jnp.float32), want) < 2 ** -7


def test_raw_operands_and_the_mixers_tables_reach_one_kernel_body(
        monkeypatch):
    """`gated_delta_rule(q, k, v, g, beta, head_block=)`, what the
    benchmark's rule check calls, runs the body the mixer's entry runs,
    with prologue and epilogue left out when it is traced: a static
    description of the operands (`_Form`), no argument of either entry."""
    seen = []
    for name in ("_fwd_kernel", "_bwd_kernel"):
        body = getattr(kernels, name)

        def spy(*refs, form, body=body, name=name):
            seen.append((name, form.mixer, form.ratio))
            return body(*refs, form=form)

        monkeypatch.setattr(kernels, name, spy)
    # shapes no other test of this file traces: the two calls are jitted
    raw = odd_rule_inputs(72, 0.05, h=6)
    jax.grad(lambda *a: kernels.gated_delta_rule_kernels(*a).sum())(*raw)
    assert seen == [("_fwd_kernel", False, 1), ("_bwd_kernel", False, 1)]
    del seen[:]
    args = mixer_inputs(72, 0.05, key_heads=3, heads=6)
    jax.grad(lambda *a: kernels.gated_delta_mixer_kernels(
        *a, EPSILON, key_heads=3).sum())(*args)
    assert seen == [("_fwd_kernel", True, 2), ("_bwd_kernel", True, 2)]
    assert list(inspect.signature(gdr.gated_delta_mixer).parameters) == [
        "qkvz", "taps", "g", "beta", "norm_w", "epsilon", "key_heads",
        "head_block"]


def all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(inner)


def pallas_calls(jaxpr):
    return (eqn for eqn in all_eqns(jaxpr)
            if eqn.primitive.name == "pallas_call")


def test_the_mixers_forward_keeps_the_two_residuals_and_not_its_output():
    """Undifferentiated: one output, in the tables' dtype. Differentiated:
    the chunk-start states and the inverses besides, as for raw operands;
    the rule's float32 output is not among them (the backward makes it
    again from what it has)."""
    args = mixer_inputs(128, 0.05, key_heads=2, heads=4, dtype=jnp.bfloat16)
    form = mixer_forms(2)[0]

    def outputs(f):
        before, call = pallas_calls(jax.make_jaxpr(f)(*args).jaxpr)
        assert [c.params["name"] for c in (before, call)] == [
            "gdn_conv_fwd", "gdn_rule_fwd"]
        return [(v.aval.shape, v.aval.dtype.name) for v in call.outvars]

    assert outputs(form) == [((2, 128, 4 * 8), "bfloat16")]
    assert outputs(lambda *a: jax.vjp(form, *a)[0]) == [
        ((2, 128, 4 * 8), "bfloat16"), ((2, 2, 4, 16, 8), "float32"),
        ((2, 2, 2, 64, 128), "float32")]


def test_a_ratio_the_packs_cannot_hold_goes_to_the_xla_form(monkeypatch):
    """Two value heads a key head or one are the kernels'; four, or three,
    are not: the shapes' gate says so, the kernels' entry refuses them, and
    on a TPU `gated_delta_mixer` then runs its XLA lines around the rule
    (whose raw operands the kernels do take)."""
    assert kernels.gdn_rule_supports(4, 16, 8, key_heads=2)
    assert kernels.gdn_rule_supports(4, 16, 8, key_heads=4)
    assert not kernels.gdn_rule_supports(8, 16, 8, key_heads=2)
    assert not kernels.gdn_rule_supports(6, 16, 8, key_heads=2)
    assert not kernels.gdn_rule_supports(6, 16, 8, key_heads=4)
    # v's columns begin inside a block of a step's value heads
    assert not kernels.gdn_rule_supports(8, 8, 16, key_heads=4)
    args = mixer_inputs(100, 0.05, key_heads=2, heads=8)
    with pytest.raises(ValueError, match="no shape of the kernels'"):
        kernels.gated_delta_mixer_kernels(*args, EPSILON, key_heads=2)
    monkeypatch.setattr(gdr, "gdn_rule_backend_supported", lambda: True)
    monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    calls = []
    for name in ("gated_delta_mixer_kernels", "gated_delta_rule_kernels"):
        real = getattr(gdr, name)
        monkeypatch.setattr(
            gdr, name, lambda *a, real=real, name=name, **kw: (
                calls.append(name), real(*a, **kw))[1])
    want = stepwise_mixer(*args, key_heads=2)
    assert rel(gdr.gated_delta_mixer(*args, EPSILON, key_heads=2,
                                     head_block=8), want) < FWD_TOL
    assert calls == ["gated_delta_rule_kernels"]
    args = mixer_inputs(100, 0.05, key_heads=4, heads=8)
    assert rel(gdr.gated_delta_mixer(*args, EPSILON, key_heads=4,
                                     head_block=8),
               stepwise_mixer(*args, key_heads=4)) < FWD_TOL
    assert calls[1:] == ["gated_delta_mixer_kernels"]


@pytest.mark.parametrize("program", ["gspmd", "shard_map"])
def test_the_mixer_lowered_for_a_tpu_on_two_devices(monkeypatch, program):
    """`test_lowered_for_a_tpu_on_two_devices` for the mixer's entry: the
    two-device GSPMD program is the XLA form as it was (a `while` over
    chunks, no Mosaic kernel), and per shard in a `shard_map` the kernels'
    new bodies go through the Pallas-to-Mosaic lowering at lane-aligned
    head sizes, bf16 tables in."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_pytorch_training_tpu.parallel.collectives import (
        shard_map,
    )
    from distributed_pytorch_training_tpu.parallel.mesh import BATCH_AXES

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    b, s, key_heads, h, d = 2, 128, 1, 2, 128
    shapes = [((b, s, (2 * key_heads + 2 * h) * d), jnp.bfloat16),
              ((4, (2 * key_heads + h) * d), jnp.float32),
              ((b, s, h), jnp.float32), ((b, s, h), jnp.float32),
              ((d,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(*shape) for shape in shapes]
    mesh = two_device_mesh()
    mixer = lambda *a: gdr.gated_delta_mixer(  # noqa: E731
        *a, EPSILON, key_heads=key_heads, head_block=8)
    specs = (P(BATCH_AXES), P(), P(BATCH_AXES), P(BATCH_AXES), P())
    if program == "shard_map":
        mixer = shard_map(mixer, mesh, in_specs=specs,
                          out_specs=P(BATCH_AXES))
    mixer = jax.jit(mixer, in_shardings=tuple(
        NamedSharding(mesh, spec) for spec in specs),
        out_shardings=NamedSharding(mesh, P(BATCH_AXES)))
    step = jax.jit(jax.grad(
        lambda *a: mixer(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)))
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    for kernel in ("gdn_rule_fwd", "gdn_rule_bwd", "gdn_conv_fwd",
                   "gdn_conv_bwd"):
        assert (kernel in text) == (program == "shard_map")
    assert ("tpu_custom_call" in text) == (program == "shard_map")
    assert ("stablehlo.while" in text) == (program == "gspmd")


def test_hybrid_train_step_hands_the_kernels_the_mixers_own_tables(
        monkeypatch):
    """Does the mechanism engage: by shapes, in the one-device hybrid train
    step on the kernel path. Every `gdn_rule_fwd` reads q, k and v from ONE
    bf16 array, the convolution's output, in blocks a step's KEY heads wide
    for q and k; z is a block of the in-projection's own bf16 output, found
    by column; no float32 table a value head wide, repeated or not, feeds
    it; what it writes for ``out_proj`` is bf16. `gdn_rule_bwd` writes dq
    and dk at the key heads' width in bf16 and dz into the projection's
    full-width cotangent. PR 41: that table is what `gdn_conv_fwd` wrote
    from the projection's output as it stands, six forward and three
    backward calls a step of three layers with remat (the rule's forward
    three: the remat keeps what it wrote); `gdn_conv_bwd` fills
    the cotangent's other columns in place from dq, dk and dv as they
    stand; and nothing under ``gdn`` slices, pads or concatenates an array
    of the projection's width or of the convolution's."""
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

    monkeypatch.setattr(gdr, "gdn_rule_backend_supported", lambda: True)
    monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    config = json.loads(
        (ROOT / "benchmark/configs/qwen3_next_80b_a3b.json").read_text())
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    model = get_model("qwen3_next_80b_a3b", dtype=jnp.bfloat16, remat=True,
                      **config["rehearsal"]["model_overrides"])
    hk, hv = model.linear_num_key_heads, model.linear_num_value_heads
    dk, dv = model.linear_key_head_dim, model.linear_value_head_dim
    assert hv == 2 * hk
    trainer = Trainer(LanguageModelingTask(compute_dtype=jnp.bfloat16), mesh,
                      TrainConfig(per_device_batch=2, bf16=True),
                      rules=type(model).partition_rules())
    state = trainer.init_state(
        model, np.zeros((1, 64), np.int32),
        make_optimizer("adamw", make_schedule("constant", 3e-4)),
        jax.random.PRNGKey(0))
    batch = shard_batch({"input_ids": np.zeros((2, 64), np.int32),
                         "weight": np.ones(2, np.float32)}, mesh)
    traced = trainer._train_step.trace(state, batch, jax.random.PRNGKey(0))
    calls = {"gdn_rule_fwd": [], "gdn_rule_bwd": [], "gdn_conv_fwd": [],
             "gdn_conv_bwd": []}
    for call in pallas_calls(traced.jaxpr.jaxpr):
        if call.params["name"] in calls:
            calls[call.params["name"]].append(call)
    layers = sum((i + 1) % model.full_attention_interval != 0
                 for i in range(model.depth))
    assert layers == 3
    # a layer's forward, its remat, its backward: the remat makes the
    # convolution's table again and keeps what the rule's forward wrote
    # (PR 50: `qwen3_next.LAYER_REMAT_POLICY`)
    assert len(calls["gdn_conv_fwd"]) == 2 * layers
    assert len(calls["gdn_rule_fwd"]) == layers
    for pair in ("gdn_rule", "gdn_conv"):
        assert len(calls[f"{pair}_bwd"]) == layers
    step_heads = kernels._heads_per_step(hv)
    conv_dim = 2 * hk * dk + hv * dv
    width = conv_dim + hv * dv
    made_by = {id(v): eqn for eqn in all_eqns(traced.jaxpr.jaxpr)
               for v in eqn.outvars}
    value_wide = lambda aval: aval.shape[-1] == hv * dv or \
        aval.shape[-2:] == (hv, dv)  # noqa: E731
    for call in calls["gdn_rule_fwd"] + calls["gdn_rule_bwd"]:
        q, k, v, _, _, z, weight = call.invars[:7]
        assert q is k is v
        assert (q.aval.shape[-1], q.aval.dtype) == (conv_dim, jnp.bfloat16)
        assert made_by[id(q)].params["name"] == "gdn_conv_fwd"
        # z: the in-projection's output itself, what the convolution read
        assert (z.aval.shape[-1], z.aval.dtype) == (width, jnp.bfloat16)
        assert z is made_by[id(q)].invars[0]
        assert weight.aval.shape == (1, dv)
        blocks = call.params["grid_mapping"].block_mappings
        assert [m.block_shape[-1].block_size for m in blocks[:3]] == [
            step_heads // 2 * dk, step_heads // 2 * dk, step_heads * dv]
        assert blocks[5].block_shape[-1].block_size == step_heads * dv
    for call in calls["gdn_rule_fwd"]:
        assert not any(value_wide(x.aval) and x.aval.dtype == jnp.float32
                       for x in call.invars)
        out = call.outvars[0].aval
        assert (out.shape[-1], out.dtype) == (hv * dv, jnp.bfloat16)
    for call in calls["gdn_rule_bwd"]:
        dq, dk_, dv_ = (x.aval for x in call.outvars[:3])
        assert dq.shape[-1] == dk_.shape[-1] == hk * dk
        assert dv_.shape[-1] == hv * dv
        assert {dq.dtype, dk_.dtype, dv_.dtype} == {jnp.dtype(jnp.bfloat16)}
        dz = call.outvars[5]
        assert (dz.aval.shape[-1], dz.aval.dtype) == (width, jnp.bfloat16)
        # the convolution's backward reads dq, dk, dv where they stand,
        # takes that array and returns it filled
        [fills] = [c for c in calls["gdn_conv_bwd"] if c.invars[6] is dz]
        assert all(a is b for a, b in zip(fills.invars[3:6],
                                          call.outvars[:3]))
        assert fills.params["input_output_aliases"] == ((6, 0),)
        assert fills.outvars[0].aval == dz.aval
    for call in calls["gdn_conv_fwd"] + calls["gdn_conv_bwd"]:
        assert call.invars[0].aval.shape[-1] == width
        assert made_by[id(call.invars[0])].primitive.name == "dot_general"
    cuts = [eqn for eqn in all_eqns(traced.jaxpr.jaxpr)
            if eqn.primitive.name in ("slice", "dynamic_slice", "pad",
                                      "concatenate", "gather")
            and "gdn" in str(eqn.source_info.name_stack)]
    # nor of the convolution's: dq, dk and dv are not joined either
    assert not [eqn for eqn in cuts for v in eqn.invars + eqn.outvars
                if v.aval.shape[-1:] in ((width,), (conv_dim,))]

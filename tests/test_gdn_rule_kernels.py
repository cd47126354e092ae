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
    for kernel in ("gdn_rule_fwd", "gdn_rule_bwd"):
        under = [p for p in paths if f"/{kernel}" in p]
        assert under, kernel
        assert {_regions.region_of(p, regions) for p in under} == {"gdn_rule"}
    assert any("transpose" in p for p in paths if "/gdn_rule_bwd" in p)
    # what the XLA form's head blocks were: a `while` under the rule's scope
    assert not any("gdn_rule/while" in p for p in paths)

"""The Gated DeltaNet mixer's convolution + SiLU as a kernel pair
(`ops/gdn_conv_kernels.py`, ISSUE 41), called directly so that it runs here,
in Pallas interpreter mode on the CPU.

Pins, in order:
* `gdn_conv_fwd` against the XLA form (`gated_delta_rule.causal_conv_silu`)
  and against a plain position-by-position convolution, over lengths the
  mixer pads to whole blocks (3, 100, 200), whole ones, and two that span
  three row blocks: the halo across a block edge, zeros before row 0;
* a batch above 1: the carry is zeroed per sequence, in both kernels;
* `gdn_conv_bwd` against the XLA form's autodiff: the cotangent of q | k |
  v's columns written INTO an array whose z columns stay as they came, and
  the taps' gradient, with the table's cotangent as one table and as three
  side by side (dq, dk, dv as the rule's backward leaves them); through the
  mixer's entry z's columns receive exactly the rule's dz;
* bf16 tables are cast up inside and rounded once;
* which form `gated_delta_mixer` takes: the XLA form off a TPU, over several
  devices under GSPMD and for convolved columns off the 128 lanes, the
  kernels on one TPU device, by what the code observes and no option;
* both kernels go through the Pallas-to-Mosaic lowering at the timed shape.
"""

import importlib
import inspect
import itertools

import jax
import jax.numpy as jnp
import pytest

from test_gdn_rule_kernels import (
    EPSILON, mixer_inputs, stepwise_conv_silu, stepwise_mixer,
)
from test_qwen3_next import FWD_TOL, GRAD_TOL, rel

gdr = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.gated_delta_rule")
kernels = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.gdn_rule_kernels")
conv = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.gdn_conv_kernels")

QUANTUM = conv.ROW_BLOCKS[-1]


def conv_inputs(length, *, b=2, conv_dim=96, z_dim=32, dtype=jnp.float32):
    """(qkvz, taps, the table's cotangent, an array of qkvz's shape to write
    the projection's cotangent into)."""
    ks = jax.random.split(jax.random.PRNGKey(length + conv_dim), 4)
    width = conv_dim + z_dim
    return (jax.random.normal(ks[0], (b, length, width)).astype(dtype),
            jax.random.uniform(ks[1], (4, conv_dim), jnp.float32, -0.5, 0.5),
            jax.random.normal(ks[2], (b, length, conv_dim)).astype(dtype),
            jax.random.normal(ks[3], (b, length, width)).astype(dtype))


def whole_blocks(x):
    """Zero rows behind the last, to whole blocks: what the mixer's entry
    does to a length the blocks do not divide."""
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % QUANTUM), (0, 0)))


def forward(qkvz, taps):
    return conv.conv_silu_forward(whole_blocks(qkvz), taps)[:, :qkvz.shape[1]]


def backward(qkvz, taps, dout, into, tables=None):
    """``tables``: the widths to hand ``dout`` over in, side by side."""
    ends = list(itertools.accumulate(tables or [dout.shape[-1]]))
    douts = tuple(whole_blocks(dout[..., lo:hi])
                  for lo, hi in zip([0] + ends[:-1], ends))
    dqkvz, dtaps = conv.conv_silu_backward(
        whole_blocks(qkvz), taps, douts, whole_blocks(into))
    return dqkvz[:, :qkvz.shape[1]], dtaps


def xla_gradients(qkvz, taps, dout):
    conv_dim = taps.shape[1]
    _, vjp = jax.vjp(lambda x, t: gdr.causal_conv_silu(x[..., :conv_dim], t),
                     qkvz, taps)
    return vjp(dout)


# ---------------------------------------------------------------------------
# the forward kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [3, 64, 100, 200, 192, 1536])
def test_forward_matches_the_xla_form_and_the_stepwise_convolution(length):
    qkvz, taps, _, _ = conv_inputs(length)
    conv_dim = taps.shape[1]
    if length in (192, 1536):       # three row blocks: two edges to cross
        rows, _ = conv._blocks(length, (conv_dim,))
        assert length // rows == 3
    got = forward(qkvz, taps)
    assert got.shape == (*qkvz.shape[:2], conv_dim)
    assert got.dtype == qkvz.dtype
    assert rel(got, gdr.causal_conv_silu(qkvz[..., :conv_dim], taps)) < 1e-6
    assert rel(got, stepwise_conv_silu(qkvz[..., :conv_dim], taps)) < 1e-6


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_every_sequence_of_a_batch_starts_and_ends_at_zeros(kernel):
    """The windows' carries are per sequence, not per call: a batch row's
    result is what it is alone, bit for bit, whatever the row before it
    ends with and the row behind it starts with."""
    qkvz, taps, dout, into = conv_inputs(128, b=3)
    loud = lambda x: x.at[0, -4:].set(1e3).at[2, :4].set(1e3)  # noqa: E731
    qkvz, dout = loud(qkvz), loud(dout)
    alone = lambda *xs: [x[1:2] for x in xs]  # noqa: E731
    if kernel == "forward":
        assert (forward(qkvz, taps)[1:2]
                == forward(*alone(qkvz), taps)).all()
        return
    dqkvz, _ = backward(qkvz, taps, dout, into)
    a, d, i = alone(qkvz, dout, into)
    assert (dqkvz[1:2] == backward(a, taps, d, i)[0]).all()
    assert bool(jnp.isfinite(dqkvz).all())


def test_a_length_off_the_row_blocks_is_the_mixers_to_pad():
    qkvz, taps, _, _ = conv_inputs(100)
    with pytest.raises(ValueError, match="not whole blocks"):
        conv.conv_silu_forward(qkvz, taps)


# ---------------------------------------------------------------------------
# the backward kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length,conv_dim,z_dim,tables", [
    (64, 96, 32, None), (100, 96, 32, None), (192, 96, 32, (32, 32, 32)),
    (1536, 128, 128, None), (128, 1024, 512, None),
    (192, 1024, 512, (256, 256, 512)), (128, 96, 32, (24, 24, 48))],
    ids=["one_block", "padded", "three_blocks_three_tables",
         "three_blocks_of_512", "two_column_blocks",
         "q_k_v_apart_in_blocks_of_256", "q_k_v_apart_off_the_lanes"])
def test_backward_matches_the_xla_forms_autodiff(length, conv_dim, z_dim,
                                                 tables):
    """dx in q | k | v's columns of ``into``, whose z columns stay exactly
    as they came; the taps' gradient summed over rows and the batch. The
    table's cotangent comes as one table or as several side by side, each
    column block of the grid then read from the one it lies in."""
    qkvz, taps, dout, into = conv_inputs(length, conv_dim=conv_dim,
                                         z_dim=z_dim)
    columns = conv._blocks(-(-length // QUANTUM) * QUANTUM,
                           tables or (conv_dim,))[1]
    if conv_dim == 1024:
        assert columns == (256 if tables else 512)
    elif tables:
        assert columns == min(tables)
    dqkvz, dtaps = backward(qkvz, taps, dout, into, tables)
    want_dx, want_dtaps = xla_gradients(qkvz, taps, dout)
    assert dqkvz.shape == qkvz.shape and dqkvz.dtype == qkvz.dtype
    assert (dtaps.shape, dtaps.dtype) == (taps.shape, jnp.float32)
    assert rel(dqkvz[..., :conv_dim], want_dx[..., :conv_dim]) < 1e-5
    assert (dqkvz[..., conv_dim:] == into[..., conv_dim:]).all()
    assert rel(dtaps, want_dtaps) < 1e-5


def test_through_the_mixer_z_columns_receive_exactly_the_rules_dz():
    """The projection's cotangent is written once, by two kernels: the
    rule's backward leaves dz in z's columns of a full-width array, the
    convolution's fills the others in place. Against the two calls made by
    hand, bit for bit; and the taps' gradient against the XLA form's."""
    key_heads, heads = 2, 4
    args = mixer_inputs(128, 0.05, key_heads=key_heads, heads=heads)
    qkvz, taps, g, beta, norm_w = args
    conv_dim = taps.shape[1]
    mixer = lambda *a: kernels.gated_delta_mixer_kernels(  # noqa: E731
        *a, EPSILON, key_heads=key_heads)
    out, vjp = jax.vjp(mixer, *args)
    dout = jax.random.normal(jax.random.PRNGKey(3), out.shape)
    dqkvz, dtaps, *_ = vjp(dout)
    form = kernels._Form(heads, key_heads, 16, 8, EPSILON)
    qkv = conv.conv_silu_forward(qkvz, taps)
    gate = kernels._gate(qkvz, norm_w)
    _, starts, inverses = kernels._forward(qkv, qkv, qkv, g, beta, gate,
                                           form=form, residuals=True)
    dq, dk, dv, _, _, dz, _ = kernels._backward(
        qkv, qkv, qkv, g, beta, gate, starts, inverses, dout, form=form)
    assert dz.shape == qkvz.shape
    assert (dqkvz[..., conv_dim:] == dz[..., conv_dim:]).all()
    assert bool(jnp.abs(dz[..., conv_dim:]).max() > 0)
    by_hand, _ = conv.conv_silu_backward(qkvz, taps, (dq, dk, dv), dz)
    assert (dqkvz == by_hand).all()
    xla = lambda *a: gdr.gated_delta_mixer(  # noqa: E731
        *a, EPSILON, key_heads=key_heads, head_block=2)
    want_dqkvz, want_dtaps, *_ = jax.vjp(xla, *args)[1](dout)
    assert rel(dqkvz, want_dqkvz) < GRAD_TOL
    assert rel(dtaps, want_dtaps) < GRAD_TOL


def test_bf16_tables_are_cast_up_inside_and_rounded_once():
    """What the kernels read is the bf16 the in-projection wrote; the four
    products, their sum and the SiLU are float32; the table is rounded to
    bf16 once (the lines this replaced rounded after each of seven
    operations: they sit several times further from the float32 result),
    and so is the projection's cotangent; the taps' gradient is float32."""
    qkvz, taps, dout, into = conv_inputs(200, dtype=jnp.bfloat16)
    conv_dim = taps.shape[1]
    up = lambda x: x.astype(jnp.float32)  # noqa: E731
    exact = gdr.causal_conv_silu(up(qkvz[..., :conv_dim]), taps)
    got = forward(qkvz, taps)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.mean(got == exact.astype(jnp.bfloat16))) > 0.999
    x = qkvz[..., :conv_dim]
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    rounded_each = jax.nn.silu(sum(                  # the parent's lines
        padded[:, j:j + x.shape[1]] * taps[j].astype(x.dtype)
        for j in range(4)))
    assert rel(up(got), exact) < 2 ** -8
    assert rel(up(rounded_each), exact) > 2 * rel(up(got), exact)
    dqkvz, dtaps = backward(qkvz, taps, dout, into)
    want_dx, want_dtaps = xla_gradients(up(qkvz), taps, up(dout))
    assert dqkvz.dtype == jnp.bfloat16 and dtaps.dtype == jnp.float32
    want_dx = want_dx[..., :conv_dim]
    assert float(jnp.mean(dqkvz[..., :conv_dim]
                          == want_dx.astype(jnp.bfloat16))) > 0.999
    assert rel(up(dqkvz[..., :conv_dim]), want_dx) < 2 ** -8
    assert (dqkvz[..., conv_dim:] == into[..., conv_dim:]).all()
    assert rel(dtaps, want_dtaps) < 1e-5


# ---------------------------------------------------------------------------
# which form the mixer takes
# ---------------------------------------------------------------------------

def test_the_shapes_gate_of_the_convolutions_blocks(monkeypatch):
    assert conv.gdn_conv_supports((32, 32, 32), 4)   # the interpreter: any
    assert not conv.gdn_conv_supports((32, 32, 32), conv.HALO + 2)
    monkeypatch.setattr(conv, "_interpret", lambda: False)
    assert conv.gdn_conv_supports((2048, 2048, 4096), 4)
    assert conv.gdn_conv_supports((128,), 4)
    assert not conv.gdn_conv_supports((32, 32, 32), 4)
    assert not conv.gdn_conv_supports((2048, 2048, 4096 + 64), 4)
    assert conv._blocks(8192, (8192,)) == (conv.ROW_BLOCKS[0],
                                           conv.COLUMN_BLOCKS[0])
    assert conv._blocks(8192, (2048, 2048, 4096)) == (512, 512)
    assert conv._blocks(192, (128, 128, 256)) == (64, 128)


@pytest.mark.parametrize("where", [
    "off_a_tpu", "gspmd_over_devices", "columns_off_the_lanes",
    "one_tpu_device"])
def test_the_mixer_takes_both_kernel_pairs_or_neither(monkeypatch, where):
    """Chosen by backend, shapes and trace alone: off a TPU, in a program
    over several devices that GSPMD partitions, and for convolved columns
    the lane blocks do not divide, the XLA form with the same numbers; on
    one TPU device both kernel pairs. No flag, no environment variable."""
    if where != "off_a_tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kernels, "_interpret", lambda: True)
    if where in ("columns_off_the_lanes", "one_tpu_device"):
        monkeypatch.setattr(gdr, "gdn_rule_one_device_trace", lambda: True)
    # 96 convolved columns: the interpreter takes them, a TPU's lanes do not
    monkeypatch.setattr(conv, "_interpret",
                        lambda: where != "columns_off_the_lanes")
    assert jax.device_count() > 1
    taken = []
    real_kernels = gdr.gated_delta_mixer_kernels
    monkeypatch.setattr(
        gdr, "gated_delta_mixer_kernels",
        lambda *a, **kw: (taken.append("kernels"), real_kernels(*a, **kw))[1])
    real_xla = gdr.causal_conv_silu
    monkeypatch.setattr(
        gdr, "causal_conv_silu",
        lambda *a: (taken.append("xla"), real_xla(*a))[1])
    args = mixer_inputs(100, 0.05, key_heads=2, heads=4)
    got = gdr.gated_delta_mixer(*args, EPSILON, key_heads=2, head_block=2)
    assert taken == (["kernels"] if where == "one_tpu_device" else ["xla"])
    assert rel(got, stepwise_mixer(*args, key_heads=2)) < FWD_TOL
    for module in (gdr, conv):
        assert "environ" not in inspect.getsource(module)
    assert list(inspect.signature(conv.gdn_conv_supports).parameters) == [
        "widths", "taps"]


# ---------------------------------------------------------------------------
# lowered for a TPU at the timed shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["gdn_conv_fwd", "gdn_conv_bwd"])
def test_lowered_for_a_tpu_at_the_timed_shape(monkeypatch, kernel):
    """The Pallas-to-Mosaic lowering (no compile, nothing runs) of each
    kernel at the cell's shape: one sequence of 8,192 rows, 8,192 convolved
    columns of 12,288 in bf16, blocks of 512 x 512, the table's cotangent
    as the rule's backward leaves it (dq, dk, dv)."""
    monkeypatch.setattr(conv, "_interpret", lambda: False)
    qkvz = jax.ShapeDtypeStruct((1, 8192, 12288), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 8192), jnp.float32)
    douts = tuple(jax.ShapeDtypeStruct((1, 8192, w), jnp.bfloat16)
                  for w in (2048, 2048, 4096))
    # the two calls are jitted by shape: trace them anew, on this path
    if kernel == "gdn_conv_fwd":
        traced = jax.jit(conv.conv_silu_forward.__wrapped__).trace(qkvz, taps)
    else:
        traced = jax.jit(conv.conv_silu_backward.__wrapped__).trace(
            qkvz, taps, douts, qkvz)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert text.count("tpu_custom_call") == 1

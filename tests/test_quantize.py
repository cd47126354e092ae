"""Fused int8 codec kernels (ops/quantize.py, ISSUE 6 tentpole 2).

The binding contract (PARITY.md): the Pallas kernels are BIT-IDENTICAL to
the XLA-composed reference codecs in parallel/grad_sync.py — same absmax,
same ``max(amax, 1e-30) * (1/127)`` scale, same round/clip, same fp32
dequant-sum reduction order. On the CPU tier-1 backend they run in
interpreter mode (forced here via ``fused=True`` — the gate itself keeps
CPU on the XLA-composed reference by default), so what these tests pin is
the kernel's arithmetic, and the TPU run only changes the scheduling.
Codes and scales are `==` here too; the dequant-sum is bitwise on the chip
(`chip_smoke.py` phase 1 reads it) and held to a derived bound here,
because this XLA:CPU build contracts the interpreted body into FMAs
(`_assert_dequant_sum_matches`).

Three layers:
* kernel-level bit-identity on TPU-shaped and edge-case vectors (acceptance
  criterion: "bit-identical to `_quantize_int8_rows` on TPU-shaped test
  vectors, interpreter mode in tier-1");
* gate/selection semantics (`resolve_fused`: explicit config beats the
  DPT_FUSED_QUANTIZE env, which beats the TPU-only backend default);
* whole-step bitwise parity: an `int8_multihop` training run with the
  kernel path selected lands bit-for-bit where the XLA-composed run lands
  (the int8 parity suites "pass unchanged with the kernel path selected" —
  bit-identical codecs compose to a bit-identical trajectory).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.ops.quantize import (
    FUSED_QUANTIZE_ENV, dequant_sum_rows_fused, fused_quantize_default,
    quantize_backend_supported, quantize_int8_rows_fused, resolve_fused,
)
from distributed_pytorch_training_tpu.parallel.grad_sync import (
    _dequant_sum_rows, _quantize_int8_rows,
)

# TPU-shaped vectors (the codec's real shapes: n replicas x a bucket chunk,
# chunk a multiple of nothing in particular) plus the edge cases.
SHAPES = [(8, 16384),   # a real bucket: 8 replicas x 64KiB/4 chunk
          (4, 128),     # exactly one lane block
          (3, 200),     # ragged: padding in the last block
          (1, 5),       # single row, sub-lane chunk
          (2, 1),       # degenerate chunk
          (16, 1000)]   # many rows, ragged


def _rand_rows(shape, seed=0, scale=10.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


def _assert_dequant_sum_matches(got, q, s):
    """The fused dequant-sum against ``_dequant_sum_rows(q, s, fused=False)``.

    Bitwise is the CHIP's property: Mosaic compiles the kernel body as
    written, and `chip_smoke.py` phase 1 reads it there every run (found
    bitwise at its three shapes, PR 21; held to fp32 rounding). In interpreter
    mode the body is one more XLA:CPU program, and jax 0.9.0's XLA:CPU
    contracts its ``q * s`` and the row accumulate into an FMA: found
    (PR 30) the kernel equals ``acc = fl(acc + q_i * s_i)`` row by row and
    the reference equals ``acc = fl(acc + fl(q_i * s_i))``, both exactly, so
    the order of the reduction IS the reference's and only the product's
    rounding is skipped. Found gap: max abs 1.5e-5 = one ulp at 128, the
    partial sums' size (up to 2048 ulps of an output near zero, which is why
    no ulp count of the OUTPUT is the bound). What a CPU run can hold is
    that bound derived: row 0 agrees exactly, and each later row moves the
    two accumulators apart by at most 1.5 ulp at the column's magnitude
    ``sum_i |q_i * s_i|`` (half for the product, half for each add). Found:
    2.0 of those ulps at worst, against 10.5 allowed at 8 rows. One row is
    still ``==``."""
    want = np.asarray(_dequant_sum_rows(q, s, fused=False))
    terms = np.abs(np.asarray(q, np.float32) * np.asarray(s)[:, None])
    bound = 1.5 * (q.shape[0] - 1) * np.spacing(terms.sum(axis=0))
    gap = np.abs(np.asarray(got) - want)
    assert np.all(gap <= bound), (
        f"worst {np.max(gap / np.maximum(bound, 1e-45)):.2f} x the bound")


class TestKernelBitIdentity:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_quantize_bit_identical(self, shape):
        rows = _rand_rows(shape)
        q_ref, s_ref = _quantize_int8_rows(rows, fused=False)
        q_fused, s_fused = quantize_int8_rows_fused(rows)
        assert q_fused.dtype == jnp.int8 and s_fused.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_fused))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_fused))

    @pytest.mark.parametrize("shape", SHAPES)
    def test_dequant_sum_matches_reference(self, shape):
        q, s = _quantize_int8_rows(_rand_rows(shape, seed=1), fused=False)
        _assert_dequant_sum_matches(dequant_sum_rows_fused(q, s), q, s)

    def test_row_split_tiles_bit_identical(self):
        """The int8 KV-page scatter quantizes tens of thousands of short
        rows; past `_ROW_SPLIT_BYTES` the kernel tiles the ROW axis too
        (an un-split tile outgrows VMEM on the chip). Rows are independent,
        so the split — including its zero-padded last row block — changes
        no code and no scale."""
        from distributed_pytorch_training_tpu.ops.quantize import (
            _fit_block, _fit_rows,
        )

        shape = (8200, 64)
        block_r, padded_n = _fit_rows(shape[0], _fit_block(shape[1],
                                                           shape[0])[0])
        assert block_r < shape[0] < padded_n  # split, with row padding
        rows = _rand_rows(shape, seed=3)
        q_ref, s_ref = _quantize_int8_rows(rows, fused=False)
        q_fused, s_fused = quantize_int8_rows_fused(rows)
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_fused))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_fused))

    def test_zero_rows_hit_the_scale_floor(self):
        """All-zero rows exercise the 1e-30 floor: codes 0, scale
        1e-30/127 — identical on both paths (the floor is what keeps the
        divide finite)."""
        rows = jnp.zeros((3, 300), jnp.float32)
        q_ref, s_ref = _quantize_int8_rows(rows, fused=False)
        q_fused, s_fused = quantize_int8_rows_fused(rows)
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_fused))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_fused))
        assert not np.any(np.isnan(np.asarray(s_fused)))

    def test_mixed_magnitude_rows(self):
        """Per-row scales are independent: a tiny row next to a huge row
        must not leak scale across rows on either path."""
        rows = jnp.stack([_rand_rows((400,), seed=2, scale=1e-6),
                          _rand_rows((400,), seed=3, scale=1e6),
                          jnp.zeros(400, jnp.float32)])
        q_ref, s_ref = _quantize_int8_rows(rows, fused=False)
        q_fused, s_fused = quantize_int8_rows_fused(rows)
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_fused))
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_fused))

    def test_grid_codes_roundtrip_exactly(self):
        """Values already ON the int8 grid quantize losslessly through the
        fused kernel, like the reference (TestMultihopCodec's grid case)."""
        scale = 0.125
        codes = np.arange(-127, 128, dtype=np.float32)
        rows = jnp.asarray((codes * scale)[None])
        q, s = quantize_int8_rows_fused(rows)
        np.testing.assert_array_equal(np.asarray(q)[0], codes.astype(np.int8))
        np.testing.assert_allclose(float(s[0]), scale, rtol=1e-7)

    def test_inside_jit(self):
        """The codecs run inside compiled steps — the kernels must lower
        (interpreter mode on CPU) under jit: identical codes and scales,
        and the sum held as `_assert_dequant_sum_matches` holds it."""
        rows = _rand_rows((4, 300), seed=4)

        @jax.jit
        def f(r):
            q, s = quantize_int8_rows_fused(r)
            return q, s, dequant_sum_rows_fused(q, s)

        q, s, out = f(rows)
        q_ref, s_ref = _quantize_int8_rows(rows, fused=False)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
        _assert_dequant_sum_matches(out, q_ref, s_ref)


class TestGate:
    def test_backend_gate_is_tpu_only(self):
        assert quantize_backend_supported("tpu")
        assert not quantize_backend_supported("cpu")
        assert not quantize_backend_supported("gpu")
        # tier-1 runs on CPU: the default must be the XLA-composed path
        assert jax.default_backend() == "cpu"
        assert not quantize_backend_supported()

    def test_env_override_beats_backend(self, monkeypatch):
        monkeypatch.setenv(FUSED_QUANTIZE_ENV, "1")
        assert fused_quantize_default() is True
        monkeypatch.setenv(FUSED_QUANTIZE_ENV, "0")
        assert fused_quantize_default() is False
        monkeypatch.setenv(FUSED_QUANTIZE_ENV, "bogus")  # ignored, not a crash
        assert fused_quantize_default() == quantize_backend_supported()

    def test_explicit_flag_beats_everything(self, monkeypatch):
        monkeypatch.setenv(FUSED_QUANTIZE_ENV, "0")
        assert resolve_fused(True) is True
        monkeypatch.setenv(FUSED_QUANTIZE_ENV, "1")
        assert resolve_fused(False) is False
        assert resolve_fused(None) is True  # None = auto: env decides

    def test_codecs_follow_the_resolved_gate(self, monkeypatch):
        """grad_sync's reference implementations must not silently call
        back into the kernels: fused=False IS the XLA-composed path even
        when the env forces the kernels on."""
        monkeypatch.setenv(FUSED_QUANTIZE_ENV, "1")
        rows = _rand_rows((2, 100), seed=5)
        # both paths still agree bit-for-bit, so equality can't distinguish
        # them — instead pin that fused=None routes through the kernel
        # wrapper (padding machinery accepts TPU-hostile widths) without
        # error, and fused=False never imports trouble
        q_auto, s_auto = _quantize_int8_rows(rows)          # kernel path
        q_ref, s_ref = _quantize_int8_rows(rows, fused=False)
        np.testing.assert_array_equal(np.asarray(q_auto), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(s_auto), np.asarray(s_ref))


class TestStepParity:
    """Whole-step bitwise parity on the CPU mesh (interpreter mode): the
    int8/int8_multihop trajectories are IDENTICAL with the kernel path
    selected — the acceptance criterion's 'parity tests pass unchanged'
    strengthened to bit-equality, which bit-identical codecs must give."""

    def _run(self, mesh8, steps=6, **cfg):
        from tests.test_grad_sync import _batch, _trainer

        t, s = _trainer(mesh8, **cfg)
        batch = _batch(mesh8)
        key = jax.random.PRNGKey(1)
        for _ in range(steps):
            s, _m = t._train_step(s, batch, key)
        return s

    def _assert_bitwise(self, a, b):
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(jax.device_get(x)),
                                          np.asarray(jax.device_get(y)))

    @pytest.mark.parametrize("wire", [
        # ~7 s; the gather-wire fused kernels stay pinned fast by the
        # paged-KV fused-scatter bitwise legs (same _quantize_int8_rows
        # kernels) and the gsync_int8_mh_fused matrix contract
        pytest.param("int8", marks=pytest.mark.slow),
        "int8_multihop",
    ])
    def test_fused_step_bitwise_equals_composed(self, mesh8, wire):
        base = dict(bucket_cap_mb=0.25, wire_dtype=wire)
        fused = self._run(mesh8, fused_quantize=True, **base)
        composed = self._run(mesh8, fused_quantize=False, **base)
        self._assert_bitwise(fused, composed)
        assert int(fused.step) == int(composed.step) == 6

    @pytest.mark.slow  # ~23 s; zero1 x multihop parity is pinned fast by test_grad_sync, fused-vs-composed by the fast [int8_multihop] leg
    def test_zero1_multihop_fused_bitwise(self, mesh8):
        """The zero1+multihop composition (compressed scatter + quantized
        delta gather) routes BOTH codec call sites through the kernels."""
        base = dict(zero1=True, wire_dtype="int8_multihop")
        fused = self._run(mesh8, fused_quantize=True, **base)
        composed = self._run(mesh8, fused_quantize=False, **base)
        self._assert_bitwise(fused, composed)

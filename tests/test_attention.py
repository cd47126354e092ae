"""Attention kernel tests: flash (Pallas, interpreter mode on CPU) and ring
(shard_map over the seq axis) against the XLA reference — values and
gradients (SURVEY.md §5 long-context requirements)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.models.layers import dot_product_attention
from distributed_pytorch_training_tpu.ops import (
    flash_attention,
    make_flash_attention_fn,
    make_ring_attention_fn,
    ring_attention,
)
from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh


def _rand_qkv(b=2, s=128, h=4, d=32, seed=0):
    rng = np.random.RandomState(seed)
    shape = (b, s, h, d)
    q = rng.randn(*shape).astype(np.float32) * 0.5
    k = rng.randn(*shape).astype(np.float32) * 0.5
    v = rng.randn(*shape).astype(np.float32) * 0.5
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _ref(q, k, v, causal):
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))[None, None]
    return dot_product_attention(q, k, v, mask=mask)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _rand_qkv()
        out = flash_attention(q, k, v, causal, None, 64, 64)
        expect = _ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    def test_uneven_blocks_auto_fit(self):
        # 100 has no divisor that is a multiple of 8, so the block picker
        # falls back to spanning the axis — still correct, never an error.
        q, k, v = _rand_qkv(s=100)
        out = flash_attention(q, k, v, False, None, 64, 64)
        expect = _ref(q, k, v, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)
        # 96 = 12 blocks of 8: picker takes the largest <=64 divisor (48).
        from distributed_pytorch_training_tpu.ops.flash_attention import (
            _fit_block,
        )
        assert _fit_block(64, 96) == 48
        assert _fit_block(64, 100) == 100
        assert _fit_block(512, 1024) == 512
        assert _fit_block(512, 384) == 384
        # degenerate divisors (8 | 2056 but grid would be 257 tiny tiles)
        # and sub-8 requests must not produce pathological kernels
        with pytest.raises(ValueError, match="block"):
            _fit_block(512, 2056)
        assert _fit_block(4, 2048) == 8
        assert _fit_block(512, 1032) == 344  # >= s//8 floor keeps the grid sane

    @pytest.mark.slow
    def test_gradients_match_reference(self):
        q, k, v = _rand_qkv(b=1, s=64, h=2, d=16)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, True, None, 32, 32) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q, k, v, True) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


    @pytest.mark.slow
    def test_long_context_grad_parity_s4096(self):
        """S=4096 forward+backward through the blockwise Pallas kernels
        (interpreter mode) vs the XLA reference — the long-context bar from
        SURVEY.md §5. The r2 backward was an O(S^2) recompute; this exercises
        the real dq/dk/dv kernels at a length where the (S,S) score matrix
        (64 MB fp32 per head) would no longer be a reasonable residual."""
        q, k, v = _rand_qkv(b=1, s=4096, h=1, d=64, seed=3)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, True, None, 512, 512) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q, k, v, True) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} diverges at S=4096")

    @pytest.mark.slow
    def test_bf16_grad_parity(self):
        """bf16 inputs (the TPU compute dtype): kernel stats stay fp32, so
        grads must track the fp32-stat reference within bf16 tolerance."""
        q, k, v = _rand_qkv(b=1, s=256, h=2, d=32, seed=4)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, True, None, 128, 128)
                    .astype(jnp.float32) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), True) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(qb, kb, vb)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=0.1, atol=0.5)

    def test_adapter_general_mask_falls_back_to_einsum(self):
        """A mask with (Sq, Sk) structure has no blockwise formulation here;
        the adapter must fall back to the XLA path (bit-equal), not error —
        the fast path narrowing to a ValueError on real data was r3 weak-#3."""
        fn = make_flash_attention_fn(causal=True)
        q, k, v = _rand_qkv(b=2, s=64)
        rng = np.random.RandomState(7)
        general = jnp.asarray(rng.rand(2, 1, 64, 64) > 0.3)
        out = fn(q, k, v, mask=general)
        cm = jnp.tril(jnp.ones((64, 64), bool))[None, None]
        expect = dot_product_attention(q, k, v, mask=general & cm)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


    def test_adapter_on_a_mesh_runs_per_shard_and_matches(self, devices):
        """GSPMD cannot partition a Mosaic kernel (a lowering error on any
        multi-device TPU program), so given a mesh the adapter runs the
        kernel per shard inside a shard_map — batch over the batch axes,
        heads over ``model`` — under the implicit (jit) step, and stays the
        plain call when traced from inside an explicit shard_map step.
        Values and gradients match the un-wrapped call; the kv_valid form
        rides the same wrapper."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_pytorch_training_tpu.parallel.collectives import (
            shard_map,
        )
        from distributed_pytorch_training_tpu.parallel.mesh import BATCH_AXES

        mesh = build_mesh(MeshSpec(data=4, model=2), devices=devices)
        plain = make_flash_attention_fn(causal=True)
        meshed = make_flash_attention_fn(causal=True, mesh=mesh)
        q, k, v = _rand_qkv(b=8, s=64)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        jaxpr = str(jax.make_jaxpr(meshed)(q, k, v))
        assert "shard_map" in jaxpr
        got = jax.jit(jax.grad(loss(meshed), argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)
        assert got[0].sharding.is_equivalent_to(
            NamedSharding(mesh, P(BATCH_AXES, None, "model", None)), 4)

        # key-padding mask + a batch the shards do not divide (init's B=1)
        mask = jnp.asarray(np.arange(64)[None, :] < 50)[:, None, None, :]
        np.testing.assert_allclose(
            np.asarray(meshed(q[:1], k[:1], v[:1], mask=mask)),
            np.asarray(plain(q[:1], k[:1], v[:1], mask=mask)),
            rtol=2e-5, atol=2e-5)

        # already inside a manual region: no nested shard_map
        spec = P(BATCH_AXES, None, "model", None)
        inner = shard_map(meshed, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
        assert str(jax.make_jaxpr(inner)(q, k, v)).count("shard_map") == 1
        np.testing.assert_allclose(np.asarray(inner(q, k, v)),
                                   np.asarray(plain(q, k, v)),
                                   rtol=2e-5, atol=2e-5)


class TestFlashPaddingMask:
    """Key-padding masks ride the Pallas kernels (VERDICT r3 #2): BERT on
    real padded batches must keep the flash path, gradients included."""

    def _padded_mask(self, b, s, n_pad, front=False):
        valid = np.ones((b, s), np.float32)
        if front:
            valid[:, :n_pad] = 0.0  # all-masked FIRST blocks: the online
            # softmax accumulates p=1 garbage until the first live block
            # rescales it to 0 — the hard case for the m=NEG_INF init
        else:
            valid[:, s - n_pad:] = 0.0
        return jnp.asarray(valid)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("front", [False, True])
    def test_padded_forward_matches_reference(self, causal, front):
        q, k, v = _rand_qkv(b=2, s=128)
        kv_valid = self._padded_mask(2, 128, 40, front)
        out = flash_attention(q, k, v, causal, None, 64, 64, kv_valid)
        mask = kv_valid[:, None, None, :].astype(bool)
        if causal:
            mask = mask & jnp.tril(jnp.ones((128, 128), bool))[None, None]
        expect = dot_product_attention(q, k, v, mask=mask)
        valid_rows = np.asarray(kv_valid, bool) if causal else \
            np.ones((2, 128), bool)
        # padded-out query rows emit garbage by contract (loss zero-weights
        # them); compare only rows with at least one live key
        np.testing.assert_allclose(
            np.asarray(out)[valid_rows], np.asarray(expect)[valid_rows],
            rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_padded_gradients_match_reference(self):
        """Grad parity under the real contract: the loss zero-weights padded
        query rows, so their garbage output contributes no cotangent."""
        q, k, v = _rand_qkv(b=2, s=128, h=2, d=16, seed=5)
        kv_valid = self._padded_mask(2, 128, 48)
        w = kv_valid[:, :, None, None]  # zero-weight padded query rows

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, False, None, 64, 64, kv_valid)
            return ((out * w) ** 2).sum()

        def loss_ref(q, k, v):
            mask = kv_valid[:, None, None, :].astype(bool)
            return ((dot_product_attention(q, k, v, mask=mask) * w) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} diverges (padded)")
        # no gradient may leak into padded K/V positions
        pad = np.asarray(kv_valid) == 0
        for g, name in ((g_flash[1], "dk"), (g_flash[2], "dv")):
            leaked = np.abs(np.asarray(g)[pad]).max()
            assert leaked < 1e-6, f"{name} leaks {leaked} into padding"

    @pytest.mark.slow
    def test_long_context_padded_grad_parity_s4096(self):
        """The S=4096 grad-parity bar from r2/r3, now with padded rows
        (VERDICT r3 #2's done-criterion)."""
        q, k, v = _rand_qkv(b=1, s=4096, h=1, d=64, seed=6)
        kv_valid = self._padded_mask(1, 4096, 512)
        w = kv_valid[:, :, None, None]

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, True, None, 512, 512, kv_valid)
            return ((out * w) ** 2).sum()

        def loss_ref(q, k, v):
            mask = kv_valid[:, None, None, :].astype(bool) & \
                jnp.tril(jnp.ones((4096, 4096), bool))[None, None]
            return ((dot_product_attention(q, k, v, mask=mask) * w) ** 2).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3,
                err_msg=f"d{name} diverges at S=4096 (padded)")

    def test_adapter_padding_mask_takes_kernel_path(self):
        """The (B, 1, 1, Sk) padding_mask form must ride the kernel, and
        match the einsum path on the valid rows."""
        from distributed_pytorch_training_tpu.models.layers import padding_mask

        q, k, v = _rand_qkv(b=2, s=64)
        am = self._padded_mask(2, 64, 16)
        fn = make_flash_attention_fn(causal=False, block_q=32, block_k=32)
        out = fn(q, k, v, mask=padding_mask(am))
        expect = dot_product_attention(q, k, v, mask=padding_mask(am))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)


class TestRingAttention:
    @pytest.fixture(scope="class")
    def seq_mesh(self, devices):
        return build_mesh(MeshSpec(data=2, seq=4), devices=devices)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, seq_mesh, causal):
        q, k, v = _rand_qkv(b=2, s=64, h=2, d=16)
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, seq_mesh, causal=causal))(q, k, v)
        expect = _ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_gradients_flow_through_ring(self, seq_mesh):
        q, k, v = _rand_qkv(b=2, s=32, h=2, d=8)

        def loss_ring(q, k, v):
            return (ring_attention(q, k, v, seq_mesh, causal=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q, k, v, True) ** 2).sum()

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_seq_axis_1_degrades_gracefully(self, devices):
        # mesh with seq=1: ring of length 1 == plain attention
        mesh = build_mesh(MeshSpec(data=2), devices=devices[:2])
        q, k, v = _rand_qkv(b=2, s=32, h=2, d=8)
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(q, k, v, True)),
                                   rtol=2e-5, atol=2e-5)


class TestModelKernelIntegration:
    """Kernel plumbing THROUGH a real GPT2LMHead (mask routing, adapter
    dispatch, logits parity) — the property is architecture-independent, so
    a shrunk gpt2_124m keeps these in the FAST set (the full-size variants
    cost 1-2 min each in interpreter mode and tested nothing extra)."""

    TINY = dict(depth=2, hidden_dim=128, num_heads=2, vocab_size=1000)

    def test_gpt2_flash_matches_xla(self):
        from distributed_pytorch_training_tpu.models import get_model

        ids = jnp.asarray(np.random.RandomState(0).randint(0, 1000, (2, 64)))
        m_xla = get_model("gpt2_124m", max_position=64, **self.TINY)
        variables = m_xla.init(jax.random.PRNGKey(0), ids, train=False)
        out_xla = m_xla.apply(variables, ids, train=False)

        m_flash = get_model("gpt2_124m", max_position=64, **self.TINY,
                            attention_fn=make_flash_attention_fn(
                                causal=True, block_q=32, block_k=32))
        out_flash = m_flash.apply(variables, ids, train=False)
        np.testing.assert_allclose(np.asarray(out_xla), np.asarray(out_flash),
                                   rtol=3e-4, atol=3e-4)

    def test_gpt2_flash_with_padding_mask_matches_xla(self):
        """Padded batches keep the flash path end-to-end through the model
        (r3 weak-#3: the fast path used to narrow exactly where real data
        begins). Valid-position logits must match the einsum path."""
        from distributed_pytorch_training_tpu.models import get_model

        rng = np.random.RandomState(1)
        ids = jnp.asarray(rng.randint(0, 1000, (2, 64)))
        am = np.ones((2, 64), np.float32)
        am[:, 48:] = 0.0
        am = jnp.asarray(am)

        m_xla = get_model("gpt2_124m", max_position=64, **self.TINY)
        variables = m_xla.init(jax.random.PRNGKey(0), ids, train=False)
        out_xla = m_xla.apply(variables, ids, attention_mask=am, train=False)

        m_flash = get_model("gpt2_124m", max_position=64, **self.TINY,
                            attention_fn=make_flash_attention_fn(
                                causal=True, block_q=32, block_k=32))
        out_flash = m_flash.apply(variables, ids, attention_mask=am,
                                  train=False)
        valid = np.asarray(am, bool)
        np.testing.assert_allclose(np.asarray(out_xla)[valid],
                                   np.asarray(out_flash)[valid],
                                   rtol=3e-4, atol=3e-4)

    def test_gpt2_ring_path_still_rejects_padding_mask(self):
        from distributed_pytorch_training_tpu.models import get_model

        ids = jnp.zeros((8, 32), jnp.int32)
        m = get_model("gpt2_124m", max_position=32, **self.TINY,
                      attention_fn=make_ring_attention_fn(
                          build_mesh(MeshSpec(data=8)), causal=True))
        variables = m.init(jax.random.PRNGKey(0), ids, train=False)
        with pytest.raises(ValueError, match="mask"):
            m.apply(variables, ids, attention_mask=jnp.ones((8, 32)),
                    train=False)


class TestRingFlashFused:
    """The fused ring+flash path (VERDICT r3 #4): each ring step runs the
    Pallas blockwise kernel (interpreter mode on CPU), partials merge via
    fp32 lse, the backward re-runs the ring with the flash grad kernels.
    Must be numerically interchangeable with the einsum ring."""

    @pytest.fixture(scope="class")
    def seq_mesh(self, devices):
        return build_mesh(MeshSpec(data=2, seq=4), devices=devices)

    @pytest.mark.parametrize("causal", [False, True])
    def test_fused_matches_reference(self, seq_mesh, causal):
        q, k, v = _rand_qkv(b=2, s=128, h=2, d=16)  # S_loc=32
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, seq_mesh, causal=causal, use_pallas=True,
            block_q=32, block_k=32))(q, k, v)
        expect = _ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_fused_gradients_match_reference(self, seq_mesh):
        q, k, v = _rand_qkv(b=2, s=64, h=2, d=8, seed=2)  # S_loc=16

        def loss_fused(q, k, v):
            return (ring_attention(q, k, v, seq_mesh, causal=True,
                                   use_pallas=True, block_q=16,
                                   block_k=16) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q, k, v, True) ** 2).sum()

        g_f = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_f, g_r, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name} (fused ring)")

    def test_fused_path_runs_pallas_kernels(self, seq_mesh):
        """The point of the fusion: the compiled step must contain the
        Pallas kernel, not the einsum formulation (r3 weak-#4: 'flash
        speed and ring scale-out don't compose')."""
        q, k, v = _rand_qkv(b=2, s=128, h=2, d=16)

        def count_pallas(jaxpr):
            n = 0
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    n += 1
                # fun_jaxpr: custom_vjp_call_jaxpr's body param on jax
                # 0.4.x — without it the fused ring's kernels (inside the
                # _ring_flash custom_vjp) are invisible to this census
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    sub = eqn.params.get(key) if eqn.params else None
                    if sub is not None:
                        n += count_pallas(getattr(sub, "jaxpr", sub))
                for key in ("branches",):
                    for s in (eqn.params.get(key) or ()):
                        n += count_pallas(getattr(s, "jaxpr", s))
            return n

        fused = jax.make_jaxpr(lambda q, k, v: ring_attention(
            q, k, v, seq_mesh, causal=True, use_pallas=True,
            block_q=32, block_k=32))(q, k, v)
        einsum = jax.make_jaxpr(lambda q, k, v: ring_attention(
            q, k, v, seq_mesh, causal=True, use_pallas=False))(q, k, v)
        assert count_pallas(fused.jaxpr) > 0
        assert count_pallas(einsum.jaxpr) == 0

    def test_auto_selection_logic(self, seq_mesh):
        """On CPU backends auto must pick the einsum path (pallas would run
        in interpreter mode — pure overhead); the TPU decision is
        flash_supports_length on the SHARD length."""
        from distributed_pytorch_training_tpu.ops.flash_attention import (
            flash_backend_supported,
        )

        assert not flash_backend_supported()  # test backend is CPU
        q, k, v = _rand_qkv(b=2, s=128, h=2, d=16)
        jaxpr = jax.make_jaxpr(lambda q, k, v: ring_attention(
            q, k, v, seq_mesh, causal=True))(q, k, v)  # use_pallas=None
        assert "pallas_call" not in str(jaxpr)


class TestRingAttentionChunked:
    """The q-chunked ring body (bounded per-step score memory) must be a
    pure memory trade: same values, same grads as the straight-through
    block — exercised by forcing q_chunk below the shard length."""

    @pytest.fixture(scope="class")
    def seq_mesh(self, devices):
        return build_mesh(MeshSpec(data=2, seq=4), devices=devices)

    @pytest.mark.parametrize("causal", [False, True])
    def test_chunked_matches_reference(self, seq_mesh, causal):
        q, k, v = _rand_qkv(b=2, s=128, h=2, d=16)  # S_loc=32, chunks of 8
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, seq_mesh, causal=causal, q_chunk=8))(q, k, v)
        expect = _ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    def test_chunked_grads_match(self, seq_mesh):
        q, k, v = _rand_qkv(b=2, s=64, h=2, d=8)  # S_loc=16, chunks of 4

        def loss_chunked(q, k, v):
            return (ring_attention(q, k, v, seq_mesh, causal=True,
                                   q_chunk=4) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref(q, k, v, True) ** 2).sum()

        g_c = jax.jit(jax.grad(loss_chunked, argnums=(0, 1, 2)))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_c, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the tile body: operand dtype, the two bodies, the census, the block rule
# ---------------------------------------------------------------------------

def _flash_module():
    import importlib

    # the module, not the function `ops` re-exports under the same name
    return importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")


# name -> (b, sq, sk, h, d, causal, padded keys)
PARITY_SHAPES = {
    "d64_s1024": (1, 1024, 1024, 2, 64, True, 0),
    "d256_s1024": (1, 1024, 1024, 1, 256, True, 0),
    "sq_ne_sk": (1, 256, 512, 2, 64, True, 0),
    "non_causal": (1, 512, 512, 2, 64, False, 0),
    "kv_valid": (2, 512, 512, 1, 64, True, 200),
}
# ||got - want|| / ||want|| of (out, dq, dk, dv) against the float32 oracle:
# float32 tiles are float32 products, so rounding order only; bf16 tiles keep
# float32 scores, statistics and accumulators and round p, ds (and the
# outputs) to bf16 once, 2^-9 a value
PARITY_TOL = {jnp.float32: 2e-6, jnp.bfloat16: 7.5e-3}   # found 6e-7, 2.5e-3


def _parity_case(shape, dtype, seed=11):
    b, sq, sk, h, d, causal, n_pad = PARITY_SHAPES[shape]
    rng = np.random.RandomState(seed)
    q, g = (jnp.asarray(rng.randn(b, sq, h, d) * 0.5, dtype) for _ in "qg")
    k, v = (jnp.asarray(rng.randn(b, sk, h, d) * 0.5, dtype) for _ in "kv")
    kv_valid = None
    if n_pad:
        valid = np.ones((b, sk), np.float32)
        valid[:, sk - n_pad:] = 0.0        # key 0 stays: no all-masked row
        kv_valid = jnp.asarray(valid)
    return q, k, v, g, causal, kv_valid


def _out_and_grads(attend, q, k, v, g):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + tuple(vjp(g.astype(out.dtype)))


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


class TestFlashTileBody:
    @pytest.mark.parametrize("blocks", ["chosen", "explicit_128"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bf16"])
    @pytest.mark.parametrize("shape", list(PARITY_SHAPES))
    def test_parity_with_the_reference(self, shape, dtype, blocks):
        """Forward and all three gradients against `_reference_attention`
        in float32 on the same (bf16-valued) inputs."""
        fa = _flash_module()
        q, k, v, g, causal, kv_valid = _parity_case(shape, dtype)
        block = None if blocks == "chosen" else 128
        scale = 1.0 / np.sqrt(q.shape[-1])
        got = _out_and_grads(
            lambda q, k, v: flash_attention(q, k, v, causal, None, block,
                                            block, kv_valid), q, k, v, g)
        want = _out_and_grads(
            lambda q, k, v: fa._reference_attention(q, k, v, causal, scale,
                                                    kv_valid),
            *(x.astype(jnp.float32) for x in (q, k, v, g)))
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            assert a.dtype == dtype
            assert _rel(a, b) < PARITY_TOL[dtype], (name, _rel(a, b))
        if kv_valid is not None:         # no gradient leaks into padding
            pad = np.asarray(kv_valid) == 0
            for grad in got[2:]:
                assert np.abs(np.asarray(grad, np.float32)[pad]).max() == 0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bf16"])
    def test_a_tile_under_the_diagonal_is_bitwise_the_same_masked(
            self, monkeypatch, dtype):
        """The unmasked body is the masked body less a select that keeps
        every score: with no tile ever counted as wholly under the diagonal
        every live tile builds the mask, and out, lse, dq, dk, dv are the
        same bits."""
        fa = _flash_module()
        q, k, v, g, causal, _ = _parity_case("sq_ne_sk", dtype)
        static = dict(causal=True, sm_scale=0.125, block_q=64, block_k=64,
                      interpret=True)
        assert fa.tile_census(256, 512, 64, 64, True).full == 6

        def run():       # under the jitted calls: their cache keeps a trace
            out, lse = fa._fwd_call.__wrapped__(q, k, v, None, **static)
            return (out, lse) + fa._bwd_call.__wrapped__(
                q, k, v, out, lse, g, None, **static)

        two_bodies = run()
        monkeypatch.setattr(fa, "_tile_is_full",
                            lambda qb, kb, block_q, block_k: qb < 0)
        assert fa.tile_census(256, 512, 64, 64, True)[:3] == (22, 10, 0)
        for a, b in zip(two_bodies, run()):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    @pytest.mark.parametrize("sq,sk,block_q,block_k,causal", [
        (1024, 1024, 512, 512, True), (8192, 8192, 512, 512, True),
        (1024, 1024, 256, 256, True), (1024, 1024, 256, 512, True),
        (1024, 1024, 512, 128, True), (256, 512, 64, 64, True),
        (512, 256, 128, 64, True), (96, 96, 48, 48, True),
        (100, 100, 100, 100, True), (1024, 2048, 512, 512, False),
    ])
    def test_census_against_a_count_of_the_mask(self, sq, sk, block_q,
                                                block_k, causal):
        fa = _flash_module()
        keep = np.tril(np.ones((sq, sk), bool)) if causal \
            else np.ones((sq, sk), bool)
        tiles = keep.reshape(sq // block_q, block_q, sk // block_k, block_k)
        some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        want = (int((~some).sum()), int((some & ~every).sum()),
                int(every.sum()))
        got = fa.tile_census(sq, sk, block_q, block_k, causal)
        assert tuple(got)[:3] == want

    def test_census_at_the_two_timed_shapes(self):
        """What PERF.md quotes as how often the unmasked body engages."""
        fa = _flash_module()
        assert fa.tile_census(1024, 1024, 512, 512, True)[:3] == (1, 2, 1)
        assert fa.tile_census(8192, 8192, 512, 512, True)[:3] == (120, 16,
                                                                  120)
        # and with the blocks the rule picks there
        q = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16)
        assert fa._blocks(None, None, q, q) == (1024, 1024)
        q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16)
        assert fa._blocks(None, None, q, q) == (1024, 1024)
        one_tile = fa.tile_census(1024, 1024, 1024, 1024, True)
        assert one_tile[:3] == (0, 1, 0)
        # the walked tile's rectangles: 2 blocks of 512 rows forward (3 of
        # 4 quarters), 8 of 128 backward (36 of 64 squares)
        assert one_tile.scores(512) == 3 * 512 * 512
        assert one_tile.scores(128) == 36 * 128 * 128
        assert one_tile.scores(1024) == 1024 * 1024      # taken whole
        long = fa.tile_census(8192, 8192, 1024, 1024, True)
        assert long[:3] == (28, 8, 28)
        assert long.scores(128) == (28 * 64 + 8 * 36) * 128 * 128


class TestFlashLowersForATpu:
    """The lowering a chip would run, Pallas to Mosaic included, from the CPU
    mesh (nothing compiles, nothing runs): the two timed shapes with the
    blocks the shape rule picks, as a one-device program and per shard
    inside `make_flash_attention_fn(mesh=)`'s `shard_map`. A block shape or
    an operation the interpreter accepts and the TPU lowering refuses fails
    here, before chip time."""

    # cell -> (per-chip batch, S, heads, d)
    TIMED = {"gpt2_355m": (8, 1024, 16, 64),
             "qwen3_next_gated_attn": (1, 8192, 16, 256)}

    @pytest.mark.parametrize("program", ["one_device", "shard_map"])
    @pytest.mark.parametrize("cell", list(TIMED))
    def test_forward_and_backward_lower(self, monkeypatch, devices, cell,
                                        program):
        fa = _flash_module()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fa, "_interpret", lambda: False)
        b, s, h, d = self.TIMED[cell]
        mesh = None
        if program == "shard_map":
            mesh = build_mesh(MeshSpec(data=4), devices=devices[:4])
            b *= 4
        attend = make_flash_attention_fn(causal=True, mesh=mesh)
        x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

        def loss(q, k, v):
            return attend(q, k, v, dtype=jnp.bfloat16).astype(
                jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            x, x, x).lower(lowering_platforms=("tpu",)).as_text()
        # a shard_map lowers to a manual computation over the mesh's axes
        assert ("manual" in text) == (mesh is not None)
        assert text.count("tpu_custom_call") >= 3
        for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
            assert kernel in text

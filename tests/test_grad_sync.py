"""Explicit bucketed/compressed gradient synchronization (ISSUE 2:
parallel/grad_sync.py + training/loop.py `_grad_sync_step`).

The contracts pinned here:

(a) **fp32 parity.** The bucketed reducer computes the SAME real-number
    gradient as the implicit XLA path — layout is a performance fact. The
    reassociation order differs (documented in `_grad_sync_step`): the
    implicit path contracts the loss mean over the global batch inside one
    XLA program; the explicit path sums each shard locally and psums across
    shards (and, under accumulation with overlap, sums per-microbatch psums
    instead of psum-ing one sum). So trajectories match at fp-reassociation
    tolerance (the zero1 precedent), NOT bit-for-bit. What IS bit-for-bit:
    bucket BOUNDARIES (per-element reductions are independent of how the
    flat vector is cut — different bucket_cap_mb, identical trajectory) and
    leaf order within the flat vector (jax.tree_util.tree_leaves order,
    fixed).

(b) **Compressed convergence.** bf16 and int8+error-feedback wires are
    perturbations, not parity: the tiny-LM task must still converge, with
    final loss within the stated tolerance of the fp32 run, and the int8
    residual buffers must actually carry feedback (non-zero after a step).

(c) **The HLO census.** The compiled bucketed step carries at most
    ceil(total_grad_bytes / bucket_cap) + 2 gradient-sized collectives, and
    compressed modes put bf16/s8 on the wire (bf16 read from the
    PRE-optimization HLO — the CPU backend's float-normalization pass
    promotes bf16 collectives to f32 in the optimized text; TPU keeps them).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.parallel import (
    MeshSpec, build_mesh, shard_batch,
)
from distributed_pytorch_training_tpu.parallel.collectives import shard_map
from distributed_pytorch_training_tpu.parallel.grad_sync import (
    build_bucket_plan, flatten_tree, padded_bucket_bounds, padded_total_size,
    reduce_flat, unflatten_tree, wire_bytes_per_replica,
)
from distributed_pytorch_training_tpu.training import TrainConfig, Trainer
from distributed_pytorch_training_tpu.training.optim import adamw, sgd
from distributed_pytorch_training_tpu.training.tasks import LanguageModelingTask

SEQ = 16
VOCAB = 64


def _tiny_gpt2():
    return GPT2LMHead(vocab_size=VOCAB, hidden_dim=32, depth=2, num_heads=2,
                      max_position=SEQ)


def _trainer(mesh, opt="sgd", **cfg):
    t = Trainer(LanguageModelingTask(), mesh, TrainConfig(seed=0, **cfg))
    tx = (sgd(0.1, momentum=0.9, weight_decay=5e-4) if opt == "sgd"
          else adamw(1e-2, grad_clip_norm=1.0))
    state = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32), tx,
                         jax.random.PRNGKey(0))
    return t, state


def _batch(mesh, n=16, pad_tail=0):
    rng = np.random.RandomState(0)
    w = np.ones(n, np.float32)
    if pad_tail:
        w[-pad_tail:] = 0.0
    return shard_batch({
        "input_ids": rng.randint(0, VOCAB, (n, SEQ)).astype(np.int32),
        "weight": w,
    }, mesh)


def _run(mesh, steps=4, opt="sgd", pad_tail=0, **cfg):
    """(per-step losses, final state) for one config."""
    t, s = _trainer(mesh, opt=opt, **cfg)
    batch = _batch(mesh, pad_tail=pad_tail)
    key = jax.random.PRNGKey(1)
    losses = []
    for _ in range(steps):
        s, m = t._train_step(s, batch, key)
        losses.append(float(m["loss_sum"]) / max(float(m["weight"]), 1.0))
    return losses, s


def _assert_params_close(a, b, **tol):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)),
            **tol),
        a.params, b.params)


# ---------------------------------------------------------------------------
# Unit: bucket plan + flatten/unflatten
# ---------------------------------------------------------------------------


class TestBucketPlan:
    def test_cap_and_coverage(self):
        tree = {"a": np.zeros((100, 7)), "b": np.zeros(33),
                "c": np.zeros((5, 5, 5))}
        total = 100 * 7 + 33 + 125
        cap_mb = 400 * 4 / (1024 ** 2)  # a 400-fp32-element cap, in MB
        plan = build_bucket_plan(tree, cap_mb)
        assert plan.total_size == total
        assert plan.bounds[0] == 0 and plan.bounds[-1] == total
        assert plan.n_buckets == -(-total // 400)  # the exact ceil bound
        assert all(s <= 400 for s in plan.bucket_sizes())
        assert sum(plan.bucket_sizes()) == total

    def test_no_cap_is_one_bucket(self):
        plan = build_bucket_plan({"a": np.zeros(1000)}, 0.0)
        assert plan.n_buckets == 1
        huge = build_bucket_plan({"a": np.zeros(1000)}, 100.0)
        assert huge.n_buckets == 1

    def test_flatten_unflatten_roundtrip(self):
        rng = np.random.RandomState(0)
        tree = {"w": jnp.asarray(rng.randn(13, 4), jnp.float32),
                "b": jnp.asarray(rng.randn(9), jnp.float32),
                "s": jnp.asarray(rng.randn(2, 3, 2), jnp.float32)}
        flat = flatten_tree(tree)
        assert flat.shape == (13 * 4 + 9 + 12,)
        back = unflatten_tree(flat, tree)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            tree, back)


# ---------------------------------------------------------------------------
# Parity (contract a)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~9 s; bucketing parity stays fast via the adamw leg and bucket-boundaries test
def test_bucketed_fp32_matches_implicit(mesh8):
    l_imp, s_imp = _run(mesh8)
    l_b, s_b = _run(mesh8, bucket_cap_mb=0.05)
    np.testing.assert_allclose(l_imp, l_b, rtol=2e-5)
    _assert_params_close(s_imp, s_b, rtol=1e-4, atol=1e-6)
    assert l_b[-1] < l_b[0]


def test_bucket_boundaries_do_not_change_math(mesh8):
    """Cutting the flat vector differently must be BIT-identical: the
    per-element reductions don't see the boundaries."""
    l_a, s_a = _run(mesh8, steps=3, bucket_cap_mb=0.05)
    l_b, s_b = _run(mesh8, steps=3, bucket_cap_mb=0.004)
    assert l_a == l_b
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y))),
        s_a.params, s_b.params)


def test_bucketed_padded_batch_rows(mesh8):
    """Weight-0 rows (the loader's padded final batch) recombine by weight
    exactly as on the implicit path."""
    l_imp, _ = _run(mesh8, steps=2, pad_tail=4)
    l_b, _ = _run(mesh8, steps=2, pad_tail=4, bucket_cap_mb=0.05)
    np.testing.assert_allclose(l_imp, l_b, rtol=2e-5)


@pytest.mark.slow
def test_grad_accum_overlap_parity(mesh8):
    """grad_accum=2: implicit scan path vs bucketed with in-scan overlap vs
    bucketed post-scan reduction — one trajectory, three schedules."""
    l_imp, s_imp = _run(mesh8, steps=3, grad_accum=2)
    l_ov, s_ov = _run(mesh8, steps=3, grad_accum=2, bucket_cap_mb=0.05)
    l_no, s_no = _run(mesh8, steps=3, grad_accum=2, bucket_cap_mb=0.05,
                      overlap_grad_sync=False)
    np.testing.assert_allclose(l_imp, l_ov, rtol=2e-5)
    np.testing.assert_allclose(l_imp, l_no, rtol=2e-5)
    _assert_params_close(s_imp, s_ov, rtol=1e-4, atol=1e-6)
    _assert_params_close(s_ov, s_no, rtol=1e-4, atol=1e-6)


def test_bucketed_adamw_matches_implicit(mesh8):
    """AdamW (clip active, NO shard_axes — grads arrive globally synced):
    the optimizer chain must see the same gradient as the implicit path."""
    l_imp, s_imp = _run(mesh8, opt="adamw")
    l_b, s_b = _run(mesh8, opt="adamw", bucket_cap_mb=0.05)
    np.testing.assert_allclose(l_imp, l_b, rtol=2e-5)
    # zero-gradient elements amplify reassociation noise through Adam's
    # normalization (the test_zero1 tolerance argument, verbatim)
    _assert_params_close(s_imp, s_b, rtol=2e-2, atol=2e-3)


# ---------------------------------------------------------------------------
# Compressed convergence (contract b)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~7 s convergence smoke; bf16 wire lowering stays gated fast by the gsync_bf16/zero1_bf16 matrix contracts
def test_bf16_wire_converges(mesh8):
    l_fp, _ = _run(mesh8, steps=6)
    l_bf, _ = _run(mesh8, steps=6, bucket_cap_mb=0.05, wire_dtype="bf16")
    assert l_bf[-1] < l_bf[0]
    # bf16 wire rounding perturbs each step by ~2^-8 relative — the
    # trajectory stays within 1% of fp32 on this task
    np.testing.assert_allclose(l_fp, l_bf, rtol=1e-2)


@pytest.mark.slow  # ~10 s convergence smoke; int8 EF exactness stays fast via the multihop 20-step parity + pre-EF resume legs
def test_int8_ef_converges_and_feedback_engages(mesh8):
    l_fp, _ = _run(mesh8, steps=8)
    l_i8, s_i8 = _run(mesh8, steps=8, bucket_cap_mb=0.05, wire_dtype="int8")
    assert l_i8[-1] < l_i8[0]
    # int8 is coarse per step but error feedback telescopes the bias; the
    # loss trajectory tracks fp32 within 2% on this task
    np.testing.assert_allclose(l_fp, l_i8, rtol=2e-2)
    # the residual buffers must be alive (all-zero EF = quantization
    # claimed exact = feedback not wired)
    ef = np.asarray(jax.device_get(s_i8.grad_sync["ef"]))
    assert ef.shape[0] == 8  # one residual row per replica
    assert np.abs(ef).max() > 0.0


@pytest.mark.slow
def test_int8_ef_checkpoint_roundtrip(mesh8, tmp_path):
    """The EF residual IS trajectory state: a resume that zeroes it
    re-introduces the bias error feedback exists to cancel. Orbax must
    round-trip TrainState.grad_sync exactly and the restored run must
    continue the trajectory bit-for-bit."""
    from distributed_pytorch_training_tpu.training.checkpoint import (
        CheckpointManager,
    )

    batch = _batch(mesh8)
    key = jax.random.PRNGKey(1)
    t, state = _trainer(mesh8, bucket_cap_mb=0.05, wire_dtype="int8")
    state, _ = t._train_step(state, batch, key)
    assert np.abs(np.asarray(
        jax.device_get(state.grad_sync["ef"]))).max() > 0.0

    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(1, state, wait=True)
    t2, template = _trainer(mesh8, bucket_cap_mb=0.05, wire_dtype="int8")
    restored, _, _ = ckpt.restore_latest(template)
    ckpt.close()
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(state.grad_sync["ef"])),
        np.asarray(jax.device_get(restored.grad_sync["ef"])))
    s_a, m_a = t._train_step(state, batch, key)
    s_b, m_b = t2._train_step(restored, batch, key)
    np.testing.assert_array_equal(np.asarray(m_a["loss_sum"]),
                                  np.asarray(m_b["loss_sum"]))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        s_a.params, s_b.params)


def test_int8_resume_from_pre_ef_checkpoint(mesh8, tmp_path):
    """Turning --wire-dtype int8 ON over an existing (EF-less) checkpoint
    must resume, not crash: orbax rejects template keys the checkpoint
    lacks, so restore_latest drops the grad_sync entry for legacy
    checkpoints and error feedback restarts from zero residuals."""
    from distributed_pytorch_training_tpu.training.checkpoint import (
        CheckpointManager,
    )

    batch = _batch(mesh8)
    key = jax.random.PRNGKey(1)
    t_fp, s_fp = _trainer(mesh8)  # the legacy run: no EF state
    s_fp, _ = t_fp._train_step(s_fp, batch, key)
    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(1, s_fp, wait=True)

    t_i8, template = _trainer(mesh8, bucket_cap_mb=0.05, wire_dtype="int8")
    restored, _, _ = ckpt.restore_latest(template)
    ckpt.close()
    ef = np.asarray(jax.device_get(restored.grad_sync["ef"]))
    assert np.all(ef == 0.0)  # fresh telescopes
    s2, m = t_i8._train_step(restored, batch, key)
    assert np.isfinite(float(m["loss_sum"]))


def test_int8_requires_init_state_ef_buffers(mesh8):
    """A state built without Trainer.init_state has no EF buffers — the
    step must fail loudly, not silently skip feedback."""
    t, s = _trainer(mesh8, bucket_cap_mb=0.05, wire_dtype="int8")
    s_no_ef = s.replace(grad_sync={})
    with pytest.raises(ValueError, match="error-feedback"):
        t._train_step(s_no_ef, _batch(mesh8), jax.random.PRNGKey(1))


# ---------------------------------------------------------------------------
# Multi-hop int8 wire (ISSUE 4: the DynamiQ n-independent codec)
# ---------------------------------------------------------------------------


def _multihop_reduce_fn(mesh, plan, n=8):
    """jitted (contribs (n, S), ef (n, R)) -> (sums (n, S), new ef): the
    multihop codec run inside a shard_map over the test mesh, one
    contribution row per replica."""
    def body(x, ef):
        out, new_ef = reduce_flat(x.reshape(-1), plan, ("data",), n,
                                  "int8_multihop", ef.reshape(-1))
        return out[None], new_ef[None]

    return jax.jit(shard_map(body, mesh, in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data"))))


class TestMultihopCodec:
    """Unit contracts of `_int8_multihop_sum` via `reduce_flat` on the
    8-device CPU mesh (real collectives, no cluster)."""

    S = 1000  # not divisible by 8 — exercises the padded-to-n layout
    CAP = 400 * 4 / (1024 ** 2)  # 400-element buckets: sizes 400/400/200

    def _plan(self):
        return build_bucket_plan({"a": np.zeros(self.S)}, self.CAP)

    def test_exact_on_grid_values(self, mesh8):
        """Contributions that sit exactly on both hops' quantization grids
        (integer values, every destination chunk's max-abs pinned to 127 so
        the per-chunk scale is exactly 1 and the hop-2 scale exactly n)
        must round-trip bit-exactly with an all-zero residual — any
        deviation is codec math, not quantization."""
        plan = self._plan()
        rng = np.random.RandomState(0)
        row = rng.randint(-127, 128, self.S).astype(np.float32)
        row[::10] = 127.0  # every >=25-element chunk sees max-abs 127
        contribs = np.tile(row, (8, 1))
        ef0 = np.zeros((8, padded_total_size(plan, 8)), np.float32)
        out, ef = _multihop_reduce_fn(mesh8, plan)(contribs, ef0)
        np.testing.assert_array_equal(np.asarray(out)[0], 8.0 * row)
        np.testing.assert_array_equal(np.asarray(ef), 0.0)

    def test_one_shot_error_is_bounded_by_quanta(self, mesh8):
        """|multihop - exact| <= sum of the senders' hop-1 half-quanta plus
        the hop-2 half-quantum — the two-quantization error model PARITY.md
        documents, asserted instead of hand-waved."""
        plan = self._plan()
        rng = np.random.RandomState(1)
        contribs = rng.randn(8, self.S).astype(np.float32)
        exact = contribs.sum(0)
        ef0 = np.zeros((8, padded_total_size(plan, 8)), np.float32)
        out, ef = _multihop_reduce_fn(mesh8, plan)(contribs, ef0)
        out = np.asarray(out)[0]
        bounds = padded_bucket_bounds(plan, 8)
        for k, (a, b) in enumerate(zip(plan.bounds, plan.bounds[1:])):
            chunk = (bounds[k + 1] - bounds[k]) // 8
            seg = slice(a, b)
            # hop-1: each sender's per-destination-chunk scale; hop-2: the
            # owner's partial-sum scale. Conservative per-bucket bound.
            hop1 = 8 * (np.abs(contribs[:, seg]).max() / 127.0) / 2
            hop2 = (np.abs(exact[seg]).max() + hop1) / 127.0 / 2
            err = np.abs(out[seg] - exact[seg]).max()
            assert err <= hop1 + hop2 + 1e-5, (k, err, hop1, hop2, chunk)
        # and the hop-1 residual is alive (error feedback engaged)
        assert np.abs(np.asarray(ef)).max() > 0.0

    def test_hop1_error_feedback_telescopes(self, mesh8):
        """Repeated reduction of the SAME contributions: the hop-1 bias
        telescopes (each step's residual is re-injected), so the cumulative
        MEAN converges well below the one-shot error — what remains is the
        un-fed-back hop-2 noise, bounded by one quantum. A codec that drops
        its residual keeps the full one-shot bias at every horizon and
        fails both assertions."""
        plan = self._plan()
        rng = np.random.RandomState(2)
        contribs = rng.randn(8, self.S).astype(np.float32)
        exact = contribs.sum(0)
        f = _multihop_reduce_fn(mesh8, plan)
        ef = np.zeros((8, padded_total_size(plan, 8)), np.float32)
        out1, _ = f(contribs, np.zeros_like(ef))
        one_shot = np.abs(np.asarray(out1)[0] - exact).max()
        cum = np.zeros(self.S)
        steps = 12
        for _ in range(steps):
            out, ef = f(contribs, ef)
            cum += np.asarray(out)[0]
        mean_err = np.abs(cum / steps - exact).max()
        quantum = 8 * np.abs(contribs).max() / 127.0
        assert mean_err < one_shot / 2, (mean_err, one_shot)
        assert mean_err <= quantum, (mean_err, quantum)


def test_multihop_parity_20_steps(mesh8):
    """ISSUE-4 acceptance: fp32-vs-multihop loss trajectories agree within
    tolerance over >= 20 steps on the CPU mesh (grad-accum OFF; the two
    quantizations are bounded per step and hop-1 telescopes)."""
    l_fp, _ = _run(mesh8, steps=20)
    l_mh, s_mh = _run(mesh8, steps=20, bucket_cap_mb=0.05,
                      wire_dtype="int8_multihop")
    assert l_mh[-1] < l_mh[0]
    np.testing.assert_allclose(l_fp, l_mh, rtol=3e-2)
    # hop-1 residuals: per-replica rows in the padded-to-n layout
    plan = build_bucket_plan(s_mh.params, 0.05)
    ef = np.asarray(jax.device_get(s_mh.grad_sync["ef"]))
    assert ef.shape == (8, padded_total_size(plan, 8))
    assert np.abs(ef).max() > 0.0


@pytest.mark.slow  # ~9 s; the non-accum multihop parity stays fast and the accum interaction is gated by the gsync_int8_mh_accum matrix contract
def test_multihop_parity_20_steps_grad_accum(mesh8):
    """ISSUE-4 acceptance, grad-accum ON: the residual is carried through
    the microbatch scan (each in-scan reduction quantizes and feeds back)
    and the trajectory still tracks fp32. Twice the reductions per step =
    twice the hop-2 perturbations, and by step ~18 this tiny high-LR task
    is chaotic enough that fp32 itself swings ~15% per step — so the
    per-step bound is coarse (no gross divergence) and the time-averaged
    tail, where the noise washes out, carries the tight bound."""
    l_fp, _ = _run(mesh8, steps=20, grad_accum=2)
    l_mh, _ = _run(mesh8, steps=20, grad_accum=2, bucket_cap_mb=0.05,
                   wire_dtype="int8_multihop")
    assert l_mh[-1] < l_mh[0]
    np.testing.assert_allclose(l_fp, l_mh, rtol=1.5e-1)
    np.testing.assert_allclose(np.mean(l_fp[-5:]), np.mean(l_mh[-5:]),
                               rtol=2e-2)


@pytest.mark.slow
def test_multihop_no_overlap_matches_overlap(mesh8):
    """Post-scan reduction vs in-scan overlap: same per-step reductions in
    a different schedule position — trajectories agree at the compressed
    tolerance (EF sees different carried values, so not bit-equal)."""
    l_ov, _ = _run(mesh8, steps=6, grad_accum=2, bucket_cap_mb=0.05,
                   wire_dtype="int8_multihop")
    l_no, _ = _run(mesh8, steps=6, grad_accum=2, bucket_cap_mb=0.05,
                   wire_dtype="int8_multihop", overlap_grad_sync=False)
    assert l_no[-1] < l_no[0]
    np.testing.assert_allclose(l_ov, l_no, rtol=3e-2)


def test_multihop_requires_init_state_ef_buffers(mesh8):
    t, s = _trainer(mesh8, bucket_cap_mb=0.05, wire_dtype="int8_multihop")
    s_no_ef = s.replace(grad_sync={})
    with pytest.raises(ValueError, match="error-feedback"):
        t._train_step(s_no_ef, _batch(mesh8), jax.random.PRNGKey(1))


def test_multihop_rejects_residual_from_other_bucket_plan(mesh8):
    """The multihop residual lives in the padded layout of ITS bucket plan:
    a state restored under a different bucket_cap_mb must be rejected
    loudly (silently slicing the old residual at new offsets would
    re-inject stale error at the wrong elements)."""
    t_big, s_big = _trainer(mesh8, bucket_cap_mb=0.05,
                            wire_dtype="int8_multihop")
    t_small, _ = _trainer(mesh8, bucket_cap_mb=0.004,
                          wire_dtype="int8_multihop")
    with pytest.raises(ValueError, match="different bucket plan"):
        t_small._train_step(s_big, _batch(mesh8), jax.random.PRNGKey(1))


def test_zero1_multihop_parity_20_steps(mesh8):
    """The ROADMAP composition, landed: zero1 + int8_multihop = the s8
    all-to-all scatter (error feedback, as under wire_dtype='int8') PLUS
    the s8 delta-quantized param all-gather. 20-step fp32-parity at
    lr=0.05 — at the default high-LR 0.1 this tiny task goes chaotic by
    step ~17 (the grad-accum multihop test documents the same tail), so
    the parity run uses the saner LR where divergence measures the wire,
    not the Lyapunov exponent."""
    def run(wire):
        t = Trainer(LanguageModelingTask(), mesh8,
                    TrainConfig(seed=0, zero1=True, wire_dtype=wire))
        s = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32),
                         sgd(0.05, momentum=0.9, weight_decay=5e-4),
                         jax.random.PRNGKey(0))
        batch = _batch(mesh8)
        key = jax.random.PRNGKey(1)
        losses = []
        for _ in range(20):
            s, m = t._train_step(s, batch, key)
            losses.append(float(m["loss_sum"])
                          / max(float(m["weight"]), 1.0))
        return losses, s

    l_fp, s_fp = run("fp32")
    l_mh, s_mh = run("int8_multihop")
    assert l_mh[-1] < l_mh[0]
    np.testing.assert_allclose(l_fp, l_mh, rtol=3e-2)
    _assert_params_close(s_fp, s_mh, rtol=5e-2, atol=5e-3)
    # params must stay exactly replicated: every replica dequantized the
    # SAME (codes, scales) onto the same replicated old params
    wte = s_mh.params["wte"]["embedding"]
    assert wte.sharding.is_fully_replicated
    # the scatter half's EF residuals exist and engaged (per-leaf zero1
    # layout: (n, padded) rows)
    ef_leaves = jax.tree_util.tree_leaves(s_mh.grad_sync["ef"])
    assert ef_leaves and all(l.shape[0] == 8 for l in ef_leaves)
    assert max(float(jnp.abs(l).max()) for l in ef_leaves) > 0.0


@pytest.mark.slow  # ~9 s; strictly redundant with the zero1_int8_mh contract in the matrix gate (same census, same rules)
def test_zero1_multihop_census_all_s8_no_checker_relaxation(mesh8):
    """BOTH halves off fp32 in the lowered HLO: the gradient-sized wire is
    s8 all-to-all (scatter) + s8 all-gather (the delta-compressed param
    gather) with NO gradient-sized fp32 collective left — checked with the
    same census the analysis matrix runs (zero1_int8_mh contract), no rule
    relaxed."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census, preopt_hlo_text,
    )

    lowered, _, _ = _lower(mesh8, zero1=True, wire_dtype="int8_multihop")
    census = grad_sync_census(preopt_hlo_text(lowered), min_elements=128)
    assert census["by_op"].get("all-to-all", 0) > 0     # s8 scatter half
    assert census["by_op"].get("all-gather", 0) > 0     # s8 delta gather
    assert census["wire_dtypes"].get("s8", 0) == census["n_collectives"]
    assert "f32" not in census["wire_dtypes"]
    assert "bf16" not in census["wire_dtypes"]


class TestWireBytesAccounting:
    """`wire_bytes_per_replica`: the mode table's byte formulas as code."""

    def _plan(self, total=4096, bucket=1024):
        # bucket sizes divisible by 8 -> zero multihop padding at n<=8,
        # so the n-independence assertion below is exact, not approximate
        return build_bucket_plan({"a": np.zeros(total)},
                                 bucket * 4 / (1024 ** 2))

    def test_multihop_bytes_independent_of_n(self):
        plan = self._plan()
        vals = {n: wire_bytes_per_replica(plan, "int8_multihop", n)
                for n in (2, 4, 8)}
        assert len(set(vals.values())) == 1, vals
        assert vals[2] == 2 * plan.total_size  # ~2 B/element, flat in n

    def test_gather_int8_grows_and_breaks_even_at_9(self):
        plan = self._plan()
        s = plan.total_size
        assert [wire_bytes_per_replica(plan, "int8", n)
                for n in (2, 4, 8)] == [s, 3 * s, 7 * s]
        # the documented break-even: at n=9 the gather form's (n-1)*S
        # equals fp32's 8*S, while multihop still moves 2*S
        assert wire_bytes_per_replica(plan, "int8", 9) == \
            wire_bytes_per_replica(plan, "fp32", 9)
        assert wire_bytes_per_replica(plan, "int8_multihop", 9) < \
            wire_bytes_per_replica(plan, "int8", 9)

    def test_float_wires_and_passthrough(self):
        plan = self._plan()
        assert wire_bytes_per_replica(plan, "fp32", 8) == 8 * plan.total_size
        assert wire_bytes_per_replica(plan, "bf16", 8) == 4 * plan.total_size
        assert wire_bytes_per_replica(plan, "bf16", 1) == 0  # passthrough
        with pytest.raises(ValueError, match="unknown wire dtype"):
            wire_bytes_per_replica(plan, "int4", 8)

    def test_padded_layout_bounds(self):
        plan = build_bucket_plan({"a": np.zeros(1000)},
                                 400 * 4 / (1024 ** 2))  # 400/400/200
        assert padded_bucket_bounds(plan, 8) == (0, 400, 800, 1000)
        assert padded_bucket_bounds(plan, 3) == (0, 402, 804, 1005)
        assert padded_total_size(plan, 3) == 1005


# ---------------------------------------------------------------------------
# HLO census (contract c — the ISSUE 2 acceptance check)
# ---------------------------------------------------------------------------


def _lower(mesh, **cfg):
    t, s = _trainer(mesh, **cfg)
    lowered = t._train_step.lower(s, _batch(mesh), jax.random.PRNGKey(1))
    return lowered, lowered.compile().as_text(), s


@pytest.mark.slow  # ~7 s; strictly redundant with the gsync_fp32 contract in the matrix gate
def test_census_bucket_bound_fp32(mesh8):
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census, verify_grad_sync_collectives,
    )

    cap = 0.02  # ~5.2k fp32 elements per bucket
    lowered, opt_text, state = _lower(mesh8, bucket_cap_mb=cap)
    plan = build_bucket_plan(state.params, cap)
    assert plan.n_buckets > 1  # the bound must actually bind
    verdict = verify_grad_sync_collectives(
        opt_text, total_grad_bytes=plan.total_bytes, bucket_cap_mb=cap,
        wire_dtype="fp32", min_elements=128)
    assert verdict["census"]["n_collectives"] <= plan.n_buckets + 2
    # and the wire is fp32
    assert verdict["wire"].get("f32", 0) > 0
    # the one-per-leaf implicit baseline for comparison (informational:
    # XLA may combine, so only sanity-check it found SOME collectives)
    _, imp_text, _ = _lower(mesh8)
    assert grad_sync_census(imp_text, min_elements=128)["n_collectives"] > 0


def test_census_bf16_on_the_wire(mesh8):
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        preopt_hlo_text, verify_grad_sync_collectives,
    )

    cap = 0.05
    lowered, opt_text, state = _lower(mesh8, bucket_cap_mb=cap,
                                      wire_dtype="bf16")
    plan = build_bucket_plan(state.params, cap)
    verify_grad_sync_collectives(
        opt_text, total_grad_bytes=plan.total_bytes, bucket_cap_mb=cap,
        wire_dtype="bf16", wire_text=preopt_hlo_text(lowered),
        min_elements=128)


def test_census_int8_on_the_wire(mesh8):
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        verify_grad_sync_collectives,
    )

    cap = 0.05
    lowered, opt_text, state = _lower(mesh8, bucket_cap_mb=cap,
                                      wire_dtype="int8")
    plan = build_bucket_plan(state.params, cap)
    # s8 survives even the optimized text (no float-normalization for ints)
    verify_grad_sync_collectives(
        opt_text, total_grad_bytes=plan.total_bytes, bucket_cap_mb=cap,
        wire_dtype="int8", min_elements=128)


@pytest.mark.slow  # ~5 s; strictly redundant with the gsync_int8_mh contract in the matrix gate
def test_census_int8_multihop_two_per_bucket(mesh8):
    """ISSUE-4 acceptance: the compiled multihop step carries exactly
    2 x ceil(bytes/cap) gradient-sized collectives (+slack 2) with the
    two-hop signature (all-to-all + all-gather) and s8 — never f32 — on
    the gradient wire."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census, verify_grad_sync_collectives,
    )

    cap = 0.02
    lowered, opt_text, state = _lower(mesh8, bucket_cap_mb=cap,
                                      wire_dtype="int8_multihop")
    plan = build_bucket_plan(state.params, cap)
    assert plan.n_buckets > 1  # the bound must actually bind
    verdict = verify_grad_sync_collectives(
        opt_text, total_grad_bytes=plan.total_bytes, bucket_cap_mb=cap,
        wire_dtype="int8_multihop", min_elements=128)
    census = verdict["census"]
    assert verdict["bound"] == 2 * plan.n_buckets + 2
    assert census["n_collectives"] == 2 * plan.n_buckets
    # the hop signature: one s8 all-to-all + one s8 all-gather per bucket
    assert census["by_op"].get("all-to-all") == plan.n_buckets
    assert census["by_op"].get("all-gather") == plan.n_buckets
    # s8 survives the optimized text (no float-normalization for ints);
    # no f32 rides any gradient-sized collective
    assert census["wire_dtypes"].get("s8") == census["n_collectives"]
    assert "f32" not in census["wire_dtypes"]


def test_census_rejects_unengaged_bucketing(mesh8):
    """The verifier must FAIL when handed an implicit-path step whose
    collective count exceeds the bucket bound — that is its whole job."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census, verify_grad_sync_collectives,
    )

    _, imp_text, state = _lower(mesh8)
    plan = build_bucket_plan(state.params, 1.0)  # 1 bucket for this model
    n_implicit = grad_sync_census(imp_text, min_elements=128)["n_collectives"]
    if n_implicit <= plan.n_buckets + 2:
        pytest.skip("XLA combined the implicit path below the bound here")
    with pytest.raises(AssertionError, match="bucketing is not engaged"):
        verify_grad_sync_collectives(
            imp_text, total_grad_bytes=plan.total_bytes, bucket_cap_mb=1.0,
            min_elements=128)


# ---------------------------------------------------------------------------
# zero1 composition (the reduce-scatter halves compress)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~10 s; bf16 wire and zero1 are each pinned fast separately (bf16 converges, zero1 multihop parity)
def test_zero1_bf16_wire_matches_zero1_fp32(mesh8):
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census, preopt_hlo_text,
    )

    l_z, s_z = _run(mesh8, zero1=True)
    l_zb, s_zb = _run(mesh8, zero1=True, wire_dtype="bf16")
    assert l_zb[-1] < l_zb[0]
    np.testing.assert_allclose(l_z, l_zb, rtol=1e-2)
    _assert_params_close(s_z, s_zb, rtol=1e-2, atol=1e-3)
    # the reduce-scatter half really runs at bf16 (pre-optimization HLO;
    # CPU promotes in the optimized text)
    lowered, _, _ = _lower(mesh8, zero1=True, wire_dtype="bf16")
    wire = grad_sync_census(preopt_hlo_text(lowered),
                            min_elements=128)["wire_dtypes"]
    assert wire.get("bf16", 0) > 0, wire


@pytest.mark.slow
def test_zero1_int8_wire_trains(mesh8):
    l_zi, s_zi = _run(mesh8, steps=6, zero1=True, wire_dtype="int8")
    assert l_zi[-1] < l_zi[0]
    ef_leaves = jax.tree_util.tree_leaves(s_zi.grad_sync["ef"])
    assert ef_leaves and all(l.shape[0] == 8 for l in ef_leaves)
    assert max(float(jnp.abs(l).max()) for l in ef_leaves) > 0.0


@pytest.mark.slow
def test_zero1_int8_grad_accum_trains(mesh8):
    """EF residuals carried through the microbatch scan (the zero1 accum
    path scatters per microbatch — each scatter quantizes and feeds back)."""
    l, _ = _run(mesh8, steps=4, zero1=True, wire_dtype="int8", grad_accum=2)
    assert l[-1] < l[0]


# ---------------------------------------------------------------------------
# Engagement / rejection
# ---------------------------------------------------------------------------


def test_single_shard_is_passthrough(devices):
    mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
    t = Trainer(LanguageModelingTask(), mesh1,
                TrainConfig(seed=0, bucket_cap_mb=25.0, wire_dtype="bf16"))
    assert not t._grad_sync  # nothing to synchronize on one shard
    s = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32),
                     sgd(0.1), jax.random.PRNGKey(0))
    s, m = t._train_step(s, _batch(mesh1, n=4), jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss_sum"]))


def test_zero1_takes_priority_over_bucketing_conflict(mesh8):
    """zero1 + bucket_cap is a layout contradiction (zero1's per-leaf
    flat shards ARE its optimizer-state format) — loud failure."""
    with pytest.raises(ValueError, match="bucket_cap_mb"):
        Trainer(LanguageModelingTask(), mesh8,
                TrainConfig(zero1=True, bucket_cap_mb=25.0))


def test_rejects_unknown_wire_dtype(mesh8):
    with pytest.raises(ValueError, match="wire_dtype"):
        Trainer(LanguageModelingTask(), mesh8,
                TrainConfig(wire_dtype="fp8"))


def test_rejects_non_dp_meshes(devices):
    mesh = build_mesh(MeshSpec(data=4, model=2), devices=devices)
    with pytest.raises(ValueError, match="grad_sync"):
        Trainer(LanguageModelingTask(), mesh,
                TrainConfig(bucket_cap_mb=25.0))


def test_rejects_sharded_param_rules(devices):
    mesh = build_mesh(MeshSpec(data=2, fsdp=4), devices=devices)
    with pytest.raises(ValueError, match="fsdp"):
        Trainer(LanguageModelingTask(), mesh,
                TrainConfig(bucket_cap_mb=25.0),
                rules=GPT2LMHead.partition_rules())

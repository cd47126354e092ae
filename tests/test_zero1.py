"""ZeRO-1 cross-replica weight-update sharding (training/loop.py `zero1`).

The contract (ISSUE 1 acceptance): on the same data-parallel mesh, the
sharded update must (a) train the SAME trajectory as the replicated
DDP-style update — layout is a performance fact, not a math fact — for both
SGD-momentum and AdamW, including the grad-accum and bf16 variants; (b)
actually replace the gradient all-reduces with reduce-scatter + all-gather
in the compiled HLO (the static census, analysis/hlo_rules.py); and
(c) round-trip its flat-sharded optimizer state through a checkpoint.

Tolerances: SGD parity is tight (the update is elementwise in the gradient,
so reduce-ordering differences stay proportional). AdamW's params get a
looser absolute tolerance: elements whose gradient is ~0 (qkv biases at
init) see Adam's normalization amplify fp reassociation noise into
O(lr * eps-ratio) update differences — inherent to ANY reduce-ordering
change, not a bug; the loss trajectory is the binding contract and stays
tight.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.parallel import (
    MeshSpec, build_mesh, shard_batch,
)
from distributed_pytorch_training_tpu.training import TrainConfig, Trainer
from distributed_pytorch_training_tpu.training.optim import adamw, sgd
from distributed_pytorch_training_tpu.training.tasks import LanguageModelingTask

SEQ = 16
VOCAB = 64
DP_AXES = ("data", "fsdp")


def _tiny_gpt2():
    return GPT2LMHead(vocab_size=VOCAB, hidden_dim=32, depth=2, num_heads=2,
                      max_position=SEQ)


def _make_tx(name, shard_axes=None):
    if name == "sgd":
        # momentum + weight decay: the torch-parity chain (optim.sgd) —
        # fully elementwise, needs no shard awareness
        return sgd(0.1, momentum=0.9, weight_decay=5e-4)
    # clip active (1.0) so the psum'd global-norm path is exercised
    return adamw(1e-2, grad_clip_norm=1.0, shard_axes=shard_axes)


def _trainer(mesh, opt, zero1, grad_accum=1, bf16=False):
    t = Trainer(LanguageModelingTask(
                    compute_dtype=jnp.bfloat16 if bf16 else jnp.float32),
                mesh,
                TrainConfig(seed=0, zero1=zero1, grad_accum=grad_accum,
                            bf16=bf16))
    tx = _make_tx(opt, shard_axes=DP_AXES if zero1 else None)
    state = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32), tx,
                         jax.random.PRNGKey(0))
    return t, state


def _batch(mesh, n=16, pad_tail=0):
    rng = np.random.RandomState(0)
    w = np.ones(n, np.float32)
    if pad_tail:
        w[-pad_tail:] = 0.0  # loader-style padded rows
    return shard_batch({
        "input_ids": rng.randint(0, VOCAB, (n, SEQ)).astype(np.int32),
        "weight": w,
    }, mesh)


def _run_pair(mesh, opt, steps=6, grad_accum=1, bf16=False, pad_tail=0):
    """(replicated, zero1) trajectories: per-step losses + final states."""
    batch = _batch(mesh, pad_tail=pad_tail)
    key = jax.random.PRNGKey(1)
    out = []
    for zero1 in (False, True):
        t, s = _trainer(mesh, opt, zero1, grad_accum=grad_accum, bf16=bf16)
        losses = []
        for _ in range(steps):
            s, m = t._train_step(s, batch, key)
            losses.append(float(m["loss_sum"]) / max(float(m["weight"]), 1.0))
        out.append((losses, s))
    return out


def _assert_params_close(a, b, **tol):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)),
            **tol),
        a.params, b.params)


@pytest.mark.slow  # ~6 s; the adamw leg stays fast and is the stricter parity (two moments + bias correction through the sharded update)
def test_zero1_sgd_momentum_matches_replicated(mesh8):
    (l_rep, s_rep), (l_z1, s_z1) = _run_pair(mesh8, "sgd")
    np.testing.assert_allclose(l_rep, l_z1, rtol=2e-5)
    _assert_params_close(s_rep, s_z1, rtol=1e-4, atol=1e-6)
    assert l_rep[-1] < l_rep[0]


def test_zero1_adamw_matches_replicated(mesh8):
    (l_rep, s_rep), (l_z1, s_z1) = _run_pair(mesh8, "adamw")
    np.testing.assert_allclose(l_rep, l_z1, rtol=2e-5)
    # see module docstring for why AdamW params get an absolute tolerance
    _assert_params_close(s_rep, s_z1, rtol=2e-2, atol=2e-3)
    assert l_rep[-1] < l_rep[0]


def test_zero1_moments_actually_sharded(mesh8):
    """The memory win must be real: every AdamW moment lives as a 1-D
    flat-padded chunk of 1/8 the parameter's padded size per device —
    not a replicated copy with a sharded-looking spec."""
    _, state = _trainer(mesh8, "adamw", zero1=True)
    mu = state.opt_state[1].mu
    n_checked = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(mu):
        param = state.params
        for k in path:
            param = param[k.key]
        padded = param.size + (-param.size % 8)
        assert leaf.ndim == 1 and leaf.shape == (padded,), (path, leaf.shape)
        shard = leaf.addressable_shards[0].data
        assert shard.shape == (padded // 8,), (path, shard.shape)
        n_checked += 1
    assert n_checked >= 10
    # params themselves stay replicated (zero1 shards the UPDATE, not the
    # model — the DDP layout)
    wte = state.params["wte"]["embedding"]
    assert wte.sharding.is_fully_replicated


@pytest.mark.slow
def test_zero1_grad_accum_matches_replicated_grad_accum(mesh8):
    """grad_accum=2 inside the sharded step: the scan carry holds gradient
    SHARDS; the trajectory must still match the replicated accum path."""
    (l_rep, s_rep), (l_z1, s_z1) = _run_pair(mesh8, "sgd", steps=4,
                                             grad_accum=2)
    np.testing.assert_allclose(l_rep, l_z1, rtol=2e-5)
    _assert_params_close(s_rep, s_z1, rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_zero1_bf16_matches_replicated_bf16(mesh8):
    """bf16 compute: forward math is per-sample identical in both layouts
    (params and the gradient sync stay fp32), so parity holds at bf16-noise
    tolerance."""
    (l_rep, s_rep), (l_z1, s_z1) = _run_pair(mesh8, "sgd", steps=4,
                                             bf16=True)
    np.testing.assert_allclose(l_rep, l_z1, rtol=1e-3)
    _assert_params_close(s_rep, s_z1, rtol=1e-3, atol=1e-4)


def test_zero1_padded_batch_rows(mesh8):
    """Weight-0 rows (the loader's padded last batch) must not skew the
    sharded update: shard-local weighted means recombine by weight."""
    (l_rep, _), (l_z1, _) = _run_pair(mesh8, "sgd", steps=3, pad_tail=4)
    np.testing.assert_allclose(l_rep, l_z1, rtol=2e-5)


@pytest.mark.slow  # ~8 s; strictly redundant with the zero1 contract in the matrix gate (same census, same rules)
def test_zero1_hlo_census_reduce_scatter_replaces_all_reduce(mesh8):
    """The acceptance check: the compiled zero1 step carries NO gradient-
    sized all-reduce; reduce-scatter + all-gather appear instead. Scalar
    psums (metrics, clip norm) are allowed — the census floor excludes
    them."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        verify_zero1_collectives, weight_update_census,
    )

    batch = _batch(mesh8)
    key = jax.random.PRNGKey(1)
    texts = {}
    for zero1 in (False, True):
        t, s = _trainer(mesh8, "adamw", zero1)
        texts[zero1] = t._train_step.lower(s, batch, key).compile().as_text()

    # min_elements=128: the per-device HLO shards the 2048-element wte
    # gradient to 256 elements; every remaining zero1 all-reduce is a scalar
    verdict = verify_zero1_collectives(texts[False], texts[True],
                                       min_elements=128)
    assert verdict["replicated"]["all-reduce"] > 0
    assert verdict["zero1"]["all-reduce"] == 0
    assert verdict["zero1"]["reduce-scatter"] > 0
    assert verdict["zero1"]["all-gather"] > 0
    # and the replicated step has no reason to reduce-scatter
    rep = weight_update_census(texts[False], min_elements=128)
    assert rep["reduce-scatter"] == 0


@pytest.mark.slow
def test_zero1_checkpoint_roundtrip(mesh8, tmp_path):
    """Orbax save/restore of the flat-sharded optimizer state: restored
    leaves keep the template's dp sharding and exact values, and the
    restored run continues the trajectory bit-for-bit."""
    from distributed_pytorch_training_tpu.training.checkpoint import (
        CheckpointManager,
    )

    batch = _batch(mesh8)
    key = jax.random.PRNGKey(1)
    t, state = _trainer(mesh8, "adamw", zero1=True)
    state, _ = t._train_step(state, batch, key)

    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(1, state, wait=True)

    t2, template = _trainer(mesh8, "adamw", zero1=True)
    restored, epoch, step_in_epoch = ckpt.restore_latest(template)
    ckpt.close()
    assert epoch == 1 and step_in_epoch == 0
    assert int(restored.step) == 1

    mu = restored.opt_state[1].mu["wte"]["embedding"]
    flat = [a for e in mu.sharding.spec if e is not None
            for a in ((e,) if isinstance(e, str) else e)]
    assert "data" in flat, mu.sharding  # dp sharding survived the roundtrip
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        state.opt_state, restored.opt_state)

    # the restored trajectory continues identically
    s_a, m_a = t._train_step(state, batch, key)
    s_b, m_b = t2._train_step(restored, batch, key)
    np.testing.assert_array_equal(np.asarray(m_a["loss_sum"]),
                                  np.asarray(m_b["loss_sum"]))


def test_zero1_single_shard_is_replicated_passthrough(devices):
    """zero1 on one batch shard = the replicated path (the single-device
    passthrough convention): same compiled step, no collectives."""
    mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
    t, s = _trainer(mesh1, "sgd", zero1=True)
    assert not t._zero1  # identity passthrough engaged
    batch = _batch(mesh1, n=4)
    s, m = t._train_step(s, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss_sum"]))


def test_zero1_single_shard_passthrough_via_harness_adamw(devices):
    """zero1 asked for on one chip (`experiments.scaling zero1` there):
    AdamW's clip must NOT carry shard axes when the Trainer runs the replicated
    fallback — a psum over unbound axis names is a trace-time crash, not a
    passthrough."""
    from distributed_pytorch_training_tpu.experiments.harness import (
        build_trainer, make_synth_batch,
    )

    trainer, state, mesh = build_trainer(
        devices[:1], False, "gpt2_124m", 32,
        lm_overrides=dict(hidden_dim=32, depth=1, num_heads=2),
        zero1=True)
    assert not trainer._zero1
    batch, _ = make_synth_batch(mesh, "gpt2_124m", 2, 32)
    state, m = trainer._train_step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss_sum"]))


def test_zero1_rejects_non_dp_non_model_meshes(devices):
    """SP/PP/EP axes need the replicated update; a zero1 request there must
    fail loudly at construction, not silently mis-shard. (A `model` axis is
    the exception since ISSUE 7: zero1 composes with TP via the per-leaf
    GSPMD update — test_zero1_tp_* below.)"""
    mesh = build_mesh(MeshSpec(data=2, seq=2, model=2), devices=devices)
    with pytest.raises(ValueError, match="zero1"):
        Trainer(LanguageModelingTask(), mesh, TrainConfig(zero1=True))


def test_zero1_tp_gspmd_matches_replicated(devices):
    """zero1 x TP (the ISSUE 7 satellite): on a mesh with a model axis the
    update shards per-leaf via GSPMD flat-padded sharding constraints
    (training/loop.py _zero1_gspmd_apply) instead of the manual shard_map —
    and the trajectory must match the replicated update exactly (same
    gradients, same optimizer math, different layout)."""
    mesh_tp = build_mesh(MeshSpec(data=4, model=2), devices=devices)
    batch = _batch(mesh_tp)
    key = jax.random.PRNGKey(1)
    out = {}
    for zero1 in (False, True):
        t = Trainer(LanguageModelingTask(compute_dtype=jnp.float32),
                    mesh_tp, TrainConfig(seed=0, zero1=zero1),
                    rules=GPT2LMHead.partition_rules())
        assert t._zero1_gspmd == zero1  # per-leaf path, not the manual one
        assert not t._zero1
        # stock clip: the GSPMD update runs on GLOBAL flat arrays
        s = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32),
                         _make_tx("sgd"), jax.random.PRNGKey(0))
        losses = []
        for _ in range(4):
            s, m = t._train_step(s, batch, key)
            losses.append(float(m["loss_sum"]) / float(m["weight"]))
        out[zero1] = (losses, s)
    np.testing.assert_allclose(out[False][0], out[True][0], rtol=2e-5)
    _assert_params_close(out[False][1], out[True][1], rtol=1e-4, atol=1e-6)
    # moments born flat-sharded over the batch axes (1/4 per replica here):
    # every non-scalar optimizer leaf is 1-D flat-padded and NOT replicated
    n_checked = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            out[True][1].opt_state):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.size >= 8:
            assert leaf.ndim == 1, (path, leaf.shape)
            assert not leaf.sharding.is_fully_replicated, path
            n_checked += 1
    assert n_checked >= 10


def test_zero1_tp_rejects_compressed_wire(devices):
    """The GSPMD path's scatter/gather are layout constraints, not
    explicit collectives — the wire codecs cannot wrap them; a compressed
    wire request there must fail loudly with the reason."""
    mesh_tp = build_mesh(MeshSpec(data=4, model=2), devices=devices)
    with pytest.raises(ValueError, match="GSPMD"):
        Trainer(LanguageModelingTask(), mesh_tp,
                TrainConfig(zero1=True, wire_dtype="int8"),
                rules=GPT2LMHead.partition_rules())


def test_zero1_rejects_fsdp_rule_conflict(devices):
    """fsdp-sharded params + zero1 is a layout contradiction (zero1 assumes
    replicated params); the error must name the choice."""
    mesh = build_mesh(MeshSpec(data=2, fsdp=4), devices=devices)
    with pytest.raises(ValueError, match="fsdp"):
        Trainer(LanguageModelingTask(), mesh, TrainConfig(zero1=True),
                rules=GPT2LMHead.partition_rules())


@pytest.mark.slow
def test_zero1_resnet_batchnorm_trains(mesh8):
    """BatchNorm models under zero1: per-shard statistics (torch DDP's
    per-GPU BN semantics) — the loss must still go down and the EMAs move."""
    from distributed_pytorch_training_tpu.data import CIFAR10_MEAN, CIFAR10_STD
    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.training.tasks import (
        ImageClassificationTask,
    )

    t = Trainer(ImageClassificationTask(mean=CIFAR10_MEAN, std=CIFAR10_STD,
                                        augment=False),
                mesh8, TrainConfig(seed=0, zero1=True))
    model = get_model("resnet18", num_classes=10, cifar_stem=True)
    state = t.init_state(model, np.zeros((1, 32, 32, 3), np.float32),
                         sgd(0.05, momentum=0.9, weight_decay=5e-4),
                         jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = shard_batch({
        "image": rng.randint(0, 256, (16, 32, 32, 3)).astype(np.uint8),
        "label": rng.randint(0, 10, 16).astype(np.int32),
        "weight": np.ones(16, np.float32),
    }, mesh8)
    stats0 = jax.device_get(state.batch_stats)
    losses = []
    key = jax.random.PRNGKey(1)
    for _ in range(8):
        state, m = t._train_step(state, batch, key)
        losses.append(float(m["loss_sum"]) / float(m["weight"]))
    assert losses[-1] < losses[0], losses
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(jax.device_get(a))
                                  - np.asarray(b)).max()),
        state.batch_stats, stats0)
    assert max(jax.tree_util.tree_leaves(moved)) > 0.0

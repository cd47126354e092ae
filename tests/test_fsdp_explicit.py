"""Explicit full-parameter FSDP (training/loop.py `fsdp_explicit`).

The contract (ISSUE 7 acceptance): on the same data-parallel mesh the
explicit-FSDP step must (a) train the SAME trajectory as the replicated
DDP-style update at reassociation tolerance in fp32 — 20 steps, grad-accum
on and off — the layout (flat-sharded at rest + just-in-time per-layer
gathers) is a performance fact, not a math fact; (b) really hold params AND
moments flat-sharded 1/N per replica at rest (the memory division the mode
exists for); (c) carry exactly one param all-gather per layer group and one
gradient reduce-scatter per layer group in the compiled HLO, with NO
gradient-sized all-reduce (the per-layer census, floor-aware like the
analysis/ rules); and (d) round-trip flat-sharded params + EF residuals
through the async manifest-verified checkpoint path bit-exactly.

The int8_multihop wire compresses BOTH directions (s8 gradient scatter with
error feedback + s8 param gathers); its contract is bounded drift +
convergence, not fp32 parity (PARITY.md states the error model).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.parallel import (
    MeshSpec, build_mesh, shard_batch,
)
from distributed_pytorch_training_tpu.parallel.grad_sync import (
    build_layer_plan, fsdp_gather_bytes, wire_bytes_for_config,
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
        return sgd(0.1, momentum=0.9, weight_decay=5e-4)
    # clip active so the psum'd global-norm path runs on the shards
    return adamw(1e-2, grad_clip_norm=1.0, shard_axes=shard_axes)


def _trainer(mesh, opt, fsdp, wire="fp32", grad_accum=1):
    t = Trainer(LanguageModelingTask(compute_dtype=jnp.float32), mesh,
                TrainConfig(seed=0, fsdp_explicit=fsdp, wire_dtype=wire,
                            grad_accum=grad_accum))
    # the sharded update (fsdp's, like zero1's) needs the psum-aware clip;
    # the replicated path must NOT carry shard axes (unbound-name trace
    # error on the non-shard_map path)
    tx = _make_tx(opt, shard_axes=DP_AXES if (fsdp and t._fsdp) else None)
    state = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32), tx,
                         jax.random.PRNGKey(0))
    return t, state


def _batch(mesh, n=16):
    rng = np.random.RandomState(0)
    return shard_batch({
        "input_ids": rng.randint(0, VOCAB, (n, SEQ)).astype(np.int32),
        "weight": np.ones(n, np.float32),
    }, mesh)


def _run(mesh, opt, fsdp, steps=20, wire="fp32", grad_accum=1):
    batch = _batch(mesh)
    key = jax.random.PRNGKey(1)
    t, s = _trainer(mesh, opt, fsdp, wire=wire, grad_accum=grad_accum)
    losses = []
    for _ in range(steps):
        s, m = t._train_step(s, batch, key)
        losses.append(float(m["loss_sum"]) / max(float(m["weight"]), 1.0))
    return losses, s, t


def _full_params(t, s):
    """Model-shaped params from either layout."""
    return t._fsdp_unflatten(s.params) if t._fsdp else s.params


def _assert_params_close(ref_params, params, **tol):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)),
            **tol),
        ref_params, params)


# --- fp32 parity vs the replicated path ------------------------------------


@pytest.mark.slow  # ~7 s; the adamw 20-step leg stays fast and is the stricter parity
def test_fsdp_sgd_20step_matches_replicated(mesh8):
    l_rep, s_rep, t_rep = _run(mesh8, "sgd", fsdp=False)
    l_fs, s_fs, t_fs = _run(mesh8, "sgd", fsdp=True)
    np.testing.assert_allclose(l_rep, l_fs, rtol=2e-5)
    _assert_params_close(_full_params(t_rep, s_rep),
                         _full_params(t_fs, s_fs), rtol=1e-4, atol=1e-6)
    assert l_rep[-1] < l_rep[0]


def test_fsdp_adamw_matches_replicated(mesh8):
    """AdamW + active global-norm clip: the psum-aware clip must see the
    same global norm from 1/N shards as the replicated path sees from full
    gradients (test_zero1's tolerance rationale applies verbatim)."""
    l_rep, s_rep, t_rep = _run(mesh8, "adamw", fsdp=False, steps=6)
    l_fs, s_fs, t_fs = _run(mesh8, "adamw", fsdp=True, steps=6)
    np.testing.assert_allclose(l_rep, l_fs, rtol=2e-5)
    _assert_params_close(_full_params(t_rep, s_rep),
                         _full_params(t_fs, s_fs), rtol=2e-2, atol=2e-3)


@pytest.mark.slow  # ~8 s; the adamw non-accum parity stays fast and the accum lowering is gated by the fsdp_accum matrix contract
def test_fsdp_grad_accum_20step_matches_replicated_grad_accum(mesh8):
    """grad_accum=2: the scan carry holds per-leaf gradient SHARDS and
    each microbatch's per-layer scatter runs inside the scan body; the
    trajectory must still match the replicated accum path."""
    l_rep, s_rep, t_rep = _run(mesh8, "sgd", fsdp=False, grad_accum=2)
    l_fs, s_fs, t_fs = _run(mesh8, "sgd", fsdp=True, grad_accum=2)
    np.testing.assert_allclose(l_rep, l_fs, rtol=2e-5)
    _assert_params_close(_full_params(t_rep, s_rep),
                         _full_params(t_fs, s_fs), rtol=1e-4, atol=1e-6)


@pytest.mark.slow  # ~10 s convergence smoke; EF exactness stays fast via the flat-params+EF checkpoint roundtrip and the fsdp_int8_mh matrix contract
def test_fsdp_int8_multihop_converges_with_bounded_drift(mesh8):
    """The fully compressed wire (s8 scatter + EF, s8 param gathers): NOT
    an exactness mode — the contract is convergence and bounded drift from
    the fp32 trajectory (PARITY.md)."""
    l_fp32, _, _ = _run(mesh8, "sgd", fsdp=True, steps=8)
    l_mh, s_mh, t_mh = _run(mesh8, "sgd", fsdp=True, steps=8,
                            wire="int8_multihop")
    assert l_mh[-1] < l_mh[0]
    np.testing.assert_allclose(l_fp32, l_mh, rtol=2e-2)
    # EF residuals exist per layer group and were actually updated
    plan = t_mh._fsdp_plan
    assert set(s_mh.grad_sync["ef"].keys()) == {g.name for g in plan.groups}
    total = sum(float(jnp.abs(r).sum())
                for r in jax.tree_util.tree_leaves(s_mh.grad_sync["ef"]))
    assert total > 0.0  # int8 quantization always drops something


# --- at-rest layout --------------------------------------------------------


def test_fsdp_params_and_moments_flat_sharded_at_rest(mesh8):
    """The memory win must be real: every parameter AND every AdamW moment
    lives as a 1-D flat-padded chunk of 1/8 the padded size per device —
    not a replicated copy with a sharded-looking spec."""
    t, state = _trainer(mesh8, "adamw", fsdp=True)
    template = t._fsdp_template
    n_checked = 0
    for tree in (state.params, state.opt_state[1].mu, state.opt_state[1].nu):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            tmpl = template
            for k in path:
                tmpl = tmpl[k.key]
            size = int(np.prod(tmpl.shape) or 1)
            padded = size + (-size % 8)
            assert leaf.ndim == 1 and leaf.shape == (padded,), (
                path, leaf.shape)
            assert not leaf.sharding.is_fully_replicated, path
            shard = leaf.addressable_shards[0].data
            assert shard.shape == (padded // 8,), (path, shard.shape)
            n_checked += 1
    assert n_checked >= 30


def test_fsdp_eval_step_runs_on_unflattened_params(mesh8):
    """Eval takes the at-rest shards and rebuilds model shapes outside
    shard_map (GSPMD inserts the gathers there)."""
    t, state = _trainer(mesh8, "sgd", fsdp=True)
    m = t._eval_step(state, _batch(mesh8))
    assert np.isfinite(float(m["loss_sum"]))


# --- per-layer collective census -------------------------------------------


def _floor_aware_expected(plan, n, floor, wire):
    """Mirror of the analysis/ fsdp rules' expectation arithmetic."""
    sizes = [n * g.row_size for g in plan.groups]
    gathers = sum(1 for s in sizes if s >= floor)
    if wire in ("int8", "int8_multihop"):
        scatters = gathers  # the s8 all-to-all carries the full group
    else:
        scatters = sum(1 for s in sizes if s // n >= floor)
    return gathers, scatters


@pytest.mark.parametrize("wire", [
    "fp32",
    # ~4 s; strictly redundant with the fsdp_int8_mh contract in the
    # matrix gate — the fp32 arm keeps the census shape pinned fast
    pytest.param("int8_multihop", marks=pytest.mark.slow),
])
def test_fsdp_census_one_gather_and_one_scatter_per_layer_group(mesh8, wire):
    """The acceptance census: gathers == layer groups (above the floor),
    gradients land as per-layer reduce-scatter / s8 all-to-all, and NO
    gradient-sized all-reduce survives."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census,
    )

    floor = 64
    t, s = _trainer(mesh8, "sgd", fsdp=True, wire=wire)
    text = t._train_step.lower(
        s, _batch(mesh8), jax.random.PRNGKey(1)).compile().as_text()
    census = grad_sync_census(text, min_elements=floor)
    by_op = census["by_op"]

    plan = build_layer_plan(
        jax.tree_util.tree_map(lambda x: np.zeros(x.shape), t._fsdp_template),
        8)
    assert len(plan.groups) == 5  # wte, wpe, block0, block1, ln_f
    exp_gathers, exp_scatters = _floor_aware_expected(plan, 8, floor, wire)
    assert exp_gathers >= 4  # the floor must not trivialize the census

    assert by_op.get("all-gather", 0) == exp_gathers, by_op
    scatters = by_op.get("reduce-scatter", 0) + by_op.get("all-to-all", 0)
    assert scatters == exp_scatters, by_op
    assert by_op.get("all-reduce", 0) == 0, by_op


@pytest.mark.slow  # ~7 s; strictly redundant with the full contract-matrix gate in test_analysis_cli
def test_fsdp_analysis_contracts_pass_without_relaxation(mesh8):
    """The fsdp and fsdp_int8_mh contracts evaluate clean on the live
    trainer — per-layer gather bound, scatter signature, and
    no-full-param-residency all from the real LayerPlan budget (fsdp_accum
    rides the full-matrix `check --json` gate in test_analysis_cli, not
    re-lowered here)."""
    from distributed_pytorch_training_tpu.analysis.contracts import (
        get_contract,
    )
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        check_artifacts, evaluate_contract,
    )

    for name in ("fsdp", "fsdp_int8_mh"):
        artifacts = evaluate_contract(get_contract(name), mesh=mesh8)
        assert artifacts.layer_group_padded_sizes  # the budget rode along
        findings = check_artifacts(artifacts)
        assert not findings, (name, [f.message for f in findings])


# --- checkpoint ------------------------------------------------------------


def test_fsdp_checkpoint_roundtrip_flat_params_and_ef(mesh8, tmp_path):
    """Save/restore through the async manifest-verified path: flat-sharded
    params, flat-sharded moments and per-group EF residuals all round-trip
    bit-exactly, keep their dp sharding, and the restored run continues
    the trajectory bitwise."""
    from distributed_pytorch_training_tpu.training.checkpoint import (
        CheckpointManager,
    )

    batch = _batch(mesh8)
    key = jax.random.PRNGKey(1)
    t, state = _trainer(mesh8, "adamw", fsdp=True, wire="int8_multihop")
    state, _ = t._train_step(state, batch, key)

    ckpt = CheckpointManager(str(tmp_path / "ckpt"))  # async default
    ckpt.save(1, state, wait=True)
    assert (tmp_path / "ckpt" / ".manifests").exists()  # verified path

    t2, template = _trainer(mesh8, "adamw", fsdp=True, wire="int8_multihop")
    restored, epoch, step_in_epoch = ckpt.restore_latest(template)
    ckpt.close()
    assert epoch == 1 and step_in_epoch == 0
    assert int(restored.step) == 1

    wte = restored.params["wte"]["embedding"]
    assert wte.ndim == 1 and not wte.sharding.is_fully_replicated
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        (state.params, state.opt_state, state.grad_sync),
        (restored.params, restored.opt_state, restored.grad_sync))

    s_a, m_a = t._train_step(state, batch, key)
    s_b, m_b = t2._train_step(restored, batch, key)
    np.testing.assert_array_equal(np.asarray(m_a["loss_sum"]),
                                  np.asarray(m_b["loss_sum"]))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))),
        s_a.params, s_b.params)


# --- mode composition / guards ---------------------------------------------


def test_fsdp_single_shard_is_replicated_passthrough(devices):
    mesh1 = build_mesh(MeshSpec(data=1), devices=devices[:1])
    t, s = _trainer(mesh1, "sgd", fsdp=True)
    assert not t._fsdp  # identity passthrough engaged
    # passthrough state is the ordinary replicated layout
    assert s.params["wte"]["embedding"].ndim == 2
    s, m = t._train_step(s, _batch(mesh1, n=4), jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss_sum"]))


def test_fsdp_rejects_zero1_and_bucket_cap(mesh8):
    task = LanguageModelingTask(compute_dtype=jnp.float32)
    with pytest.raises(ValueError, match="zero1"):
        Trainer(task, mesh8, TrainConfig(fsdp_explicit=True, zero1=True))
    with pytest.raises(ValueError, match="bucket_cap_mb"):
        Trainer(task, mesh8,
                TrainConfig(fsdp_explicit=True, bucket_cap_mb=25.0))


def test_fsdp_rejects_param_sharding_rules(devices):
    """GSPMD partition rules that shard params over an engaged batch axis
    + fsdp_explicit would silently drop the rules (init_state ignores them
    in fsdp mode) — rejected loudly instead (PARITY.md composition
    matrix). Rules whose batch axes are size-1 on this mesh are fine: they
    shard nothing."""
    mesh_fsdp = build_mesh(MeshSpec(data=2, fsdp=4), devices=devices)
    with pytest.raises(ValueError, match="fsdp_explicit owns"):
        Trainer(LanguageModelingTask(), mesh_fsdp,
                TrainConfig(fsdp_explicit=True),
                rules=GPT2LMHead.partition_rules())
    # pure-DP mesh: the same rules are inert (fsdp axis size 1) — accepted
    mesh_dp = build_mesh(MeshSpec(data=8), devices=devices)
    Trainer(LanguageModelingTask(), mesh_dp,
            TrainConfig(fsdp_explicit=True),
            rules=GPT2LMHead.partition_rules())


# --- wire accounting -------------------------------------------------------


def test_fsdp_gather_bytes_accounting():
    """The `fsdp_gather_bytes` term (ISSUE 7 satellite): exact fp32
    gathers cost ~4 B/element; the s8 multihop gathers ~1 B/element — and
    the per-replica number is independent of the shard count (sizes
    divisible by every tested n, so padding cannot smuggle in a
    dependence)."""
    params = {"a": np.zeros((64, 24), np.float32),
              "b": np.zeros((48,), np.float32)}
    total = 64 * 24 + 48
    for n in (2, 4, 8):
        assert fsdp_gather_bytes(params, "fp32", n) == 4 * total
        assert fsdp_gather_bytes(params, "int8_multihop", n) == total
    assert fsdp_gather_bytes(params, "fp32", 1) == 0  # passthrough
    with pytest.raises(ValueError, match="wire dtype"):
        fsdp_gather_bytes(params, "fp16", 4)


def test_fsdp_wire_bytes_for_config_is_scatter_plus_gather():
    """wire_bytes_for_config under fsdp = scatter bytes at the wire dtype
    plus the gather term — int8_multihop lands at ~2 B/element total, at
    any n (the multihop gradient wire's n-independence argument, now for
    both directions)."""
    params = {"a": np.zeros((64, 24), np.float32),
              "b": np.zeros((48,), np.float32)}
    total = 64 * 24 + 48
    for n in (2, 4, 8):
        assert wire_bytes_for_config(
            params, dict(fsdp_explicit=True), n) == 8 * total
        assert wire_bytes_for_config(
            params, dict(fsdp_explicit=True, wire_dtype="bf16"),
            n) == 6 * total
        assert wire_bytes_for_config(
            params, dict(fsdp_explicit=True, wire_dtype="int8_multihop"),
            n) == 2 * total
    assert wire_bytes_for_config(params, dict(fsdp_explicit=True), 1) == 0

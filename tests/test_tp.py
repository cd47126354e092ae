"""Explicit tensor parallelism x FSDP on the 2-D ("data","model") mesh
(training/loop.py `_fsdp_step` with `_tp_n` > 1; ISSUE 13).

The contract (acceptance): (a) 20-step fp32 parity on the CPU mesh —
data=2,model=2 TP x FSDP matches the 1-D replicated baseline at the
PARITY.md reassociation tolerance, grad-accum on AND off, and
int8_multihop converges with EF present; (b) at-rest census — params AND
both AdamW moments flat-sharded 1/(N*M) for every TP-split leaf (the
model-major layout, parallel/sharding.tp_flat_leaf); (c) HLO census —
exactly the megatron model-axis psum budget (one per residual join
forward + its backward mirror, +2 for the vocab-parallel embedding, +2
for the parallel-vocab CE's batch-shaped stat collectives when they
clear the floor — ISSUE 16), ZERO model-axis gathers (the vocab-scale
logits gather is the regression the parallel-vocab cross-entropy
removed), one DATA-axis gather and one scatter per layer
group over the TP-LOCAL plan, and ZERO gradient-sized all-reduce off the
model axis (floor-aware, per-group); (d) the `fsdp_tp` contracts evaluate
clean in the default `analysis check` gate, and each new rule flags a
synthetic violation (mutation tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.parallel import (
    MeshSpec, build_mesh, shard_batch,
)
from distributed_pytorch_training_tpu.parallel.grad_sync import (
    build_layer_plan, tp_psum_bytes_per_step, wire_bytes_for_config,
)
from distributed_pytorch_training_tpu.parallel.mesh import BATCH_AXES, MODEL
from distributed_pytorch_training_tpu.parallel.sharding import (
    tp_clip_weights, tp_flat_leaf, tp_local_struct, tp_split_dims,
    tp_unflatten_leaf,
)
from distributed_pytorch_training_tpu.training import TrainConfig, Trainer
from distributed_pytorch_training_tpu.training.optim import adamw, sgd
from distributed_pytorch_training_tpu.training.tasks import LanguageModelingTask

SEQ = 16
VOCAB = 64  # divisible by the TP degrees below: the vocab-parallel path engages
HIDDEN, DEPTH, HEADS = 32, 2, 2
TP_AXES = (MODEL,) + BATCH_AXES


def _tiny_gpt2():
    return GPT2LMHead(vocab_size=VOCAB, hidden_dim=HIDDEN, depth=DEPTH,
                      num_heads=HEADS, max_position=SEQ)


@pytest.fixture(scope="module")
def mesh_tp(devices):
    return build_mesh(MeshSpec(data=2, model=2), devices=devices[:4])


@pytest.fixture(scope="module")
def mesh_1d(devices):
    return build_mesh(MeshSpec(data=4), devices=devices[:4])


def _split_plan(model_n=2):
    tmpl = jax.eval_shape(
        lambda: _tiny_gpt2().init(jax.random.PRNGKey(0),
                                  jnp.zeros((2, SEQ), jnp.int32),
                                  train=False))["params"]
    sd = tp_split_dims(tmpl, GPT2LMHead.partition_rules(), model_n)
    return tmpl, sd


def _make_tx(opt, tp):
    if opt == "sgd":
        return sgd(0.1, momentum=0.9, weight_decay=5e-4)
    # active global-norm clip: under TP the norm psums over
    # (model,) + batch axes with model-replicated leaves weighted 1/M
    if not tp:
        return adamw(1e-2, grad_clip_norm=1.0)
    tmpl, sd = _split_plan()
    return adamw(1e-2, grad_clip_norm=1.0, shard_axes=TP_AXES,
                 clip_leaf_weights=tp_clip_weights(tmpl, sd, 2))


def _trainer(mesh, opt, fsdp, wire="fp32", grad_accum=1):
    tp = fsdp and dict(mesh.shape).get(MODEL, 1) > 1
    t = Trainer(LanguageModelingTask(compute_dtype=jnp.float32), mesh,
                TrainConfig(seed=0, fsdp_explicit=fsdp, wire_dtype=wire,
                            grad_accum=grad_accum),
                rules=GPT2LMHead.partition_rules() if fsdp else None)
    s = t.init_state(_tiny_gpt2(), np.zeros((1, SEQ), np.int32),
                     _make_tx(opt, tp), jax.random.PRNGKey(0))
    return t, s


def _batch(mesh, n=16):
    rng = np.random.RandomState(0)
    return shard_batch({
        "input_ids": rng.randint(0, VOCAB, (n, SEQ)).astype(np.int32),
        "weight": np.ones(n, np.float32)}, mesh)


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
    return t._fsdp_unflatten(s.params) if t._fsdp else s.params


def _assert_params_close(ref, got, **tol):
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)),
            **tol),
        ref, got)


# --- fp32 parity vs the 1-D replicated baseline -----------------------------


@pytest.mark.slow  # ~11 s; the adamw+clip 20-step leg stays fast and is the stricter parity
def test_tp_fsdp_sgd_20step_matches_replicated(mesh_1d, mesh_tp):
    """THE acceptance parity: same global batch, same seed — the 2-D
    TP x FSDP trajectory matches the replicated 1-D baseline at
    reassociation tolerance (the megatron split reorders contractions,
    never the math)."""
    l_rep, s_rep, t_rep = _run(mesh_1d, "sgd", fsdp=False)
    l_tp, s_tp, t_tp = _run(mesh_tp, "sgd", fsdp=True)
    np.testing.assert_allclose(l_rep, l_tp, rtol=2e-5)
    # 20 steps of reassociated contractions accumulate ~1e-6-level drift
    # on ~1e-4-magnitude weights — atol sized to that, rtol unchanged
    _assert_params_close(_full_params(t_rep, s_rep),
                         _full_params(t_tp, s_tp), rtol=1e-4, atol=5e-6)
    assert l_rep[-1] < l_rep[0]


@pytest.mark.slow  # ~13 s; the adamw+clip non-accum parity stays fast and the accum lowering is gated by the fsdp_accum matrix contract
def test_tp_fsdp_grad_accum_matches_replicated_grad_accum(mesh_1d, mesh_tp):
    """grad_accum=2: the per-layer scatters run inside the microbatch scan
    with the TP forward; trajectory parity must hold unchanged."""
    l_rep, s_rep, t_rep = _run(mesh_1d, "sgd", fsdp=False, grad_accum=2)
    l_tp, s_tp, t_tp = _run(mesh_tp, "sgd", fsdp=True, grad_accum=2)
    np.testing.assert_allclose(l_rep, l_tp, rtol=2e-5)
    _assert_params_close(_full_params(t_rep, s_rep),
                         _full_params(t_tp, s_tp), rtol=1e-4, atol=5e-6)


def test_tp_fsdp_adamw_clip_matches_replicated(mesh_1d, mesh_tp):
    """AdamW with the global-norm clip ACTIVE: the TP-aware clip psums
    squared norms over (model,) + batch axes with model-replicated leaves
    down-weighted 1/M (tp_clip_weights) — the recovered norm must equal
    the replicated run's exactly (M=2 is a power of two: the 1/M weights
    are exact in fp32)."""
    l_rep, s_rep, t_rep = _run(mesh_1d, "adamw", fsdp=False, steps=6)
    l_tp, s_tp, t_tp = _run(mesh_tp, "adamw", fsdp=True, steps=6)
    np.testing.assert_allclose(l_rep, l_tp, rtol=2e-5)
    _assert_params_close(_full_params(t_rep, s_rep),
                         _full_params(t_tp, s_tp), rtol=2e-2, atol=2e-3)


@pytest.mark.slow  # ~8 s convergence smoke; the fsdp_tp_int8_mh matrix contract + the 1-D fsdp int8 EF legs stay fast
def test_tp_fsdp_int8_multihop_converges_with_ef(mesh_tp):
    """The fully compressed wire under TP: s8 data-axis gradient scatter
    with error feedback per (model shard, data replica) pair + s8 param
    gathers; model-axis psums stay exact fp32. Convergence + EF present,
    not fp32 parity (PARITY.md exactness model)."""
    l_fp32, _, _ = _run(mesh_tp, "sgd", fsdp=True, steps=8)
    l_mh, s_mh, t_mh = _run(mesh_tp, "sgd", fsdp=True, steps=8,
                            wire="int8_multihop")
    assert l_mh[-1] < l_mh[0]
    np.testing.assert_allclose(l_fp32, l_mh, rtol=2e-2)
    plan = t_mh._fsdp_plan
    assert set(s_mh.grad_sync["ef"].keys()) == {g.name for g in plan.groups}
    for name, r in s_mh.grad_sync["ef"].items():
        # model-major rows: one per (model shard, data replica) pair
        assert r.shape == (2 * 2, 2 * dict(
            (g.name, g.row_size) for g in plan.groups)[name]), (name,
                                                                r.shape)
    total = sum(float(jnp.abs(r).sum())
                for r in jax.tree_util.tree_leaves(s_mh.grad_sync["ef"]))
    assert total > 0.0


def test_tp_eval_step_matches_replicated_eval(mesh_1d, mesh_tp):
    """Eval unflattens the model-major at-rest layout outside shard_map
    (split leaves re-concatenate along their split dim) and runs the full
    model — same loss as the replicated eval on the same params."""
    t_rep, s_rep = _trainer(mesh_1d, "sgd", fsdp=False)
    t_tp, s_tp = _trainer(mesh_tp, "sgd", fsdp=True)
    m_rep = t_rep._eval_step(s_rep, _batch(mesh_1d))
    m_tp = t_tp._eval_step(s_tp, _batch(mesh_tp))
    np.testing.assert_allclose(float(m_rep["loss_sum"]),
                               float(m_tp["loss_sum"]), rtol=1e-5)


def test_tp_parallel_ce_matches_gathered_fp32():
    """The parallel-vocab CE pin (ISSUE 16): loss, gradient and the
    correctness mask computed from LOCAL logit columns (2 batch-shaped
    model-axis stats) match the gathered-logits optax form in fp32, and
    both shards return the identical replicated value."""
    import optax

    from distributed_pytorch_training_tpu.parallel.collectives import (
        TpShardedLogits, tp_parallel_cross_entropy,
    )

    rng = np.random.RandomState(0)
    full = (rng.randn(4, 7, VOCAB) * 4.0).astype(np.float32)
    tgt = rng.randint(0, VOCAB, (4, 7)).astype(np.int32)
    half = VOCAB // 2

    def per_shard(local):
        return tp_parallel_cross_entropy(
            TpShardedLogits(local, "m", half, VOCAB), jnp.asarray(tgt))

    locals_ = jnp.stack([full[..., :half], full[..., half:]])
    ce, correct = jax.vmap(per_shard, axis_name="m")(locals_)
    ref = optax.softmax_cross_entropy_with_integer_labels(
        jnp.asarray(full), jnp.asarray(tgt))
    np.testing.assert_array_equal(np.asarray(ce[0]), np.asarray(ce[1]))
    np.testing.assert_allclose(np.asarray(ce[0]), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(correct[0]), np.asarray(jnp.argmax(full, -1) == tgt))

    # gradient parity: d(sum ce)/d(logits) — softmax minus one-hot,
    # each shard holding exactly its own columns of the gathered grad
    g_sharded = jax.vmap(
        lambda l: jax.grad(lambda x: per_shard(x)[0].sum())(l),
        axis_name="m")(locals_)
    g_ref = jax.grad(
        lambda x: optax.softmax_cross_entropy_with_integer_labels(
            x, jnp.asarray(tgt)).sum())(jnp.asarray(full))
    np.testing.assert_allclose(np.asarray(g_sharded[0]),
                               np.asarray(g_ref[..., :half]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_sharded[1]),
                               np.asarray(g_ref[..., half:]),
                               rtol=1e-5, atol=1e-6)


# --- at-rest census ---------------------------------------------------------


def test_tp_at_rest_params_and_moments_1_over_nm(mesh_tp):
    """Params AND both AdamW moments live model-major flat-sharded: every
    TP-split leaf holds exactly local_size/(N) elements per device =
    1/(N*M) of the full tensor (padding aside); model-replicated leaves
    (layernorms, row-parallel biases, wpe) hold 1/N per device — and the
    TP-split leaves carry the BULK of the bytes (the embedding splits)."""
    t, state = _trainer(mesh_tp, "adamw", fsdp=True)
    tmpl, sd = _split_plan()
    split_bytes = repl_bytes = 0
    n_split = 0
    for tree in (state.params, state.opt_state[1].mu, state.opt_state[1].nu):
        for (path, leaf), (_, full), (_, d) in zip(
                jax.tree_util.tree_leaves_with_path(tree),
                jax.tree_util.tree_leaves_with_path(tmpl),
                jax.tree_util.tree_leaves_with_path(
                    sd, is_leaf=lambda x: x is None)):
            full_size = int(np.prod(full.shape) or 1)
            local = full_size // 2 if d is not None else full_size
            padded = local + (-local % 2)
            assert leaf.ndim == 1 and leaf.shape == (2 * padded,), (
                path, leaf.shape)
            assert not leaf.sharding.is_fully_replicated, path
            shard = leaf.addressable_shards[0].data
            # per-DEVICE residency: padded_local / N — 1/(N*M) of the
            # full tensor for split leaves
            assert shard.shape == (padded // 2,), (path, shard.shape)
            if d is not None:
                n_split += 1
                split_bytes += full_size
            else:
                repl_bytes += full_size
    assert n_split >= 3 * 13  # 13 split leaves per tree (incl. wte)
    assert split_bytes > 4 * repl_bytes  # the split leaves are the bulk


def test_tp_flat_leaf_round_trips_and_layout_is_model_major():
    rng = np.random.RandomState(0)
    x = rng.randn(12, 6).astype(np.float32)
    flat = np.asarray(tp_flat_leaf(jnp.asarray(x), 0, 3, 2))
    # model-major: segment s is slice s, flat-padded over N=2
    for s in range(3):
        np.testing.assert_array_equal(
            flat[s * 24:(s + 1) * 24], x[s * 4:(s + 1) * 4].ravel())
    back = np.asarray(tp_unflatten_leaf(jnp.asarray(flat), (12, 6),
                                        np.float32, 0, 3))
    np.testing.assert_array_equal(back, x)


def test_tp_split_dims_follow_rules_and_degrade_on_indivisible():
    tmpl, sd = _split_plan()
    flat = {jax.tree_util.keystr(p): d for p, d in
            jax.tree_util.tree_leaves_with_path(
                sd, is_leaf=lambda x: x is None)}
    assert flat["['wte']['embedding']"] == 0          # vocab-parallel
    assert flat["['wpe']['embedding']"] is None
    assert flat["['block0']['attn']['qkv']['kernel']"] == 2
    assert flat["['block0']['attn']['out']['kernel']"] == 0
    assert flat["['block0']['mlp']['fc1']['kernel']"] == 1
    assert flat["['block0']['mlp']['fc2']['kernel']"] == 0
    assert flat["['block0']['ln1']['scale']"] is None
    # indivisible vocab degrades the embedding (Megatron padding absent)
    model = GPT2LMHead(vocab_size=50257, hidden_dim=32, depth=1,
                       num_heads=2, max_position=SEQ)
    tmpl2 = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, SEQ), jnp.int32),
                           train=False))["params"]
    sd2 = tp_split_dims(tmpl2, GPT2LMHead.partition_rules(), 2)
    assert sd2["wte"]["embedding"] is None
    assert not model.clone(tp_size=2, tp_axis=MODEL).tp_vocab


def test_tp_clip_weights_mark_duplicated_leaves():
    tmpl, sd = _split_plan()
    w = tp_clip_weights(tmpl, sd, 2)
    assert w["wte/embedding"] == 1.0
    assert w["wpe/embedding"] == 0.5
    assert w["block0/mlp/fc2/kernel"] == 1.0
    assert w["block0/mlp/fc2/bias"] == 0.5
    assert w["ln_f/scale"] == 0.5
    # every leaf classified — a missing path would silently mis-weight
    assert len(w) == len(jax.tree_util.tree_leaves(tmpl))


# --- HLO census -------------------------------------------------------------


def _axis_counts(text, floor, n_batch, n_model):
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        grad_sync_census, replica_group_axis,
    )

    out = {}
    for r in grad_sync_census(text, min_elements=floor)["rows"]:
        ax = replica_group_axis(r["replica_groups"], n_batch, n_model)
        key = (r["op"], ax)
        out[key] = out.get(key, 0) + r["count"]
    return out


@pytest.mark.parametrize("wire", [
    "fp32",
    # ~5 s; strictly redundant with the fsdp_tp_int8_mh contract in the
    # matrix gate — the fp32 arm keeps the census shape pinned fast
    pytest.param("int8_multihop", marks=pytest.mark.slow),
])
def test_tp_census_model_psums_and_data_only_wire(mesh_tp, wire):
    """The acceptance census: exactly 4*depth + 2 model-axis psums (one
    per residual join forward + backward mirror, + the vocab-parallel
    embedding pair) + 2 parallel-vocab CE stat collectives (the pmax +
    the stacked sumexp/target psum — batch-shaped (rows, S-1, 2) = 240
    elements here, over the 64 floor), ZERO model-axis gathers (the
    vocab-scale logits gather is gone), one DATA-axis gather and one
    scatter per layer group over the TP-LOCAL plan, and zero
    gradient-sized all-reduce off the model axis — floor-aware,
    per-group."""
    floor = 64
    t, s = _trainer(mesh_tp, "sgd", fsdp=True, wire=wire)
    text = t._train_step.lower(
        s, _batch(mesh_tp), jax.random.PRNGKey(1)).compile().as_text()
    counts = _axis_counts(text, floor, n_batch=2, n_model=2)

    assert counts.get(("all-reduce", "model"), 0) == 4 * DEPTH + 2 + 2
    assert counts.get(("all-gather", "model"), 0) == 0  # no logits gather
    assert counts.get(("all-reduce", "data"), 0) == 0
    assert counts.get(("all-reduce", "all"), 0) == 0

    plan = t._fsdp_plan
    sizes = [2 * g.row_size for g in plan.groups]
    exp_gathers = sum(1 for sz in sizes if sz >= floor)
    assert exp_gathers >= 4  # the floor must not trivialize the census
    assert counts.get(("all-gather", "data"), 0) == exp_gathers
    if wire == "int8_multihop":
        exp_scatter = sum(1 for sz in sizes if sz >= floor)
        got = counts.get(("all-to-all", "data"), 0)
    else:
        exp_scatter = sum(1 for sz in sizes if sz // 2 >= floor)
        got = counts.get(("reduce-scatter", "data"), 0)
    assert got == exp_scatter, counts
    # nothing rides groups spanning the whole mesh
    assert not any(ax in ("all", "other", "unknown")
                   for (_op, ax) in counts), counts


def test_tp_layer_plan_is_local(mesh_tp):
    """The layer plan cuts the TP-LOCAL template: per-group row sizes are
    1/M of the 1-D plan's for fully-split groups (the 1/M gather/scatter
    wire reduction, as layout arithmetic)."""
    t, _ = _trainer(mesh_tp, "sgd", fsdp=True)
    tmpl, sd = _split_plan()
    local = tp_local_struct(tmpl, sd, 2)
    expect = build_layer_plan(local, 2)
    assert [g.name for g in t._fsdp_plan.groups] == \
        [g.name for g in expect.groups]
    assert [g.row_size for g in t._fsdp_plan.groups] == \
        [g.row_size for g in expect.groups]
    full_plan = build_layer_plan(tmpl, 2)
    by_name = {g.name: g.row_size for g in full_plan.groups}
    wte_local = {g.name: g.row_size for g in expect.groups}["wte"]
    assert wte_local == by_name["wte"] // 2  # the embedding really halves


# --- analysis contracts + mutation tests ------------------------------------


@pytest.mark.slow  # ~8 s; strictly redundant with the full contract-matrix gate in test_analysis_cli
def test_fsdp_tp_contracts_pass_without_relaxation():
    """The fsdp_tp contracts evaluate clean on their OWN 2-D mesh
    (Contract.mesh_spec) with the trainer-derived psum budget — and the
    artifacts really carry it (a zero budget would vacuously pass the new
    rules)."""
    from distributed_pytorch_training_tpu.analysis.contracts import (
        get_contract,
    )
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        check_artifacts, evaluate_contract,
    )

    for name in ("fsdp_tp", "fsdp_tp_int8_mh"):
        a = evaluate_contract(get_contract(name))
        assert a.model_shards == 2
        assert a.tp_expected_psums == 4 * DEPTH + 2
        assert a.tp_expected_model_gathers == 0  # the gather-regression pin
        # the CE stats really carry a nonzero floor-aware budget: 4 rows
        # per data shard (2/device x 8 devices / 4 shards) x 15 positions
        # x width 2 — over the contract's 64 floor, so the rule binds at
        # +2 (not vacuously at +0)
        assert a.tp_ce_stat_elements == 2 * 4 * (16 - 1)
        assert a.tp_ce_stat_elements >= a.min_elements
        findings = check_artifacts(a)
        assert not findings, (name, [f.message for f in findings])


def _synthetic_tp_text(model_ars=10, model_gathers=0, data_gathers=5,
                       data_scatters=5, extra=""):
    """Synthetic optimized-HLO text for the mutation tests: 4 batch shards
    x 2 model shards (8 devices, model minor)."""
    model_g = "{{0,1},{2,3},{4,5},{6,7}}"
    data_g = "{{0,2,4,6},{1,3,5,7}}"
    lines = ["HloModule synthetic", "ENTRY main {"]
    for i in range(model_ars):
        lines.append(f"  %ar{i} = f32[4,16,32]{{2,1,0}} all-reduce(%x), "
                     f"replica_groups={model_g}, to_apply=%sum")
    for i in range(model_gathers):
        lines.append(f"  %mg{i} = f32[4,16,64]{{2,1,0}} all-gather(%x), "
                     f"replica_groups={model_g}, dimensions={{2}}")
    for i in range(data_gathers):
        lines.append(f"  %dg{i} = f32[4096]{{0}} all-gather(%x), "
                     f"replica_groups={data_g}, dimensions={{0}}")
    for i in range(data_scatters):
        lines.append(f"  %ds{i} = f32[1024]{{0}} reduce-scatter(%x), "
                     f"replica_groups={data_g}, to_apply=%sum")
    if extra:
        lines.append(extra)
    lines.append("  input_output_alias={ {0}: (0, {}, may-alias) }")
    lines.append("}")
    return "\n".join(lines)


def _tp_artifacts(text, **overrides):
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        StepArtifacts,
    )

    kw = dict(name="synthetic", optimized_text=text,
              config={"fsdp_explicit": True}, n_shards=4, model_shards=2,
              tp_expected_psums=10, tp_expected_model_gathers=0,
              min_elements=128,
              layer_group_padded_sizes=(4096, 4096, 4096, 4096, 4096))
    kw.update(overrides)
    return StepArtifacts(**kw)


class TestTpRuleMutations:
    """Each new rule must flag a synthetic violation (the ISSUE-3 mutation
    discipline) — and pass the clean text."""

    def _check(self, text, rule, **overrides):
        from distributed_pytorch_training_tpu.analysis.hlo_rules import (
            check_artifacts,
        )

        return check_artifacts(_tp_artifacts(text, **overrides),
                               rules=[rule])

    def test_clean_text_passes_both_rules(self):
        text = _synthetic_tp_text()
        assert not self._check(text, "tp-psum-signature")
        assert not self._check(text, "fsdp-gather-rides-data-only")

    def test_missing_model_psum_flagged(self):
        f = self._check(_synthetic_tp_text(model_ars=9),
                        "tp-psum-signature")
        assert f and "expected exactly 10" in f[0].message

    def test_extra_model_psum_flagged(self):
        assert self._check(_synthetic_tp_text(model_ars=11),
                           "tp-psum-signature")

    def test_model_gather_regression_flagged(self):
        # the vocab-scale logits gather the parallel-vocab CE removed:
        # its reappearance is the regression the rule pins at zero
        f = self._check(_synthetic_tp_text(model_gathers=1),
                        "tp-psum-signature")
        assert f and "regression it replaced" in f[0].message

    def test_ce_stats_raise_the_psum_budget_when_over_floor(self):
        # with batch-shaped CE stats over the floor the budget is 10+2:
        # 12 psums pass, the bare structural 10 now FAILS (a dropped CE
        # stat collective is a lost loss reduction, not noise)
        assert not self._check(_synthetic_tp_text(model_ars=12),
                               "tp-psum-signature",
                               tp_ce_stat_elements=2048)
        f = self._check(_synthetic_tp_text(model_ars=10),
                        "tp-psum-signature", tp_ce_stat_elements=2048)
        assert f and "expected exactly 12" in f[0].message
        # under the floor the stats are census-invisible: budget stays 10
        assert not self._check(_synthetic_tp_text(model_ars=10),
                               "tp-psum-signature", tp_ce_stat_elements=64)

    def test_missing_budget_is_itself_a_finding(self):
        f = self._check(_synthetic_tp_text(), "tp-psum-signature",
                        tp_expected_psums=0)
        assert f and "without a model-axis collective budget" \
            in f[0].message

    def test_mesh_spanning_gather_flagged(self):
        all_g = "{{0,1,2,3,4,5,6,7}}"
        extra = (f"  %bad = f32[4096]{{0}} all-gather(%x), "
                 f"replica_groups={all_g}, dimensions={{0}}")
        f = self._check(_synthetic_tp_text(extra=extra),
                        "fsdp-gather-rides-data-only")
        assert f and "spanning" in f[0].message

    def test_model_axis_scatter_flagged(self):
        model_g = "{{0,1},{2,3},{4,5},{6,7}}"
        extra = (f"  %bad = f32[1024]{{0}} reduce-scatter(%x), "
                 f"replica_groups={model_g}, to_apply=%sum")
        f = self._check(_synthetic_tp_text(extra=extra),
                        "fsdp-gather-rides-data-only")
        assert f and "MODEL axis" in f[0].message

    def test_rules_abstain_without_model_axis(self):
        # 1-D artifacts never consult the classifier — no relaxation of
        # existing contracts, no accidental binding
        text = _synthetic_tp_text()
        assert not self._check(text, "tp-psum-signature", model_shards=1)
        assert not self._check(text, "fsdp-gather-rides-data-only",
                               model_shards=1)


def test_replica_group_axis_classifier():
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        parse_replica_groups, replica_group_axis,
    )

    assert replica_group_axis("{{0,1},{2,3}}", 2, 2) == "model"
    assert replica_group_axis("{{0,2},{1,3}}", 2, 2) == "data"
    assert replica_group_axis("{{0,1,2,3}}", 2, 2) == "all"
    assert replica_group_axis("{{0,3},{1,2}}", 2, 2) == "other"
    assert replica_group_axis("", 2, 2) == "unknown"
    # iota form: [n_groups, size]<=[total] in iota order == consecutive
    assert parse_replica_groups("[2,2]<=[4]") == ((0, 1), (2, 3))
    assert replica_group_axis("[2,2]<=[4]", 2, 2) == "model"
    # transposed iota — XLA's strided-group print form: iota over the
    # reshape dims, transposed, flattened, then chunked
    assert parse_replica_groups("[2,2]<=[2,2]T(1,0)") == ((0, 2), (1, 3))
    assert replica_group_axis("[2,2]<=[2,2]T(1,0)", 2, 2) == "data"
    # malformed perm / mismatched sizes are refused, not guessed
    assert parse_replica_groups("[2,2]<=[2,2]T(0,0)") is None
    assert parse_replica_groups("[2,3]<=[4]") is None


def test_census_extracts_iota_replica_groups_from_hlo_lines():
    """The line regex must capture every groups shape the parser decodes —
    incl. multi-dim iota with a transpose suffix (XLA's strided-group
    print form); a capture miss would classify real data-axis collectives
    as 'unknown' and misfire the TP rules on backends that print it."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        collective_census, replica_group_axis,
    )

    text = "\n".join([
        "HloModule m",
        "ENTRY main {",
        "  %a = f32[4096]{0} all-gather(%x), "
        "replica_groups=[2,4]<=[4,2]T(1,0), dimensions={0}",
        "  %b = f32[4096]{0} all-reduce(%y), "
        "replica_groups=[4,2]<=[8], to_apply=%sum",
        "}",
    ])
    rows = {r["op"]: r for r in collective_census(text)}
    # [2,4]<=[4,2]T(1,0): iota(8).reshape(4,2).T -> groups {0,2,4,6},{1,3,5,7}
    assert replica_group_axis(rows["all-gather"]["replica_groups"],
                              4, 2) == "data"
    # plain iota [4,2]<=[8]: consecutive pairs == the model groups
    assert replica_group_axis(rows["all-reduce"]["replica_groups"],
                              4, 2) == "model"


# --- wire accounting --------------------------------------------------------


def test_tp_data_axis_bytes_drop_by_1_over_m():
    """The 1/M gather/scatter reduction as accounting: the data-axis
    bytes computed over the TP-LOCAL template are exactly the 1-D
    number / M for every model degree (sizes divisible by every tested
    M*N, so padding cannot smuggle in a dependence) — equivalently, the
    per-element data-axis accounting is model-axis-count independent."""
    tmpl = {"k": jax.ShapeDtypeStruct((64, 24), jnp.float32),
            "b": jax.ShapeDtypeStruct((48,), jnp.float32)}
    sd = {"k": 0, "b": 0}
    base = wire_bytes_for_config(tmpl, dict(fsdp_explicit=True), 2)
    for m in (1, 2, 4):
        local = tp_local_struct(tmpl, sd, m)
        got = wire_bytes_for_config(local, dict(fsdp_explicit=True), 2)
        assert got == base // m, (m, got, base)
    # the TP term adds on top, via the cfg key
    with_tp = wire_bytes_for_config(
        tp_local_struct(tmpl, sd, 2),
        dict(fsdp_explicit=True, tp_psum_bytes=1000), 2)
    assert with_tp == base // 2 + 1000


def test_tp_psum_bytes_per_step_formula():
    b = tp_psum_bytes_per_step(32, 2, 4, 16, 2, tp_vocab=True,
                               padded_vocab=64)
    # the vocab head's wire is the two (B, S, 2) CE stat all-reduces
    # (32 bytes x B x S) — NOT the 4 x B x S x padded_vocab logits
    # gather the parallel-vocab CE replaced
    assert b == 8 * (4 * 16 * 32) * 10 + 32 * 4 * 16
    assert b < 8 * (4 * 16 * 32) * 10 + 4 * 4 * 16 * 64  # strictly shrank
    assert tp_psum_bytes_per_step(32, 2, 4, 16, 1) == 0
    no_vocab = tp_psum_bytes_per_step(32, 2, 4, 16, 2)
    assert no_vocab == 8 * (4 * 16 * 32) * 8


def test_emit_wire_accounting_splits_tp_tier(tmp_path):
    """The telemetry satellite: model-axis psum bytes land in their OWN
    counter row (axis="model") and `telemetry summary` reports them next
    to the data-axis number."""
    import json

    from distributed_pytorch_training_tpu import telemetry
    from distributed_pytorch_training_tpu.parallel.grad_sync import (
        emit_wire_accounting,
    )
    from distributed_pytorch_training_tpu.telemetry.__main__ import (
        main as telemetry_main,
    )

    stream = tmp_path / "t.jsonl"
    telemetry.configure(str(stream), meta={"entry": "test"})
    try:
        params = {"k": np.zeros((64, 24), np.float32)}
        out = emit_wire_accounting(
            params, dict(fsdp_explicit=True, model_shards=2,
                         tp_psum_bytes=4096), 2)
        assert out["tp_psum_bytes_per_replica"] == 4096
        assert out["wire_bytes_per_replica"] == 8 * 64 * 24
    finally:
        telemetry.reset()
    events = [json.loads(ln) for ln in stream.read_text().splitlines()]
    tp_rows = [e for e in events
               if e.get("name") == "tp_psum_bytes_per_replica"]
    assert tp_rows and tp_rows[0]["axis"] == "model"
    data_rows = [e for e in events
                 if e.get("name") == "wire_bytes_per_replica"]
    assert data_rows and data_rows[0]["axis"] == "data"
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert telemetry_main(["summary", str(stream), "--json"]) == 0
    summary = json.loads(buf.getvalue())
    assert summary["wire"]["tp_psum_bytes_per_replica"] == 4096
    assert summary["wire"]["wire_bytes_per_replica"] == 8 * 64 * 24


# --- guards / composition ---------------------------------------------------


def test_tp_requires_a_tp_capable_model(devices):
    from distributed_pytorch_training_tpu.models.resnet import resnet18

    mesh = build_mesh(MeshSpec(data=2, model=2), devices=devices[:4])
    t = Trainer(LanguageModelingTask(), mesh,
                TrainConfig(seed=0, fsdp_explicit=True))
    with pytest.raises(ValueError, match="no explicit-TP form"):
        t.init_state(resnet18(num_classes=10),
                     np.zeros((1, 32, 32, 3), np.float32), sgd(0.1),
                     jax.random.PRNGKey(0))


def test_tp_rejects_indivisible_heads(devices):
    mesh = build_mesh(MeshSpec(data=1, model=4), devices=devices[:4])
    t = Trainer(LanguageModelingTask(), mesh,
                TrainConfig(seed=0, fsdp_explicit=True))
    with pytest.raises(ValueError, match="not divisible"):
        t.init_state(GPT2LMHead(vocab_size=VOCAB, hidden_dim=32, depth=1,
                                num_heads=2, max_position=SEQ),
                     np.zeros((1, SEQ), np.int32), sgd(0.1),
                     jax.random.PRNGKey(0))


def test_tp_rejects_dropout():
    # indivisible vocab keeps the embedding off the vocab-parallel path
    # (no axis_index before the blocks), so the dropout guard inside the
    # first block is what fires — even outside a shard_map
    model = GPT2LMHead(vocab_size=50257, hidden_dim=32, depth=1,
                       num_heads=2, max_position=SEQ, dropout_rate=0.1,
                       tp_size=2, tp_axis=MODEL)
    with pytest.raises(ValueError, match="dropout"):
        jax.eval_shape(
            lambda: model.init(
                {"params": jax.random.PRNGKey(0),
                 "dropout": jax.random.PRNGKey(1)},
                jnp.zeros((2, SEQ), jnp.int32), train=True))


def test_build_lm_trainer_zero1_model_axis_keeps_stock_clip(devices):
    """zero1 on a model-axis mesh (newly reachable through the harness's
    mesh_spec) runs the per-leaf GSPMD update OUTSIDE shard_map — the
    clip must stay stock (shard_axes=None), or its batch-axes psum hits
    unbound axis names at trace (the train.py exclusion, mirrored)."""
    from distributed_pytorch_training_tpu.experiments.harness import (
        build_lm_trainer, synth_token_batch,
    )

    trainer, state, mesh = build_lm_trainer(
        devices[:4], False, "gpt2_124m", SEQ,
        model_kwargs=dict(hidden_dim=32, depth=1, num_heads=2),
        zero1=True, mesh_spec="data=2,model=2")
    assert trainer._zero1_gspmd
    batch, _gb = synth_token_batch(mesh, 2, SEQ)
    _s, m = trainer._train_step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss_sum"]))


def test_zero1_tp_wire_rejection_points_at_fsdp_explicit(devices):
    """The carried ROADMAP item, closed: the per-leaf GSPMD zero1 path
    rejects wire compression WITH a pointer to --fsdp-explicit + TP
    (PARITY.md records the path as subsumed)."""
    mesh = build_mesh(MeshSpec(data=4, model=2), devices=devices)
    with pytest.raises(ValueError, match="fsdp-explicit"):
        Trainer(LanguageModelingTask(), mesh,
                TrainConfig(zero1=True, wire_dtype="int8_multihop"),
                rules=GPT2LMHead.partition_rules())


def test_validate_mesh_rejects_model_axis_for_ruleless_models(devices):
    from distributed_pytorch_training_tpu.parallel import validate_mesh

    mesh = build_mesh(MeshSpec(data=2, model=2), devices=devices[:4])
    with pytest.raises(ValueError, match="model"):
        validate_mesh(mesh, rules=None)
    validate_mesh(mesh, rules=GPT2LMHead.partition_rules())  # usable: ok


# --- checkpoint -------------------------------------------------------------


@pytest.mark.slow  # ~12 s; sharded-layout checkpoint roundtrip stays fast via the richer fsdp flat-params+EF leg
def test_tp_checkpoint_roundtrip_bitwise(mesh_tp, tmp_path):
    """The model-major at-rest layout round-trips through the async
    manifest-verified checkpoint path bit-exactly, and the restored run
    continues the trajectory bitwise."""
    from distributed_pytorch_training_tpu.training.checkpoint import (
        CheckpointManager,
    )

    batch = _batch(mesh_tp)
    key = jax.random.PRNGKey(1)
    t, state = _trainer(mesh_tp, "adamw", fsdp=True, wire="int8_multihop")
    state, _ = t._train_step(state, batch, key)

    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(1, state, wait=True)

    t2, template = _trainer(mesh_tp, "adamw", fsdp=True,
                            wire="int8_multihop")
    restored, epoch, _sie = ckpt.restore_latest(template)
    ckpt.close()
    assert epoch == 1
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


# --- serving on the 2-D mesh ------------------------------------------------


def test_serving_engine_tp_mesh_matches_1d(devices):
    """`--mesh data=2,model=2` serving: the served weights shard over the
    model axis via the GSPMD rules and the token server's greedy tokens
    match the 1-D engine's (multi-chip serving of big models — the ISSUE-13
    motivation's serving half)."""
    from distributed_pytorch_training_tpu.serving import (
        ContinuousScheduler, RequestQueue,
    )
    from distributed_pytorch_training_tpu.serving.build import (
        build_slot_engine,
    )

    overrides = dict(vocab_size=VOCAB, hidden_dim=32, depth=2, num_heads=2)
    prompts = [np.arange(5, dtype=np.int32),
               np.arange(9, dtype=np.int32) % VOCAB]

    def tokens(mesh_spec):
        engine, mesh = build_slot_engine(
            devices[:4], "gpt2_124m", buckets=(16,), rows=4,
            max_new_tokens=4, page_size=4, model_overrides=overrides,
            mesh_spec=mesh_spec)
        if mesh_spec:
            wte = engine._served["wte"]["embedding"]
            assert not wte.sharding.is_fully_replicated
        queue = RequestQueue(engine.config.buckets)
        reqs = [queue.submit(p) for p in prompts]
        ContinuousScheduler(engine, queue).drain()
        return [r.result(timeout=300.0).tokens.tolist() for r in reqs]

    assert tokens("data=2,model=2") == tokens(None)


def test_serving_engine_rejects_model_axis_without_rules(devices):
    from distributed_pytorch_training_tpu.serving import (
        PagedServeConfig, SlotEngine,
    )

    mesh = build_mesh(MeshSpec(data=2, model=2), devices=devices[:4])
    model = _tiny_gpt2()
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, SEQ), np.int32), train=False)["params"]
    with pytest.raises(ValueError, match="partition rules"):
        SlotEngine(model, mesh,
                   PagedServeConfig(buckets=(8,), rows=4, max_new_tokens=2,
                                    page_size=2), params)


# --- ring attention on the TP mesh ------------------------------------------


def test_ring_attention_sharded_inside_tp_mesh_shard_map(devices):
    """`ring_attention_sharded` (the in-shard_map form): called with the
    bound `seq` axis inside a shard_map over a (data, seq, model) mesh —
    the nested-shard_map-free entry the explicit TP step can compose with
    — matches full attention."""
    from jax.sharding import PartitionSpec as P

    from distributed_pytorch_training_tpu.models.layers import (
        dot_product_attention,
    )
    from distributed_pytorch_training_tpu.ops.ring_attention import (
        ring_attention_sharded,
    )
    from distributed_pytorch_training_tpu.parallel.collectives import (
        shard_map,
    )
    from distributed_pytorch_training_tpu.parallel.mesh import SEQ as SEQ_AX

    mesh = build_mesh(MeshSpec(data=2, seq=2, model=2), devices=devices)
    rng = np.random.RandomState(0)
    q = rng.randn(2, 8, 2, 4).astype(np.float32)
    k = rng.randn(2, 8, 2, 4).astype(np.float32)
    v = rng.randn(2, 8, 2, 4).astype(np.float32)
    ref = np.asarray(dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                           jnp.asarray(v)))

    spec = P(BATCH_AXES, SEQ_AX, MODEL, None)
    f = shard_map(
        lambda a, b, c: ring_attention_sharded(a, b, c, axis_name=SEQ_AX,
                                               causal=False,
                                               use_pallas=False),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = np.asarray(jax.jit(f)(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

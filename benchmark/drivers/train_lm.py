"""The training job of ``drivers/train.py`` (same loop, window, warm-up,
trace, facts and notes) for any language-model family: the plain reference
and the FLOP count are found by the configuration's ``family``, as
``benchmark.reference.<family>`` and ``benchmark.flops.<family>``, so the
next family is files alone.

What a family's modules give this driver:

* ``reference.for_config(config) -> logits_fn(program params, ids)``: the
  plain reference's float32 logits over the share of the model that the
  configuration runs (its next-token loss is taken from them here);
* ``flops.train_flops_per_token(config, seq_len)`` and
  ``flops.train_shape(config, batch, seq_len, attention)`` (the flash calls
  of one step, for the flash readers); optionally
  ``flops.moe_assignments_per_token(config)`` where the model routes.

``correct`` (outside the window, at the timed sizes, from the seeded initial
weights). On ``check_sequences`` seeded sequences of the mix's ``seq_len``:
the loss of ``Trainer.evaluate``'s step against the reference's, relative; AND
the logits of those sequences from the model as the task applies it against
the reference's rows, each row as max|diff| / max|row|, the median row and
the worst row each under its limit: a loss near ln(vocabulary) hides most of
a model, the median is what the compute precision costs every row, the worst
row is one token's fault. The family's own layer checks, where it has a
module under ``benchmark/checks``. The TIMED step's backward and optimizer
(`timed_step_gradient`): one step of the compiled train step from the seeded
state on the first global batch, whose gradient is read back from the
optimizer's first moment and held, by a forward-mode derivative of the
reference's loss along it, to the reference's gradient, with no leaf's
gradient zero or non-finite. Every counter the model's eval step returns
under a name ending ``dropped_assignments`` is 0; every fetched loss is
finite; nothing compiles inside the window. The limits are the
configuration's (``correct``), with their reasons and the readings they lie
between.

The window is the mix's ``window_steps`` optimizer steps from the seeded
weights (after ``warmup_steps``), closed earlier only if ``--seconds`` runs
out first: a model whose routing moves as it trains (PERF.md section 4) is
then measured over the same stretch of its training in every run, however
fast a step is.
"""

from __future__ import annotations

import functools
import importlib
import importlib.util
import inspect
import itertools
import json
import math
import time

import numpy as np


def family_modules(config: dict):
    """(reference, flops, checks or None) of the configuration's family."""
    family = config["family"]
    checks = f"benchmark.checks.{family}"
    return (importlib.import_module(f"benchmark.reference.{family}"),
            importlib.import_module(f"benchmark.flops.{family}"),
            importlib.import_module(checks)
            if importlib.util.find_spec(checks) else None)


def build(run):
    """The job as ``train.py`` builds it: mesh, model, task, loader,
    optimizer, trainer, state. State is made on the device from the seed in
    one jitted call of the trainer's own ``init_state``."""
    import jax
    import jax.numpy as jnp

    import train as train_cli
    from distributed_pytorch_training_tpu.data.text import (
        TokenLoader, synthetic_token_dataset,
    )
    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.parallel import (
        MeshSpec, build_mesh,
    )
    from distributed_pytorch_training_tpu.parallel.mesh import (
        batch_shard_count, validate_mesh_usage,
    )
    from distributed_pytorch_training_tpu.training.loop import (
        TrainConfig, Trainer,
    )
    from distributed_pytorch_training_tpu.training.optim import (
        make_optimizer, make_schedule,
    )
    from distributed_pytorch_training_tpu.training.tasks import (
        LanguageModelingTask,
    )

    job, mix = run.config["job"], run.traffic
    devices = run.devices[:run.cell["chips"]]
    mesh = build_mesh(MeshSpec.parse(mix["mesh"]), devices=devices)
    seq_len = int(mix["seq_len"])
    dtype = jnp.bfloat16 if job["amp"] else jnp.float32
    attention = train_cli.resolve_attention(
        job["attention"], True, jax.default_backend(), mesh.shape["pipe"],
        seq_len)
    kwargs = dict(dtype=dtype, remat=bool(job["remat"]))
    kwargs.update(run.config.get("model_overrides", {}))
    if attention == "flash":
        from distributed_pytorch_training_tpu.ops import (
            make_flash_attention_fn,
        )

        kwargs["attention_fn"] = make_flash_attention_fn(causal=True,
                                                         mesh=mesh)
    model = get_model(run.config["registry_model"], **kwargs)
    rules = type(model).partition_rules()
    validate_mesh_usage(mesh, rules=rules, attention=attention,
                        is_moe=False, pipelined=False)
    task = LanguageModelingTask(compute_dtype=dtype)
    per_chip = int(mix["per_chip_batch"])
    global_batch = per_chip * batch_shard_count(mesh)
    dataset = synthetic_token_dataset(
        int(mix["dataset_batches"]) * global_batch, seq_len,
        model.vocab_size, seed=run.seed)
    loader = TokenLoader(dataset, mesh, per_chip, shuffle=True,
                         seed=run.seed, drop_last=True)
    tx = make_optimizer(job["optimizer"],
                        make_schedule(job["schedule"], job["lr"]),
                        weight_decay=job["weight_decay"])
    trainer = Trainer(task, mesh, TrainConfig(
        per_device_batch=per_chip, print_freq=int(mix["print_freq"]),
        seed=run.seed, bf16=bool(job["amp"]),
        grad_accum=int(job["grad_accum"])), rules=rules)
    sample = np.zeros((1, seq_len), np.int32)
    init = jax.jit(lambda key: trainer.init_state(model, sample, tx, key))
    seeded_state = lambda: init(jax.random.PRNGKey(run.seed))  # noqa: E731
    # the same weights without the optimizer's moments (XLA drops them)
    init_params = jax.jit(lambda key: init(key).params)
    seeded_params = lambda: init_params(  # noqa: E731
        jax.random.PRNGKey(run.seed))
    state = seeded_state()
    run.note(attention=attention, mesh=dict(mesh.shape),
             global_batch=global_batch, seq_len=seq_len,
             params=state.param_count())
    return dict(mesh=mesh, model=model, trainer=trainer, state=state,
                seeded_state=seeded_state, seeded_params=seeded_params,
                loader=loader, dataset=dataset,
                global_batch=global_batch, seq_len=seq_len,
                attention=attention)


def timed_step_gradient(run, job) -> dict:
    """One step of the TIMED program, from the seeded state on the first
    global batch, and its gradient as a direction.

    After ONE step AdamW's first moment is the step's gradient times
    ``1 - b1``, so the compiled step's own gradient ``g`` is read from the
    state it returns. ``direction`` is each leaf of ``g`` scaled to unit
    norm; ``along`` is ``<g, direction>``, the sum of the leaves' norms;
    ``dead`` names what a direction cannot show, the leaves whose gradient
    is zero or not finite. The step donates its state, and all that it
    returns but the moment is let go at once (the direction then takes the
    moment's buffers), so that what the check holds on the device stays
    under what the timed step holds. `check_against_reference` makes the
    seeded state again when it is done (the same jitted call, the same
    seed): the run goes on from the weights it would have started from."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_training_tpu.parallel.sharding import shard_batch
    from distributed_pytorch_training_tpu.training import optim

    assert run.config["job"]["optimizer"] == "adamw"
    b1 = inspect.signature(optim.adamw).parameters["b1"].default
    n = job["global_batch"]
    batch = shard_batch({"input_ids": job["dataset"].tokens[:n],
                         "weight": np.ones(n, np.float32)}, job["mesh"])
    stepped, metrics = job["trainer"]._train_step(
        job.pop("state"), batch, jax.random.PRNGKey(run.seed))
    moment = next(s.mu for s in jax.tree_util.tree_leaves(
        stepped.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu"))

    @functools.partial(jax.jit, donate_argnums=0)
    def unit_leaves(mu):
        norms = jax.tree_util.tree_map(jnp.linalg.norm, mu)
        unit = jax.tree_util.tree_map(
            lambda m, r: m / jnp.where(r > 0, r, 1.0), mu, norms)
        return unit, jax.tree_util.tree_map(lambda r: r / (1.0 - b1), norms)

    loss = float(metrics["loss_sum"]) / float(metrics["weight"])
    del stepped, metrics
    direction, norms = unit_leaves(moment)
    norms = {jax.tree_util.keystr(path): float(r) for path, r
             in jax.tree_util.tree_leaves_with_path(norms)}
    dead = sorted(k for k, r in norms.items() if not r > 0)   # NaN too
    return {"direction": direction, "along": sum(norms.values()),
            "dead": dead, "leaf_norms": norms, "loss": loss, "sequences": n}


def check_against_reference(run, job, reference, checks) -> dict:
    """The program against the plain reference at the timed sizes, from the
    seeded initial weights: loss and logits on seeded sequences, the timed
    step's gradient, the family's layer checks.

    The reference's reverse-mode gradient does not fit beside the state at
    these sizes (a position-by-position rule alone would keep 16 GB of
    states a layer); its derivative ALONG a direction is one forward-mode
    pass, which keeps nothing. Along `timed_step_gradient`'s direction it
    reads the sum of the leaves' norms if the step's gradient is the
    reference's: ``step_grad_rel_diff`` is the relative difference, a
    norm-weighted mean over the leaves of how far each leaf's gradient is
    from the reference's along itself."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_training_tpu.parallel.mesh import (
        batch_shard_count,
    )
    from distributed_pytorch_training_tpu.parallel.sharding import (
        replicated, shard_batch,
    )
    from distributed_pytorch_training_tpu.training.tasks import summarize

    mesh, model = job["mesh"], job["model"]
    tol = run.config["correct"]
    n = max(int(run.traffic.get("check_sequences", 2)),
            batch_shard_count(mesh), job["global_batch"])
    ids = job["dataset"].tokens[:n]
    batch = shard_batch({"input_ids": ids,
                         "weight": np.ones(n, np.float32)}, mesh)
    # the step `Trainer.evaluate` runs, called once here so that the
    # counters it returns beside the loss sums are kept
    metrics = job["trainer"]._eval_step(job["state"], batch)
    got, _ = summarize(metrics)
    counters = {k: float(v) for k, v in metrics.get("counters", {}).items()}
    vocab = model.vocab_size
    rows_of = [jax.device_put(ids[i:i + 1], replicated(mesh))
               for i in range(n)]

    # the logits as the task applies the model, a sequence at a time and
    # kept on the host: the device holds the state and one (S, vocab) table
    apply_fn = job["state"].apply_fn

    @jax.jit
    def program_logits(params, one):
        logits = apply_fn({"params": params}, one, train=False,
                          mutable=["losses", "counters"])[0]
        return logits[..., :vocab].astype(jnp.float32)

    mine = [np.asarray(program_logits(job["state"].params, one))
            for one in rows_of]
    step = timed_step_gradient(run, job)
    params = job["seeded_params"]()
    logits_fn = reference.for_config(run.config)

    # ONE reference program, a sequence at a time: its forward-mode pass
    # along the step's direction gives the reference's logits (held against
    # the program's rows), its loss and that loss's derivative. Beside it
    # the device holds the weights, the direction and a few (S, vocab)
    # float32 tables: less than the timed step holds.
    @jax.jit
    def compare(params, direction, one, mine):
        def theirs_and_loss(p):
            theirs = logits_fn(p, one)
            logp = jax.nn.log_softmax(theirs[:, :-1], axis=-1)
            picked = jnp.take_along_axis(logp, one[:, 1:, None], axis=-1)
            return -picked.mean(), theirs

        nll, along, theirs = jax.jvp(theirs_and_loss, (params,),
                                     (direction,), has_aux=True)
        gap = jnp.abs(mine - theirs)
        return gap.max(-1) / jnp.abs(theirs).max(-1), nll, along

    direction = step.pop("direction")
    found = [compare(params, direction, one, table)
             for one, table in zip(rows_of, mine)]
    del direction, params, mine
    # the family's layer checks while the device holds nothing else: their
    # temporaries beside the state would be the run's peak of memory
    layers = checks.layer_checks(run.config, run.traffic, run.seed) \
        if checks else {}
    job["state"] = state = job["seeded_state"]()
    rows = np.concatenate([np.asarray(gap).ravel() for gap, _, _ in found])
    logits_rel, logits_rel_p50 = float(rows.max()), float(np.median(rows))
    want = sum(float(nll) for _, nll, _ in found) / n
    loss_rel = abs(got - want) / abs(want)
    # the step's loss is the mean over its global batch of equal sequences
    along = sum(float(a) for _, _, a in found[:step["sequences"]]) \
        / step["sequences"]
    step_grad_rel = abs(along - step["along"]) / abs(along)
    dropped = sum(v for k, v in counters.items()
                  if k.endswith("dropped_assignments"))
    # the state must live on every chip of the cell
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    spans = len(leaf.sharding.device_set) == run.cell["chips"]
    found = {"loss_rel_diff": loss_rel, "logits_rel_p50": logits_rel_p50,
             "logits_rel_diff": logits_rel,
             "step_grad_rel_diff": step_grad_rel, **layers}
    run.note(check="loss_logits_and_step_gradient_vs_reference",
             sequences=n, program=got, reference=want, **found,
             logits_rel_p99=float(np.percentile(rows, 99)),
             step={"loss": step["loss"], "sum_of_leaf_norms": step["along"],
                   "reference_along_them": along,
                   "dead_leaves": step["dead"],
                   "leaf_norms": step["leaf_norms"]},
             tolerances={k: v for k, v in tol.items() if k != "why"},
             eval_counters=counters, state_spans_all_chips=spans)
    ok = (math.isfinite(got) and math.isfinite(step["loss"])
          and logits_rel_p50 <= tol["logits_rel_p50_tol"]
          and all(v <= tol[k.removesuffix("_diff") + "_tol"]
                  for k, v in found.items() if k.endswith("_diff"))
          and not step["dead"] and dropped == 0 and spans)
    return {"ok": bool(ok), **found, "step_dead_leaves": len(step["dead"]),
            "counters": counters}


def run(run) -> dict:
    import jax

    reference, flops, checks = family_modules(run.config)
    job = build(run)
    trainer, loader = job["trainer"], job["loader"]
    tokens_per_step = job["global_batch"] * job["seq_len"]
    chips = run.cell["chips"]
    check = check_against_reference(run, job, reference, checks)

    def endless():
        for epoch in itertools.count():
            yield from loader.epoch(epoch)

    batches = endless()
    state = job["state"]
    losses = []
    steps_failed = 0

    def epoch(label: int, stop_fn):
        nonlocal state, steps_failed
        t0 = time.perf_counter()
        state, loss, _, _, steps = trainer.train_epoch(
            state, batches, label, len(loader),
            samples_per_step=[job["global_batch"]], stop_fn=stop_fn)
        t1 = time.perf_counter()   # train_epoch has fetched the loss
        losses.append(loss)
        if not math.isfinite(loss):
            steps_failed += steps
        return steps, t0, t1

    def after(n_steps: int):
        left = itertools.count(n_steps - 1, -1)
        return lambda: next(left) <= 0

    def until(deadline: float, n_steps: int = 0):
        enough = after(n_steps) if n_steps else (lambda: False)
        return lambda: enough() or time.perf_counter() >= deadline

    # warm-up: compiles the step and every small program the loop uses
    warm_steps, _, _ = epoch(0, after(int(run.traffic["warmup_steps"])))
    run.window_opens(time.perf_counter())

    trace_s = float(run.traffic["trace_seconds"]) if run.trace else 0.0
    wall0 = time.time()
    window_steps = int(run.traffic["window_steps"])
    steps, t0, t1 = epoch(1, until(
        time.perf_counter() + max(run.seconds - trace_s, 1.0), window_steps))
    run.window = (wall0, wall0 + (t1 - t0))
    run.window_closes()
    window_s = t1 - t0
    tokens_per_s_chip = steps * tokens_per_step / window_s / chips
    run.facts.update(
        window_s=window_s, steps=steps, tokens_per_step=tokens_per_step,
        tokens_per_s_chip=tokens_per_s_chip,
        flops_per_token=flops.train_flops_per_token(run.config,
                                                    job["seq_len"]),
        train_shape=flops.train_shape(
            run.config, job["global_batch"] // chips, job["seq_len"],
            job["attention"]))
    if hasattr(flops, "moe_assignments_per_token"):
        run.facts["moe_assignments_per_step"] = \
            tokens_per_step * flops.moe_assignments_per_token(run.config)

    attempted = steps
    if run.trace:
        # S0: does block_until_ready return before the device is done?
        # train_epoch's own `device_sync` span ends when block_until_ready
        # returns; the fetch that follows would have to wait if it lied.
        sync = [e for e in run.events if e.get("name") == "device_sync"]
        if sync:
            run.note(check="S0_block_until_ready_vs_fetch",
                     fetch_after_block_until_ready_ms=(
                         (wall0 + window_s - sync[-1]["ts"]) * 1e3),
                     step_ms=window_s / max(steps, 1) * 1e3)
        traced = []
        run.profile(lambda: traced.append(
            epoch(2, until(time.perf_counter() + trace_s))[0]))
        attempted += traced[0]
        run.window_closes()

    # the step program once more, from the compile cache, for XLA's own
    # FLOP count: a note beside the closed form, outside window and set-up
    cost = trainer._train_step.lower(
        state, next(batches), jax.random.PRNGKey(0)).compile() \
        .cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    xla_flops = float(cost.get("flops", 0.0)) or None
    counted = [e for e in run.events if e.get("kind") == "counter"
               and e.get("steps")]    # the model's, a print boundary each
    dropped_in_steps = sum(
        e["value"] for e in counted
        if str(e.get("name", "")).endswith("dropped_assignments"))
    if counted:
        # how the model's counters move over the window and the traced
        # steps: per step, boundary by boundary
        run.note(counters_per_step_by_boundary={
            name: [e["value"] / e["steps"] for e in counted
                   if e["name"] == name]
            for name in sorted({e["name"] for e in counted})})
    run.note(window_s=window_s, steps=steps, window_steps=window_steps,
             warmup_steps=warm_steps,
             tokens_per_step=tokens_per_step, losses=losses,
             dropped_assignments_in_traced_steps=dropped_in_steps,
             flops_per_token_closed_form=run.facts["flops_per_token"],
             flops_per_token_xla_per_chip=(
                 xla_flops / (tokens_per_step / chips)
                 if xla_flops else None))
    (run.out_dir / "train_facts.json").write_text(
        json.dumps(run.facts, default=str))

    correct = (check["ok"] and all(math.isfinite(x) for x in losses)
               and dropped_in_steps == 0
               and run.facts["compiles_in_window"] == 0 and steps > 0)
    return {"correct": correct, "attempted": attempted,
            "failed": steps_failed,
            "values": {"train_tokens_per_s_chip": tokens_per_s_chip},
            "counts": {"steps": steps, "tokens_per_step": tokens_per_step,
                       **{k: v for k, v in check.items()
                          if k not in ("ok", "counters")},
                       **check["counters"]}}

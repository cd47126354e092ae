"""The training job: seeded synthetic tokens through ``TokenLoader`` into
``Trainer.train_epoch``, the loop and the loader ``train.py`` uses, stopped
on the clock by ``stop_fn``.

The window opens on an idle device (the warm-up epoch ended in a fetch) and
closes when ``train_epoch`` returns, which is after it has fetched the last
step's loss to the host: a window closed by a fetched value, not by
``block_until_ready`` alone (PERF.md records that the two agree on this
runtime; the fetch is kept because it cannot return before the step ran).
"""

from __future__ import annotations

import itertools
import json
import math
import time

import numpy as np

from benchmark.flops import gpt2 as gpt2_flops
from benchmark.reference import gpt2 as reference

# The program computes in bf16 over fp32 weights; the reference in fp32.
# With random weights the loss is ~ln(vocab) = 10.8; a bf16 logit is off by up
# to 2^-9 of itself, and over 2 x 1023 positions that averages out: nine runs
# on the chip found 1.5e-5..6.4e-5 relative (PERF.md). fp32 compute would land
# near 1e-6; an 8-bit float, sixteen times coarser than bf16, near 1e-3.
LOSS_REL_TOL = 5e-4


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
    state = jax.jit(lambda key: trainer.init_state(model, sample, tx, key))(
        jax.random.PRNGKey(run.seed))
    run.note(attention=attention, mesh=dict(mesh.shape),
             global_batch=global_batch, seq_len=seq_len,
             params=state.param_count())
    return dict(mesh=mesh, model=model, trainer=trainer, state=state,
                loader=loader, dataset=dataset, global_batch=global_batch,
                seq_len=seq_len, attention=attention)


def check_against_reference(run, job) -> dict:
    """The program's loss on a seeded sample, from the seeded initial
    weights, against the plain reference at full width."""
    import jax

    from distributed_pytorch_training_tpu.parallel.mesh import (
        batch_shard_count,
    )
    from distributed_pytorch_training_tpu.parallel.sharding import (
        replicated, shard_batch,
    )

    mesh, model, state = job["mesh"], job["model"], job["state"]
    n = max(2, batch_shard_count(mesh))
    ids = job["dataset"].tokens[:n]
    batch = shard_batch({"input_ids": ids,
                         "weight": np.ones(n, np.float32)}, mesh)
    got, _ = job["trainer"].evaluate(state, [batch])
    eps = run.config["published"]["layer_norm_epsilon"]
    ref_loss = jax.jit(
        lambda params, x: reference.next_token_loss(
            reference.from_program_params(params), x, model.vocab_size,
            eps))
    want = float(ref_loss(state.params,
                          jax.device_put(ids, replicated(mesh))))
    rel = abs(got - want) / abs(want)
    # the state must live on every chip of the cell
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    spans = len(leaf.sharding.device_set) == run.cell["chips"]
    run.note(check="loss_vs_reference", sequences=n, program=got,
             reference=want, rel_diff=rel, tol=LOSS_REL_TOL,
             state_spans_all_chips=spans)
    return {"ok": bool(math.isfinite(got) and rel <= LOSS_REL_TOL and spans),
            "rel_diff": rel}


def run(run) -> dict:
    import jax

    job = build(run)
    trainer, loader = job["trainer"], job["loader"]
    tokens_per_step = job["global_batch"] * job["seq_len"]
    chips = run.cell["chips"]
    check = check_against_reference(run, job)

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

    def until(deadline: float):
        return lambda: time.perf_counter() >= deadline

    # warm-up: compiles the step and every small program the loop uses
    warm_steps, _, _ = epoch(0, after(int(run.traffic["warmup_steps"])))
    run.window_opens(time.perf_counter())

    trace_s = float(run.traffic["trace_seconds"]) if run.trace else 0.0
    wall0 = time.time()
    steps, t0, t1 = epoch(1, until(time.perf_counter()
                                   + max(run.seconds - trace_s, 1.0)))
    run.window = (wall0, wall0 + (t1 - t0))
    run.window_closes()
    window_s = t1 - t0
    tokens_per_s_chip = steps * tokens_per_step / window_s / chips
    run.facts.update(window_s=window_s, steps=steps,
                     tokens_per_s_chip=tokens_per_s_chip,
                     flops_per_token=gpt2_flops.train_flops_per_token(
                         run.config["published"], job["seq_len"]),
                     train_shape=dict(
                         batch=job["global_batch"] // chips,
                         seq_len=job["seq_len"],
                         heads=run.config["published"]["n_head"],
                         head_dim=(run.config["published"]["n_embd"]
                                   // run.config["published"]["n_head"]),
                         layers=run.config["published"]["n_layer"],
                         attention=job["attention"]))

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
    run.note(window_s=window_s, steps=steps, warmup_steps=warm_steps,
             tokens_per_step=tokens_per_step, losses=losses,
             flops_per_token_closed_form=run.facts["flops_per_token"],
             flops_per_token_xla_per_chip=(
                 xla_flops / (tokens_per_step / chips)
                 if xla_flops else None))
    (run.out_dir / "train_facts.json").write_text(
        json.dumps(run.facts, default=str))

    correct = (check["ok"] and all(math.isfinite(x) for x in losses)
               and run.facts["compiles_in_window"] == 0 and steps > 0)
    return {"correct": correct, "attempted": attempted,
            "failed": steps_failed,
            "values": {"train_tokens_per_s_chip": tokens_per_s_chip},
            "counts": {"steps": steps, "tokens_per_step": tokens_per_step,
                       "loss_rel_diff": check["rel_diff"]}}

"""Shared measurement harness for bench.py and experiments/scaling.py.

One copy of the recipe (build trainer -> synthetic device batch -> warmup ->
timed windows) so the headline bench and the experiment tables stay
comparable — the throughput-meter role of the reference
(/root/reference/train_ddp.py:224-243), done without host syncs in the loop.

Timing methodology (important): the synchronization point is a **value
fetch** (`jax.device_get` of a step output), not `block_until_ready`: a
value fetch cannot return before the program ran — the bytes must exist.
It carries a constant round-trip cost, so the rate is computed by **window
differencing**: time T(k) for k steps and T(2k) for 2k steps (each
fetch-synced) and report k / (T(2k) - T(k)). Constant per-window overhead
(dispatch, fetch) cancels exactly. Windows auto-grow until the differenced
time is large enough to trust. (Whether `block_until_ready` agrees with
the fetch on this machine's stock TPU runtime is ROADMAP S0's to
re-check.)
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def build_image_trainer(devices: Sequence[jax.Device], bf16: bool,
                        model_name: str = "resnet18", image_hw: int = 32,
                        num_classes: int = 10, zero1: bool = False,
                        grad_sync: Optional[dict] = None,
                        mesh_spec: Optional[str] = None):
    """(trainer, state, mesh) for an image-classification config on a pure-DP
    mesh over `devices` (the benchmark workload, BASELINE.json:8).
    ``zero1`` switches the trainer to the sharded weight update;
    ``grad_sync`` holds TrainConfig overrides for the explicit reducer
    (bucket_cap_mb / wire_dtype / overlap_grad_sync / grad_accum).
    ``mesh_spec`` may name BATCH axes only ("slice=2,data=-1", the
    int8_hier tiered-wire arms) — image models ship replicated-only
    partition rules, so a model/seq axis is rejected upstream."""
    from ..data import CIFAR10_MEAN, CIFAR10_STD
    from ..models import get_model
    from ..parallel import MeshSpec, build_mesh
    from ..training import TrainConfig, Trainer
    from ..training.optim import sgd
    from ..training.tasks import ImageClassificationTask

    spec = (MeshSpec.parse(mesh_spec) if mesh_spec
            else MeshSpec(data=len(devices)))
    mesh = build_mesh(spec, devices=list(devices))
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    model = get_model(model_name, num_classes=num_classes, dtype=dtype)
    task = ImageClassificationTask(mean=CIFAR10_MEAN, std=CIFAR10_STD,
                                   augment=True, compute_dtype=dtype)
    trainer = Trainer(task, mesh, TrainConfig(seed=0, bf16=bf16,
                                              zero1=zero1,
                                              **(grad_sync or {})))
    state = trainer.init_state(
        model, np.zeros((1, image_hw, image_hw, 3), np.float32),
        sgd(0.1, momentum=0.9, weight_decay=5e-4), jax.random.PRNGKey(0))
    return trainer, state, mesh


def is_lm_model(model_name: str) -> bool:
    """One source of truth for the image-vs-LM dispatch (bench + drivers)."""
    return model_name.startswith(("gpt2", "bert"))


def lm_vocab(model_name: str) -> int:
    return 30522 if model_name.startswith("bert") else 50257


def build_lm_trainer(devices: Sequence[jax.Device], bf16: bool,
                     model_name: str, seq_len: int,
                     model_kwargs: Optional[dict] = None,
                     zero1: bool = False,
                     grad_sync: Optional[dict] = None,
                     mesh_spec: Optional[str] = None):
    """(trainer, state, mesh) for a language-model config (gpt2_*/bert_base,
    BASELINE.json:11-12) on a pure-DP mesh, AdamW, real vocab sizes.
    `model_kwargs` overrides architecture fields (CI smoke runs shrink the
    model; benchmarks use the real sizes). ``grad_sync`` — see
    `build_image_trainer`. ``mesh_spec`` ("data=-1,model=2") builds the
    2-D explicit TP x FSDP mesh (the gpt2_355m_fsdp_tp bench arm); the
    vocab pads to lcm(128, model) exactly as train.py pads it."""
    import math

    from ..models import get_model
    from ..parallel import MeshSpec, build_mesh
    from ..training import TrainConfig, Trainer
    from ..training.optim import adamw
    from ..training.tasks import (
        LanguageModelingTask, MaskedLMTask, MoeLanguageModelingTask,
    )

    spec = (MeshSpec.parse(mesh_spec) if mesh_spec
            else MeshSpec(data=len(devices)))
    mesh = build_mesh(spec, devices=list(devices))
    model_n = dict(mesh.shape).get("model", 1)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    kwargs = dict(model_kwargs or {})
    if model_n > 1:
        kwargs.setdefault("pad_vocab_to_multiple_of",
                          math.lcm(128, model_n))
    from ..ops.flash_attention import (
        flash_backend_supported, flash_supports_length,
    )

    if "attention_fn" not in kwargs and flash_backend_supported() \
            and flash_supports_length(seq_len):
        # Benchmark with the flash kernel — the fast path users get via
        # --attention flash (auto default): 42% faster than the einsum path
        # for GPT-2 @ S=1024 on v5e. Legal for BERT too (bidirectional,
        # causal=False; padding masks ride the kernel). The length gate
        # matches resolve_attention: a seq_len with no usable block (e.g.
        # 2056) falls back to the einsum path instead of erroring at trace.
        from ..ops import make_flash_attention_fn

        kwargs["attention_fn"] = make_flash_attention_fn(
            causal=not model_name.startswith("bert"), mesh=mesh)
    model = get_model(model_name, dtype=dtype, max_position=max(seq_len, 512),
                      **kwargs)
    if model_name.startswith("bert"):
        task = MaskedLMTask(compute_dtype=dtype)
    elif "moe" in model_name:
        # measuring an MoE step without the router load-balancing loss
        # would time a step nobody trains
        task = MoeLanguageModelingTask(compute_dtype=dtype)
    else:
        task = LanguageModelingTask(compute_dtype=dtype)
    from ..parallel.mesh import BATCH_AXES, batch_shard_count

    trainer = Trainer(task, mesh, TrainConfig(seed=0, bf16=bf16,
                                              zero1=zero1,
                                              **(grad_sync or {})),
                      rules=type(model).partition_rules())
    # zero1/fsdp shard the update; the AdamW global-norm clip must psum
    # across the shards or each replica clips by its own shard's norm
    # (optim.py). On a single batch shard the Trainer runs the replicated
    # (non-shard_map) path, where a psum over the batch axes would hit
    # unbound axis names — shard_axes must follow the SAME passthrough
    # condition.
    fsdp = bool((grad_sync or {}).get("fsdp_explicit"))
    explicit_tp = fsdp and model_n > 1
    # zero1 on a model-axis mesh runs the per-leaf GSPMD update OUTSIDE
    # shard_map, where a batch-axes psum in the clip would hit unbound
    # axis names — the same exclusion train.py applies
    sharded = ((zero1 and model_n <= 1) or fsdp) \
        and (batch_shard_count(mesh) > 1 or explicit_tp)
    from ..parallel.mesh import MODEL

    shard_axes = None
    clip_weights = None
    if sharded:
        shard_axes = (((MODEL,) + BATCH_AXES) if explicit_tp
                      else BATCH_AXES)
    if explicit_tp:
        # the clip's norm psum rides (model,) + batch axes; the TP layout
        # stores model-replicated leaves once per model shard, so their
        # squared contributions down-weight 1/M — the ONE derivation
        # train.py also uses (parallel/sharding.py)
        from ..parallel.sharding import tp_clip_weights_for_model

        clip_weights = tp_clip_weights_for_model(
            model, type(model).partition_rules(), model_n,
            np.zeros((model_n, seq_len), np.int32))
    tx = adamw(1e-4, shard_axes=shard_axes,
               clip_leaf_weights=clip_weights)
    state = trainer.init_state(model, np.zeros((1, seq_len), np.int32),
                               tx, jax.random.PRNGKey(0))
    return trainer, state, mesh


def build_trainer(devices: Sequence[jax.Device], bf16: bool, model_name: str,
                  seq_len: int = 512, image_hw: int = 32,
                  num_classes: int = 10,
                  lm_overrides: Optional[dict] = None,
                  zero1: bool = False,
                  grad_sync: Optional[dict] = None,
                  mesh_spec: Optional[str] = None):
    """Model-family dispatch used by bench.py AND the experiment drivers —
    the same `--model` string must measure the same config everywhere.
    ``mesh_spec`` ("data=-1,model=2") builds a 2-D mesh for the explicit
    TP x FSDP arms — LM models only (image models ship replicated-only
    partition rules)."""
    if is_lm_model(model_name):
        return build_lm_trainer(devices, bf16, model_name, seq_len,
                                lm_overrides, zero1=zero1,
                                grad_sync=grad_sync, mesh_spec=mesh_spec)
    if mesh_spec:
        # image models may tier their BATCH axes (slice=2,data=-1 — the
        # int8_hier arms); any non-batch axis > 1 needs partition rules
        # image models don't have
        from ..parallel import MeshSpec
        from ..parallel.mesh import BATCH_AXES

        sizes = dataclasses.asdict(MeshSpec.parse(mesh_spec))
        bad = {a: s for a, s in sizes.items()
               if s not in (1,) and a not in BATCH_AXES}
        if bad:
            raise ValueError(
                f"mesh_spec={mesh_spec!r} puts {bad} on non-batch axes; "
                f"{model_name} has no TP/seq/pipe form — image models "
                "accept batch-axis tiers only (slice/data/fsdp)")
    return build_image_trainer(devices, bf16, model_name, image_hw,
                               num_classes, zero1=zero1,
                               grad_sync=grad_sync, mesh_spec=mesh_spec)


def make_synth_batch(mesh, model_name: str, per_device_batch: int,
                     seq_len: int = 512, image_hw: int = 32,
                     num_classes: int = 10):
    """(sharded batch, global batch) matching `build_trainer`'s config."""
    if is_lm_model(model_name):
        return synth_token_batch(mesh, per_device_batch, seq_len,
                                 lm_vocab(model_name))
    return synth_image_batch(mesh, per_device_batch, image_hw, num_classes)


def synth_image_batch(mesh, per_device_batch: int, image_hw: int = 32,
                      num_classes: int = 10):
    """(sharded_batch, global_batch): deterministic uint8 batch on the mesh."""
    from ..parallel import shard_batch
    from ..parallel.mesh import batch_shard_count

    global_batch = per_device_batch * batch_shard_count(mesh)
    rng = np.random.RandomState(0)
    batch = shard_batch({
        "image": rng.randint(0, 256, (global_batch, image_hw, image_hw, 3)
                             ).astype(np.uint8),
        "label": rng.randint(0, num_classes, global_batch).astype(np.int32),
        "weight": np.ones(global_batch, np.float32),
    }, mesh)
    return batch, global_batch


def synth_token_batch(mesh, per_device_batch: int, seq_len: int,
                      vocab_size: int = 50257):
    """(sharded_batch, global_batch): deterministic token batch on the mesh."""
    from ..parallel import shard_batch
    from ..parallel.mesh import batch_shard_count

    global_batch = per_device_batch * batch_shard_count(mesh)
    rng = np.random.RandomState(0)
    batch = shard_batch({
        "input_ids": rng.randint(0, vocab_size,
                                 (global_batch, seq_len)).astype(np.int32),
        "weight": np.ones(global_batch, np.float32),
    }, mesh)
    return batch, global_batch


def trace_exposed_comm(build_fn, key=None, steps: int = 3):
    """Best-effort exposed-comm fraction of a train step
    (`trace_analysis.comm_overlap_split` over a short jax.profiler
    capture). ``build_fn() -> (trainer, state, batch)`` must build a
    SACRIFICIAL trainer/state: the jitted step donates its input state, so
    a capture that dies mid-step consumes those buffers — they must never
    be the ones a timed run still needs. Returns the percentage, or None
    on any failure (the number is an observability nicety, never worth
    failing a measurement for).
    """
    import tempfile

    from .trace_analysis import capture_step_trace, comm_overlap_split

    try:
        trainer, state, batch = build_fn()
        key = jax.random.PRNGKey(0) if key is None else key
        state, _ = trainer._train_step(state, batch, key)  # warmup/compile
        with tempfile.TemporaryDirectory(prefix="comm_trace_") as td:
            capture_step_trace(trainer._train_step, state, batch, key, td,
                               steps=steps)
            return comm_overlap_split(td)["exposed_frac_pct"]
    except Exception:
        return None


def _fetch(metrics) -> float:
    """True completion sync: pull a step-output VALUE to the host. Unlike
    block_until_ready this cannot return before the program has executed."""
    return float(jax.device_get(metrics["weight"]))


def _run_window(step_fn: Callable, state, batch, key, n: int):
    """Dispatch n steps and fetch-sync; returns (state, wall seconds)."""
    t0 = time.perf_counter()
    metrics = None
    for _ in range(n):
        state, metrics = step_fn(state, batch, key)
    if metrics is not None:
        _fetch(metrics)
    return state, time.perf_counter() - t0


def timed_steps(step_fn: Callable, state, batch, global_batch: int,
                steps: int, repeats: int = 3, warmup: int = 3,
                min_window_s: float = 0.5,
                max_steps: int = 2048) -> Tuple[float, float]:
    """Median (steps/sec, samples/sec) over `repeats` differenced windows.

    `step_fn(state, batch, key) -> (state, metrics)` may be a jitted function
    or an AOT-compiled executable. Warmup covers compile + autotuning. Each
    repeat measures T(steps) and T(2*steps) and reports
    steps / (T(2*steps) - T(steps)) — constant sync overhead cancels. If the
    differenced time is below `min_window_s`, the window doubles (up to
    `max_steps`) so per-window overhead noise cannot dominate the rate.
    """
    from .flops import MeasurementError

    key = jax.random.PRNGKey(0)
    for _ in range(max(warmup, 1)):
        state, metrics = step_fn(state, batch, key)
    _fetch(metrics)

    # Auto-size the window: the differenced interval must dwarf timing noise.
    # The break condition keeps t1/t2 from the n they were measured at — a
    # stale-timing exit here would inflate the rate 2x.
    n = steps
    while True:
        state, t1 = _run_window(step_fn, state, batch, key, n)
        state, t2 = _run_window(step_fn, state, batch, key, 2 * n)
        if t2 - t1 >= min_window_s or 2 * n >= max_steps:
            break
        n *= 2

    # A non-positive (or tiny) differenced interval means overhead variance
    # swamped the n-step work — that window is NOISE, not a rate. Publishing
    # n/epsilon would be the impossible-throughput failure class this
    # harness exists to prevent, so bad windows are retried and a window
    # budget exhausted is a loud MeasurementError, never a number.
    floor = max(1e-4, 0.05 * min_window_s)
    rates: list = []
    bad = 0
    if t2 - t1 >= floor:
        rates.append(n / (t2 - t1))
    else:
        bad += 1
    while len(rates) < repeats and bad < repeats + 3:
        state, t1 = _run_window(step_fn, state, batch, key, n)
        state, t2 = _run_window(step_fn, state, batch, key, 2 * n)
        if t2 - t1 >= floor:
            rates.append(n / (t2 - t1))
        else:
            bad += 1
    if not rates:
        raise MeasurementError(
            f"timing windows of {n}..{2 * n} steps produced no positive "
            f"differenced interval (last T(2n)-T(n) = {t2 - t1:.4f}s) — "
            "backend timing is too noisy to report a throughput")
    sps = float(np.median(rates))
    return sps, sps * global_batch


def _contract_check(trainer, state, optimized_text: str, lowered,
                    zero1: bool, grad_sync: Optional[dict],
                    per_device_batch: int = 0,
                    seq_len: int = 0) -> Optional[dict]:
    """Evaluate the HLO contract rules against the measured executable and
    return {"pass": bool, "violations": [...]} for the bench row — the
    per-arm pass/fail bench history tracks across PRs (ISSUE 3).
    Best-effort by design: a checker failure is recorded as an error
    string, never a measurement failure."""
    try:
        from ..analysis.hlo_rules import (
            StepArtifacts, check_artifacts, preopt_hlo_text,
            replicated_large_buffers,
        )
        from ..parallel.grad_sync import build_bucket_plan
        from ..parallel.mesh import batch_shard_count

        cfg = dict(grad_sync or {})
        cfg["zero1"] = bool(zero1)
        cfg["donate_state"] = trainer.config.donate_state
        is_fsdp = bool(cfg.get("fsdp_explicit"))
        try:
            preopt = preopt_hlo_text(lowered)
        except Exception:
            preopt = None
        plan = build_bucket_plan(state.params,
                                 float(cfg.get("bucket_cap_mb", 0.0)))
        artifacts = StepArtifacts(
            name="bench",
            optimized_text=optimized_text,
            preopt_text=preopt,
            config=cfg,
            backend=jax.default_backend(),
            n_shards=batch_shard_count(trainer.mesh),
            total_grad_bytes=plan.total_bytes,
            replicated_state_buffers=(
                replicated_large_buffers(state.opt_state, 8192)
                if (zero1 or is_fsdp) else ()),
            replicated_param_buffers=(
                replicated_large_buffers(state.params, 8192)
                if is_fsdp else ()),
            layer_group_padded_sizes=(
                trainer._fsdp_plan.padded_group_sizes
                if is_fsdp and trainer._fsdp_plan is not None else ()),
        )
        tp_psums, tp_gathers = trainer.tp_expected_model_collectives()
        artifacts = dataclasses.replace(
            artifacts, model_shards=trainer._tp_n,
            tp_expected_psums=tp_psums,
            tp_expected_model_gathers=tp_gathers,
            tp_ce_stat_elements=trainer.tp_expected_ce_stat_elements(
                per_device_batch, seq_len),
            slice_shards=(trainer._hier.n_slices
                          if trainer._hier is not None else 1))
        findings = check_artifacts(artifacts)
        return {"pass": not findings,
                "violations": [f.as_dict() for f in findings]}
    except Exception as e:  # noqa: BLE001 - observability must not kill a run
        return {"pass": None, "error": f"{type(e).__name__}: {e}"}


def checkpoint_save_ab(state, base_dir: Optional[str] = None) -> dict:
    """Sync-vs-async checkpoint blocked-time A/B on the measured state —
    the ``save_blocked_ms`` bench instrument (training/checkpoint.py).

    Saves the state once through a synchronous CheckpointManager and once
    through the async (snapshot-then-write) default, into a throwaway
    directory, and reports the milliseconds the CALLING thread spent
    blocked inside ``save`` for each — the step-time stall a training loop
    pays per save. Under async the blocked time collapses to ~the
    device→host ``snapshot_ms``; the sync number is the stall the
    background writer kills. ``write_ms`` is the drained background-write
    wall (the work that moved OFF the critical path). Best-effort: an I/O
    failure returns ``{"error": ...}``, never a measurement failure."""
    import shutil
    import tempfile

    from ..training.checkpoint import CheckpointManager

    base = Path(tempfile.mkdtemp(prefix="dpt-ckpt-ab-", dir=base_dir))
    try:
        out = {}
        # Discarded warm-up save: the first save in a process pays one-time
        # orbax/TensorStore costs (driver registry, thread pools) that are
        # neither arm's steady-state stall — without this they land on
        # whichever arm runs first and skew the A/B.
        warm = CheckpointManager(str(base / "warmup"), max_to_keep=1,
                                 async_save=False)
        try:
            warm.save(1, state, epoch=0)
        finally:
            warm.close()
        for mode, async_save in (("sync", False), ("async", True)):
            mgr = CheckpointManager(str(base / mode), max_to_keep=1,
                                    async_save=async_save)
            try:
                mgr.save(1, state, epoch=0)
                blocked = mgr.save_blocked_ms
                t0 = time.perf_counter()
                mgr.wait()
                drain_ms = (time.perf_counter() - t0) * 1e3
                out[f"{mode}_blocked_ms"] = round(blocked, 1)
                if async_save:
                    out["snapshot_ms"] = round(mgr.snapshot_ms, 1)
                    out["write_ms"] = round(drain_ms, 1)
            finally:
                mgr.close()
        return out
    except Exception as e:  # noqa: BLE001 - observability must not kill a run
        return {"error": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(base, ignore_errors=True)


def build_serving_engine(devices: Sequence[jax.Device], model_name: str,
                         buckets: Sequence[int] = (16, 32), rows: int = 8,
                         max_new_tokens: int = 8, serve_dtype: str = "fp32",
                         model_overrides: Optional[dict] = None,
                         ckpt_dir: Optional[str] = None,
                         train_config=None, seed: int = 0,
                         optimizer: str = "auto", momentum: float = 0.9,
                         weight_decay: float = 5e-4,
                         mesh_spec: Optional[str] = None,
                         config=None, engine_cls=None,
                         min_positions: int = 0):
    """(engine, mesh) for a serving config on a pure-DP mesh — the serving
    sibling of `build_trainer`, so bench rows and the CLI measure the same
    engine. Without ``ckpt_dir`` the weights are random-init (a smoke of
    the serving path, not a served model — the row says so); with it, the
    newest manifest-verified checkpoint restores through the same template
    machinery a training resume uses (``train_config`` carries the
    training run's zero1/fsdp/wire flags when they differ from defaults).

    ``config``/``engine_cls`` swap in a richer config + engine pair
    (`build_slot_engine` passes PagedServeConfig + SlotEngine) while every
    other knob — checkpoint templates, mesh validation, vocab/positions
    sizing — stays this one code path; ``min_positions`` widens the LM's
    position table when the engine's padded view (pages) outgrows
    ``max(buckets) + max_new_tokens``.

    The restore template's optimizer chain must STRUCTURALLY match the
    training run's (orbax validates the opt_state tree): the template is
    built exactly as train.py builds it — ``make_optimizer`` with a
    callable (constant) schedule and no grad clip — and ``optimizer`` /
    ``momentum`` / ``weight_decay`` are the knobs that change the chain's
    structure (a zero momentum/decay drops a transform). "auto" picks the
    family recipe: adamw for LM models, sgd for vision (train.py's CLI
    default is sgd everywhere; pass ``optimizer="sgd"`` for an LM trained
    that way).
    """
    from ..models import get_model
    from ..parallel import MeshSpec, build_mesh
    from ..serving.engine import InferenceEngine, ServeConfig
    from ..training.optim import make_optimizer, make_schedule

    # --mesh (ISSUE 13 satellite): default stays the 1-D pure-DP mesh —
    # every existing invocation unchanged; "data=N,model=M" serves big
    # models TP-sharded over the model axis via the GSPMD rules
    # (validate_mesh rejects axes the served model cannot use).
    spec = (MeshSpec.parse(mesh_spec) if mesh_spec
            else MeshSpec(data=len(devices)))
    mesh = build_mesh(spec, devices=list(devices))
    cfg = config if config is not None else ServeConfig(
        buckets=tuple(buckets), rows=rows,
        max_new_tokens=max_new_tokens, serve_dtype=serve_dtype)
    serve_dtype = cfg.serve_dtype
    dtype = jnp.bfloat16 if serve_dtype == "bf16" else jnp.float32
    if optimizer == "auto":
        optimizer = "adamw" if is_lm_model(model_name) else "sgd"
    tx = make_optimizer(optimizer, make_schedule("constant", 0.1),
                        momentum=momentum, weight_decay=weight_decay)
    if not is_lm_model(model_name):
        # --model-overrides applies here too: a resnet trained with
        # num_classes=100 must be able to build a matching template
        model = get_model(model_name, dtype=dtype,
                          **(model_overrides or {}))
        sample = np.zeros((1, 32, 32, 3), np.float32)
    else:
        kwargs = dict(model_overrides or {})
        need = max(max(cfg.buckets) + cfg.max_new_tokens, min_positions)
        kwargs.setdefault("max_position", max(512, need))
        model = get_model(model_name, dtype=dtype, **kwargs)
        sample = np.zeros((1, min(cfg.buckets)), np.int32)
    rules = (type(model).partition_rules()
             if hasattr(type(model), "partition_rules") else None)
    from ..parallel.mesh import validate_mesh

    validate_mesh(mesh, rules=rules)
    serve_rules = rules if dict(mesh.shape).get("model", 1) > 1 else None
    cls = engine_cls if engine_cls is not None else InferenceEngine
    if ckpt_dir:
        engine = cls.from_checkpoint(
            ckpt_dir, model, mesh, cfg, tx, sample,
            train_config=train_config, rules=serve_rules)
    else:
        variables = model.init(jax.random.PRNGKey(seed), sample, train=False)
        engine = cls(model, mesh, cfg, variables["params"],
                     batch_stats=variables.get("batch_stats"),
                     rules=serve_rules)
    return engine, mesh


def build_slot_engine(devices: Sequence[jax.Device], model_name: str,
                      buckets: Sequence[int] = (8, 16), rows: int = 8,
                      max_new_tokens: int = 8, kv_dtype: str = "fp32",
                      page_size: int = 8, prefix_sharing: bool = True,
                      n_pages: int = 0, prefix_skip: bool = True, **kw):
    """(SlotEngine, mesh) — the token-granular sibling of
    `build_serving_engine` (same checkpoint templates, mesh validation and
    sizing; ``**kw`` forwards model_overrides/ckpt_dir/train_config/...).
    The engine decodes over a paged, optionally int8 KV pool
    (serving/continuous.py); ``min_positions`` is derived here because the
    gathered dense view is ``pages_per_slot * page_size`` wide — page
    padding can outgrow ``max(buckets) + max_new_tokens``."""
    from ..serving.continuous import SlotEngine
    from ..serving.paged import PagedServeConfig

    cfg = PagedServeConfig(
        buckets=tuple(buckets), rows=rows, max_new_tokens=max_new_tokens,
        page_size=page_size, kv_dtype=kv_dtype, n_pages=n_pages,
        prefix_sharing=prefix_sharing, prefix_skip=prefix_skip)
    return build_serving_engine(
        devices, model_name, buckets=buckets, rows=rows,
        max_new_tokens=max_new_tokens, config=cfg, engine_cls=SlotEngine,
        min_positions=cfg.pages_per_slot * cfg.page_size, **kw)


def build_spec_engine(devices: Sequence[jax.Device], model_name: str,
                      draft_model_name: str,
                      buckets: Sequence[int] = (8, 16), rows: int = 8,
                      max_new_tokens: int = 8, page_size: int = 8,
                      prefix_sharing: bool = True, n_pages: int = 0,
                      prefix_skip: bool = True, draft_k: int = 4,
                      draft_overrides: Optional[dict] = None,
                      seed: int = 0, **kw):
    """(SpeculativeEngine, mesh) — `build_slot_engine` with a draft LM
    riding along. The target side goes through the exact
    `build_serving_engine` path (checkpoint templates, mesh validation,
    position sizing) via an engine_cls closure that injects the draft;
    the draft itself is ALWAYS random-init fp32 here (it is a throughput
    device, not a served artifact — acceptance is exact-match against the
    target, so draft weights change speed, never the emitted stream).

    The draft model's position table is sized from the DRAFT padded view:
    speculative.py widens ``max_new_tokens`` by K (the last propose run of
    a request writes draft k/v past the target frontier), so its
    pages_per_slot can outgrow the target's.
    """
    from ..models import get_model
    from ..serving.paged import PagedServeConfig
    from ..serving.speculative import SpeculativeEngine

    cfg = PagedServeConfig(
        buckets=tuple(buckets), rows=rows, max_new_tokens=max_new_tokens,
        page_size=page_size, kv_dtype="fp32", n_pages=n_pages,
        prefix_sharing=prefix_sharing, prefix_skip=prefix_skip)
    dcfg = dataclasses.replace(
        cfg, max_new_tokens=max_new_tokens + draft_k, n_pages=0)
    dkwargs = dict(draft_overrides or {})
    dkwargs.setdefault("max_position",
                       max(512, dcfg.pages_per_slot * dcfg.page_size))
    draft = get_model(draft_model_name, dtype=jnp.float32, **dkwargs)
    dvars = draft.init(jax.random.PRNGKey(seed + 1),
                       np.zeros((1, min(cfg.buckets)), np.int32),
                       train=False)

    class _SpecEngine(SpeculativeEngine):
        def __init__(self, model, mesh, config, params, **ekw):
            super().__init__(model, mesh, config, params, draft,
                             dvars["params"], spec_k=draft_k, **ekw)

    return build_serving_engine(
        devices, model_name, buckets=buckets, rows=rows,
        max_new_tokens=max_new_tokens, config=cfg, engine_cls=_SpecEngine,
        min_positions=cfg.pages_per_slot * cfg.page_size, seed=seed, **kw)


def measure_serving(model_name: str = "gpt2_124m", n_requests: int = 24,
                    offered_rps: float = 16.0,
                    buckets: Sequence[int] = (16, 32), rows: int = 8,
                    max_new_tokens: int = 8, serve_dtype: str = "fp32",
                    mixed_want: bool = False,
                    devices: Optional[Sequence[jax.Device]] = None,
                    model_overrides: Optional[dict] = None,
                    ckpt_dir: Optional[str] = None, seed: int = 0,
                    optimizer: str = "auto", momentum: float = 0.9,
                    weight_decay: float = 5e-4,
                    train_config=None,
                    mesh_spec: Optional[str] = None) -> dict:
    """Serving latency/throughput at FIXED offered load — the serving row
    of the bench table (`serving bench` prints it).

    A load generator submits ``n_requests`` mixed-length prompts on a
    deterministic 1/``offered_rps`` cadence into the request queue while
    the engine worker drains it (continuous batching); per-request latency
    is submit -> result. Reports p50/p99 latency, achieved request and
    token throughput, the engine's compile census
    (``recompiles_after_warmup`` MUST be 0 — the contract the acceptance
    test asserts), and the served checkpoint's provenance when one was
    loaded. Offered load is what the schedule ASKS for; ``achieved_rps``
    is what the engine absorbed — an overloaded engine shows the gap
    honestly instead of averaging it away.

    ``mixed_want=True`` is the serving-traffic workload of the
    continuous-batching A/B: each request WANTS a per-request number of
    tokens (1..max_new, same rng stream as the token-granular row). The
    iteration engine has no per-request decode length — every batch
    member decodes the full ``max_new_tokens`` — so ``tokens_per_sec``
    counts only the WANTED tokens: the decode cycles spent past a
    request's want are the convoy waste this mode exists to measure,
    not throughput to credit.
    """
    import threading

    from ..serving.batching import RequestQueue, serve_forever

    devices = list(devices) if devices is not None else jax.devices()
    engine, mesh = build_serving_engine(
        devices, model_name, buckets=buckets, rows=rows,
        max_new_tokens=max_new_tokens, serve_dtype=serve_dtype,
        model_overrides=model_overrides, ckpt_dir=ckpt_dir, seed=seed,
        optimizer=optimizer, momentum=momentum,
        weight_decay=weight_decay, train_config=train_config,
        mesh_spec=mesh_spec)
    if not engine.is_token:
        # the load generator submits token prompts; an image engine would
        # crash mid-warmup with a confusing traceback instead of this
        raise ValueError(
            f"serving bench drives token models (gpt2/bert); {model_name} "
            "serves images — use `serving smoke` or engine.serve_images")

    # warmup: compile every bucket AND execute once per bucket, so the
    # timed window measures steady state — then pin the compile census
    engine.warmup()
    rng = np.random.RandomState(seed)
    # prompt ids from the SERVED model's vocab (overridden CI models
    # shrink it below the family default lm_vocab reports)
    vocab = int(getattr(engine.model, "vocab_size", 0)) or 256
    for b in engine.config.buckets:
        engine.serve_tokens([rng.randint(0, max(vocab, 2), b)
                             .astype(np.int32)])
    compiles_warm = engine.compiles

    lens = [int(rng.randint(1, max(engine.config.buckets) + 1))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, max(vocab, 2), n).astype(np.int32)
               for n in lens]
    # drawn AFTER the prompts so both A/B rows (this and
    # measure_serving_continuous) see identical prompt AND want streams
    wants = ([int(rng.randint(1, max_new_tokens + 1))
              for _ in range(n_requests)] if mixed_want
             else [max_new_tokens] * n_requests)
    queue = RequestQueue(engine.config.buckets)
    stop = threading.Event()
    worker = threading.Thread(target=serve_forever,
                              args=(engine, queue, stop), daemon=True)
    worker.start()
    gap = 1.0 / max(offered_rps, 1e-9)
    reqs = []
    t_start = time.perf_counter()
    for i, p in enumerate(prompts):
        # fixed offered load: submit on schedule, never "when ready"
        lag = t_start + i * gap - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        reqs.append(queue.submit(p))
    for r in reqs:
        r.result(timeout=600.0)
    stop.set()
    worker.join(timeout=60.0)

    lat_ms = np.array([(r.t_done - r.t_submit) * 1e3 for r in reqs])
    window_s = max(max(r.t_done for r in reqs) - t_start, 1e-9)
    recompiles = engine.compiles - compiles_warm
    row = {
        "mode": "serving",
        "model": model_name,
        "serve_dtype": serve_dtype,
        "buckets": list(engine.config.buckets),
        "rows": rows,
        "max_new_tokens": max_new_tokens,
        "n_requests": n_requests,
        "mixed_want": mixed_want,
        "offered_rps": offered_rps,
        "achieved_rps": round(n_requests / window_s, 2),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "mean_ms": round(float(lat_ms.mean()), 2),
        # only generating (causal-LM) engines produce tokens; a bert
        # embedding bench must not report a throughput for tokens that
        # were never generated. Under mixed_want only the WANTED tokens
        # count — the engine decoded max_new for everyone regardless
        **({"tokens_per_sec": round(sum(wants) / window_s, 1)}
           if engine.is_lm else {}),
        "compiles": engine.compiles,
        "recompiles_after_warmup": recompiles,
        "checkpoint": engine.checkpoint_info,
    }
    if serve_dtype == "int8":
        from ..serving.engine import int8_weight_bytes

        row["weight_bytes"] = int8_weight_bytes(engine._served)
    # per-arm contract verdict, exactly like the training rows: the decode
    # step of the largest bucket must keep its promises (no host
    # transfers, cache donated). Decode exists only for causal LMs; a
    # bert arm records the skip instead of a spurious error. Best-effort
    # — observability never kills a measurement.
    if engine.is_lm:
        try:
            from ..analysis.hlo_rules import (
                check_artifacts, serving_artifacts,
            )

            artifacts = serving_artifacts(
                engine, max(engine.config.buckets), name="bench-serving")
            findings = check_artifacts(artifacts)
            row["contracts"] = {
                "pass": not findings,
                "violations": [f.as_dict() for f in findings]}
        except Exception as e:  # noqa: BLE001
            row["contracts"] = {"pass": None,
                                "error": f"{type(e).__name__}: {e}"}
    else:
        row["contracts"] = {"pass": None,
                            "skipped": "no decode step (not a causal LM)"}
    return row


def measure_serving_continuous(model_name: str = "gpt2_124m",
                               n_requests: int = 24,
                               offered_rps: float = 16.0,
                               buckets: Sequence[int] = (8, 16),
                               rows: int = 8, max_new_tokens: int = 8,
                               kv_dtype: str = "fp32", page_size: int = 8,
                               mixed_want: bool = False,
                               replicas: int = 1,
                               kill_replica: bool = False,
                               temperature: float = 0.0, top_p: float = 1.0,
                               draft_model: Optional[str] = None,
                               draft_k: int = 4,
                               shared_frac: float = 0.0,
                               prefix_skip: bool = True,
                               devices: Optional[Sequence[jax.Device]] = None,
                               model_overrides: Optional[dict] = None,
                               ckpt_dir: Optional[str] = None, seed: int = 0,
                               optimizer: str = "auto",
                               momentum: float = 0.9,
                               weight_decay: float = 5e-4,
                               train_config=None,
                               mesh_spec: Optional[str] = None) -> dict:
    """Token-granular serving at fixed offered load — the continuous-
    batching row next to `measure_serving`'s iteration-granular one (same
    load schedule, same prompts, so the two rows are an apples-to-apples
    A/B on tok/s and tail latency).

    ``replicas`` in-process slot engines sit behind the stdlib `Router`
    (least-depth dispatch, resubmit-on-death); ``kill_replica=True``
    injects one replica death mid-load — the acceptance drill: every
    request still completes, the survivors absorb the resubmissions, and
    the compile census stays at warmup (``recompiles_after_warmup`` must
    be 0 across joins, leaves, AND the death). The row also carries the
    paged pool's HBM bytes against the dense fp32 baseline
    (``kv_bytes_ratio`` — the int8-paged >= 3x claim is a recorded
    number, not prose) and per-request TTFT percentiles (prefill emits
    token #0, so TTFT is an admission-latency instrument the
    iteration-granular engine cannot improve on).

    ``draft_model`` arms speculative decoding (fp32-only): each replica
    becomes a SpeculativeEngine + SpeculativeScheduler pair, and the row
    grows ``accept_ratio`` / ``accepted_per_verify`` / ``spec_rounds`` —
    the emitted streams stay BITWISE what the plain row emits (PARITY.md:
    acceptance is exact match), so the A/B is pure speed.
    ``shared_frac`` arms prefix-resident admission: that fraction of
    requests carry one identical page-aligned prompt, and the row grows
    ``prefill_skips`` / ``tail_resumes`` plus a warm/cold TTFT split —
    the zero-prefill admission claim as recorded numbers.
    """
    from ..serving.router import InProcessReplica, Router

    if draft_model is not None and kv_dtype != "fp32":
        # fail at the bench boundary with the bench's vocabulary, not
        # three layers down in SpeculativeEngine.__init__
        raise ValueError(
            f"--draft needs kv_dtype=fp32 (got {kv_dtype}): the verify "
            "window's in-view rows are fresh fp32 while the int8 path "
            "reads dequantized page bytes — the bitwise pin would break")
    devices = list(devices) if devices is not None else jax.devices()
    # Each replica gets its own DISJOINT device slice — the fleet
    # topology (replicas never share chips), and a hard requirement
    # in-process: the row-sharded decode step carries collectives, and
    # two schedulers racing collective programs over OVERLAPPING devices
    # deadlock in the CPU backend's rendezvous.
    per = len(devices) // replicas
    slices = ([devices[i * per:(i + 1) * per] for i in range(replicas)]
              if replicas > 1 and per >= 1 else [devices] * replicas)
    engines = []
    for i in range(replicas):
        common = dict(
            buckets=buckets, rows=rows, max_new_tokens=max_new_tokens,
            page_size=page_size, prefix_skip=prefix_skip,
            model_overrides=model_overrides, ckpt_dir=ckpt_dir, seed=seed,
            optimizer=optimizer, momentum=momentum,
            weight_decay=weight_decay, train_config=train_config,
            mesh_spec=mesh_spec)
        if draft_model is not None:
            # the draft inherits the target's overrides: a vocab override
            # must hit BOTH sides (acceptance compares token ids)
            engine, _ = build_spec_engine(
                slices[i], model_name, draft_model, draft_k=draft_k,
                draft_overrides=model_overrides, **common)
        else:
            engine, _ = build_slot_engine(
                slices[i], model_name, kv_dtype=kv_dtype, **common)
        engine.warmup()
        engines.append(engine)
    compiles_warm = [e.compiles for e in engines]

    rng = np.random.RandomState(seed)
    vocab = int(getattr(engines[0].model, "vocab_size", 0)) or 256
    lens = [int(rng.randint(1, max(engines[0].config.buckets) + 1))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, max(vocab, 2), n).astype(np.int32)
               for n in lens]
    # same rng order as measure_serving (lens, prompts, wants): identical
    # want stream on both sides of the A/B. HERE the wants are honored —
    # a slot retires at its want and the freed capacity admits the next
    # request, which is the continuous-batching win being measured.
    wants = ([int(rng.randint(1, max_new_tokens + 1))
              for _ in range(n_requests)] if mixed_want
             else [max_new_tokens] * n_requests)
    # prefix-resident arm: ``shared_frac`` of the requests carry ONE
    # identical page-aligned prompt. The first such request on a replica
    # prefills and registers the pages; every later one finds the whole
    # prefix resident and admits with ZERO prefill dispatch
    # (``prefill_skips`` is the census, the warm/cold TTFT split below is
    # the latency receipt). The shared indices are rng-spread over the
    # schedule so warm requests face the same queue depths cold ones do —
    # the extra draws come AFTER the lens/prompts/wants stream, so the
    # A/B against measure_serving stays intact.
    shared_idx: set = set()
    if shared_frac > 0:
        n_shared = int(round(shared_frac * n_requests))
        top = max(engines[0].config.buckets)
        shared_len = min(max(page_size, top // page_size * page_size), top)
        shared_prompt = rng.randint(0, max(vocab, 2),
                                    shared_len).astype(np.int32)
        if n_shared >= 1:
            shared_idx = set(
                int(j) for j in rng.choice(n_requests, size=n_shared,
                                           replace=False))
            for j in shared_idx:
                prompts[j] = shared_prompt

    router = Router([InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)])
    kill_at = n_requests // 3 if (kill_replica and replicas > 1) else None
    gap = 1.0 / max(offered_rps, 1e-9)
    reqs, sub_at = [], []
    t_start = time.perf_counter()
    for i, p in enumerate(prompts):
        lag = t_start + i * gap - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        sub_at.append(time.perf_counter())
        reqs.append(router.submit(p, max_new_tokens=wants[i],
                                  temperature=temperature, top_p=top_p))
        if kill_at is not None and i == kill_at:
            # the injected death: everything in flight on r0 fails with
            # ReplicaDead and the router resubmits it to the survivors
            router.replicas["r0"].kill()
    results = [r.result(timeout=600.0) for r in reqs]
    # True completion stamps: RouterRequest.t_done is the WORKER's
    # set_result time, not the moment this collection loop got around to
    # calling result(). Stamping here instead would charge every request
    # that finished during the pacing loop for the rest of the submission
    # window — at 20 rps x 32 requests that's seconds of phantom p99.
    done_at = [r.t_done for r in reqs]
    # "alive" means survived the RUN — snapshot before stop() tears the
    # scheduler threads down (after it, every replica reads unhealthy)
    alive = {name: rep.healthy() for name, rep in router.replicas.items()}
    router.stop()

    # submit -> completion wall latency AT THE ROUTER (a resubmitted
    # request's clock keeps running through its replica's death — the retry
    # is paid, not hidden), same stamps measure_serving reads (Request.t_done)
    lat_ms = np.array([(d - s) * 1e3 for s, d in zip(sub_at, done_at)])
    ttft_ms = np.array([res.queue_wait_s * 1e3 for res in results])
    window_s = max(max(done_at) - t_start, 1e-9)
    n_tokens = int(sum(res.tokens.size for res in results))
    per_replica = {}
    for name, rep in router.replicas.items():
        mine = [(reqs[i], lat_ms[i]) for i in range(n_requests)
                if reqs[i].replica_name == name]
        per_replica[name] = {
            "served": rep.scheduler.served,
            "alive": alive[name],
            **({"p50_ms": round(float(np.percentile(
                    [m for _, m in mine], 50)), 2),
                "p99_ms": round(float(np.percentile(
                    [m for _, m in mine], 99)), 2)} if mine else {}),
        }
    scheds = [rep.scheduler for rep in router.replicas.values()]
    engine = engines[0]
    row = {
        "mode": "serving_continuous",
        "granularity": "token",
        "model": model_name,
        "kv_dtype": kv_dtype,
        "page_size": page_size,
        "buckets": list(engine.config.buckets),
        "rows": rows,
        "max_new_tokens": max_new_tokens,
        "n_requests": n_requests,
        "mixed_want": mixed_want,
        "completed": len(results),
        "offered_rps": offered_rps,
        "achieved_rps": round(n_requests / window_s, 2),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "mean_ms": round(float(lat_ms.mean()), 2),
        "ttft_p50_ms": round(float(np.percentile(ttft_ms, 50)), 2),
        "ttft_p99_ms": round(float(np.percentile(ttft_ms, 99)), 2),
        "tokens": n_tokens,
        "tokens_per_sec": round(n_tokens / window_s, 1),
        "backend": jax.default_backend(),
        "compiles": sum(e.compiles for e in engines),
        "recompiles_after_warmup": sum(
            e.compiles - w for e, w in zip(engines, compiles_warm)),
        "replicas": replicas,
        "replica_deaths": sum(r.replica_deaths for r in reqs),
        "per_replica": per_replica,
        # the admission fast-path census: skips dispatched NO prefill,
        # resumes prefilled only the non-resident tail
        "prefix_skip": prefix_skip,
        "prefill_skips": sum(s.prefill_skips for s in scheds),
        "tail_resumes": sum(s.tail_resumes for s in scheds),
        "shared_frac": shared_frac,
        "draft": draft_model,
        # the HBM story: the paged (optionally int8) pool vs what the
        # dense fp32 cache would hold for the same rows at the top rung
        "paged_kv_bytes": engine.paged_bytes(),
        "dense_kv_bytes": engine.dense_baseline_bytes(),
        "checkpoint": engine.checkpoint_info,
    }
    row["kv_bytes_ratio"] = round(
        row["dense_kv_bytes"] / max(row["paged_kv_bytes"], 1), 2)
    if kv_dtype == "int8":
        # which int8 page codec the engine's programs were traced with
        from ..ops.quantize import resolve_fused

        row["kv_codec"] = ("pallas" if resolve_fused(engine._fused_quantize)
                           else "xla")
    if draft_model is not None:
        rounds = sum(s.spec_rounds for s in scheds)
        proposed = sum(s.spec_proposed for s in scheds)
        accepted = sum(s.spec_accepted for s in scheds)
        row["draft_k"] = draft_k
        row["spec_rounds"] = rounds
        # accept_ratio is the draft's hit rate; accepted_per_verify is
        # the speed-up currency — mean draft tokens banked per target
        # forward (the bonus token rides on top of it)
        row["accept_ratio"] = round(accepted / max(proposed, 1), 3)
        row["accepted_per_verify"] = round(accepted / max(rounds, 1), 2)
        row["draft_kv_bytes"] = engine.draft_bytes()
        if row["backend"] != "tpu":
            # same discipline as device_time_split's backend caveat:
            # a non-TPU row names its own limits instead of passing as
            # a chip measurement (experiments/results/README.md)
            row["caveat"] = (
                "cpu mesh: draft and verify thunks serialize (no ICI "
                "overlap), so tok/s understates the speculative win; "
                "random-init drafts pin accept_ratio near zero — only "
                "trained draft/target pairs on a chip measure real "
                "acceptance economics")
    if shared_idx:
        # warm = shared-prompt requests AFTER their replica's primer (the
        # one that paid the prefill and registered the pages); everything
        # else is the cold arm. Attribution is by final replica, so a
        # resubmitted primer stays a primer on the survivor.
        primers, seen = set(), set()
        for i in sorted(shared_idx):
            name = reqs[i].replica_name
            if name not in seen:
                seen.add(name)
                primers.add(i)
        warm = [float(ttft_ms[i]) for i in shared_idx if i not in primers]
        cold = [float(ttft_ms[i]) for i in range(n_requests)
                if i not in shared_idx or i in primers]
        if warm:
            row["ttft_warm_p50_ms"] = round(
                float(np.percentile(warm, 50)), 2)
        if cold:
            row["ttft_cold_p50_ms"] = round(
                float(np.percentile(cold, 50)), 2)
    try:
        from ..analysis.hlo_rules import (
            check_artifacts, paged_serving_artifacts,
        )

        findings = check_artifacts(
            paged_serving_artifacts(engine, name="bench-paged"))
        if draft_model is not None:
            from ..analysis.hlo_rules import spec_serving_artifacts

            findings.extend(check_artifacts(
                spec_serving_artifacts(engine, name="bench-spec")))
        row["contracts"] = {
            "pass": not findings,
            "violations": [f.as_dict() for f in findings]}
    except Exception as e:  # noqa: BLE001 - observability never kills a row
        row["contracts"] = {"pass": None,
                            "error": f"{type(e).__name__}: {e}"}
    return row


def measure_config(model_name: str, per_device_batch: int, steps: int,
                   bf16: bool, repeats: int = 3, seq_len: int = 512,
                   image_hw: int = 32, num_classes: int = 10,
                   devices: Optional[Sequence[jax.Device]] = None,
                   true_fp32: bool = True, min_window_s: float = 0.5,
                   zero1: bool = False,
                   grad_sync: Optional[dict] = None,
                   comm_trace: bool = False,
                   ckpt_ab: bool = False,
                   mesh_spec: Optional[str] = None) -> dict:
    """Full self-verifying measurement of one training config.

    Returns a dict with samples/s, FLOPs from XLA cost analysis AND the
    analytic jaxpr matmul/conv model, the detected chip peak, and mfu_pct.
    Raises flops.MeasurementError if the implied FLOP/s exceeds the chip peak
    (a broken measurement must never be reported as a result).

    When ``bf16=False`` and ``true_fp32``, the whole config is traced under
    ``jax.default_matmul_precision("highest")`` so the fp32 arm really runs
    fp32 matmul passes — without this, TPU "fp32" matmuls default to bf16 MXU
    passes and an AMP comparison measures nothing (the reference's AMP-vs-FP32
    experiment, /root/reference/README.md:31).

    Every result carries the gradient-sync bucket census of the measured
    executable (``grad_sync_census``: gradient-sized collective count +
    wire dtypes) so bench history can track overlap/bucketing efficiency
    across PRs; ``comm_trace=True`` additionally captures a short
    jax.profiler trace and records the exposed-comm fraction
    (``comm_overlap_split``) — best-effort, never a measurement failure.
    ``ckpt_ab=True`` additionally records ``save_blocked_ms`` — the
    sync-vs-async checkpoint blocked-time A/B (``checkpoint_save_ab``) on
    this config's real state.
    """
    import contextlib

    from . import flops as flops_mod

    devices = list(devices) if devices is not None else jax.devices()
    is_lm = is_lm_model(model_name)

    ctx = (jax.default_matmul_precision("highest")
           if (not bf16 and true_fp32) else contextlib.nullcontext())
    with ctx:
        trainer, state, mesh = build_trainer(
            devices, bf16, model_name, seq_len, image_hw, num_classes,
            zero1=zero1, grad_sync=grad_sync, mesh_spec=mesh_spec)
        batch, global_batch = make_synth_batch(
            mesh, model_name, per_device_batch, seq_len, image_hw,
            num_classes)

        key = jax.random.PRNGKey(0)
        # AOT-compile once: cost analysis reads the exact executable we time.
        lowered = trainer._train_step.lower(state, batch, key)
        compiled = lowered.compile()

        xla_flops = flops_mod.xla_flops_per_step(compiled)
        # fsdp_explicit states hold flat-sharded params — the analytic
        # model needs them back in model shapes (train.py does the same)
        analytic_fwd = flops_mod.jaxpr_matmul_flops(
            lambda s, b: trainer.task.loss_and_metrics(
                s, trainer._fsdp_unflatten(s.params) if trainer._fsdp
                else s.params, b, key, train=True)[0], state, batch)

        from ..parallel.grad_sync import emit_wire_accounting
        from ..parallel.mesh import batch_shard_count
        from .trace_analysis import grad_sync_census

        optimized_text = compiled.as_text()
        sync_census = grad_sync_census(optimized_text)
        contracts = _contract_check(trainer, state, optimized_text, lowered,
                                    zero1=zero1, grad_sync=grad_sync,
                                    per_device_batch=per_device_batch,
                                    seq_len=seq_len)
        # per-replica wire accounting of the configured sync mode (the
        # gather-int8 break-even and the multihop flat ~2 B/element as
        # recorded bench numbers). One call computes the row values AND
        # emits the telemetry counters (emit_wire_accounting is THE
        # emission site — the stream and the bench row read the same
        # numbers by construction; no-op stream-side when no recorder is
        # configured). The helper's conventions are the bucketed/
        # replicated reducer's; zero1's split wire (compressed scatter +
        # exact param gather) is out of its scope — omitted. The gather
        # split (ISSUE 7) is recorded for real fsdp trainers only:
        # state.params' flat leaves carry the same padded totals as the
        # model shapes.
        wire_bytes = None
        gather_bytes = None
        tp_bytes = None
        if not zero1:
            # explicit TP: the trainer assembles the (params, cfg) pair —
            # data-axis terms over the TP-LOCAL template, model-axis psum
            # bytes in their own counter row (axis="model")
            acct_params, acct_cfg = trainer.wire_accounting_inputs(
                state, grad_sync or {}, global_batch, seq_len)
            acct = emit_wire_accounting(
                acct_params, acct_cfg, batch_shard_count(trainer.mesh),
                model=model_name)
            wire_bytes = acct["wire_bytes_per_replica"]
            tp_bytes = acct.get("tp_psum_bytes_per_replica")
            if trainer._fsdp:
                gather_bytes = acct.get("fsdp_gather_bytes")

        exposed_comm_pct = None
        if comm_trace and len(devices) > 1:
            def _sacrificial():
                trainer_t, state_t, mesh_t = build_trainer(
                    devices, bf16, model_name, seq_len, image_hw,
                    num_classes, zero1=zero1, grad_sync=grad_sync,
                    mesh_spec=mesh_spec)
                batch_t, _ = make_synth_batch(
                    mesh_t, model_name, per_device_batch, seq_len, image_hw,
                    num_classes)
                return trainer_t, state_t, batch_t

            exposed_comm_pct = trace_exposed_comm(_sacrificial, key=key)

        # checkpoint blocked-time A/B BEFORE the timed windows: the step
        # donates the state buffers, so after timed_steps this state is
        # consumed — and the saves must not sit inside a timing window.
        save_blocked = checkpoint_save_ab(state) if ckpt_ab else None

        # the exposed-comm split rides the stream too (wire-byte counters
        # were already emitted by emit_wire_accounting above)
        if exposed_comm_pct is not None:
            from .. import telemetry
            telemetry.counter("exposed_comm_pct", exposed_comm_pct,
                              model=model_name)

        sps, samples_per_s = timed_steps(compiled, state, batch, global_batch,
                                         steps, repeats,
                                         min_window_s=min_window_s)

    n_dev = len(devices)
    peak = flops_mod.chip_peak_tflops(devices[0])
    # MFU numerator: the analytic matmul/conv model (FMA = 2 FLOPs — the
    # convention the chip-peak tables use). XLA's cost analysis is the
    # cross-check: it counts the compiled executable but uses FMA = 1 and
    # skips custom-call lowerings, so it should land within ~[0.25x, 1.5x]
    # of the analytic count, not be the headline.
    step_flops = 3.0 * analytic_fwd if analytic_fwd else xla_flops
    crosscheck_warning = None
    if xla_flops and analytic_fwd:
        ratio = xla_flops / (3.0 * analytic_fwd)
        if not (0.2 <= ratio <= 2.0):
            crosscheck_warning = (
                f"XLA cost analysis ({xla_flops:.3g}) vs analytic 3x-forward "
                f"({3.0 * analytic_fwd:.3g}) disagree by {ratio:.2f}x — one "
                "FLOPs instrument is miscounting this model")
    ctx_str = (f"{model_name} b={per_device_batch} on "
               f"{n_dev}x {devices[0].device_kind}")
    mfu = flops_mod.mfu_pct(step_flops, sps, peak * n_dev if peak else None)
    # Validate BOTH instruments: if either implies >peak the measurement is
    # broken, even when the headline instrument happens to undercount.
    warning = flops_mod.check_mfu(mfu, context=ctx_str)
    flops_mod.check_mfu(
        flops_mod.mfu_pct(xla_flops, sps, peak * n_dev if peak else None),
        context=ctx_str + " (XLA cost-analysis instrument)")

    result = {
        "model": model_name,
        "bf16": bf16,
        **({"zero1": True} if zero1 else {}),
        **({"grad_sync": grad_sync} if grad_sync else {}),
        "per_device_batch": per_device_batch,
        "global_batch": global_batch,
        "steps_per_sec": round(sps, 4),
        "samples_per_sec": round(samples_per_s, 2),
        "samples_per_sec_chip": round(samples_per_s / n_dev, 2),
        "flops_per_step_xla": xla_flops,
        "flops_per_step_analytic3x": 3.0 * analytic_fwd,
        "tflops_per_sec": (round(step_flops * sps / 1e12, 2)
                           if step_flops else None),
        "chip_peak_tflops_bf16": peak,
        "mfu_pct": round(mfu, 2) if mfu is not None else None,
        # overlap-efficiency instruments (ISSUE 2): the bucket census of
        # the measured executable, and (comm_trace) the exposed-comm split
        "grad_collectives": sync_census["n_collectives"],
        "grad_wire_dtypes": sync_census["wire_dtypes"],
        **({"wire_bytes_per_replica": wire_bytes}
           if wire_bytes is not None else {}),
        **({"fsdp_gather_bytes": gather_bytes}
           if gather_bytes is not None else {}),
        **({"tp_psum_bytes_per_replica": tp_bytes}
           if tp_bytes is not None else {}),
        **({"mesh_spec": mesh_spec} if mesh_spec else {}),
        # per-arm parallelism-contract verdict (analysis/hlo_rules.py):
        # bench history records whether the measured executable kept its
        # collective/wire/donation promises, not just how fast it ran
        "contracts": contracts,
    }
    if save_blocked is not None:
        # the async-checkpointing instrument (ISSUE 6): ms the train loop
        # spends blocked per save, sync vs snapshot-then-write
        result["save_blocked_ms"] = save_blocked
    if exposed_comm_pct is not None:
        result["exposed_comm_pct"] = exposed_comm_pct
    if is_lm:
        result["seq_len"] = seq_len
        result["tokens_per_sec"] = round(samples_per_s * seq_len, 1)
    else:
        result["image_hw"] = image_hw
    if warning:
        result["mfu_warning"] = warning
    if crosscheck_warning:
        result["flops_crosscheck_warning"] = crosscheck_warning
    return result

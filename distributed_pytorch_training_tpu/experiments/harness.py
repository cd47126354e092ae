"""The measurement recipe of experiments/scaling.py.

One copy of it (build trainer -> synthetic device batch -> warmup -> timed
windows) so the experiment tables stay comparable with each other — the
throughput-meter role of the reference
(/root/reference/train_ddp.py:224-243), done without host syncs in the loop.
Nothing outside `experiments/` imports this module (the
`experiments-is-a-leaf` AST rule).

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
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import is_lm_model, lm_vocab


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
    """Model-family dispatch of the experiment drivers — the same
    `--model` string must measure the same config in every table.
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
    (`telemetry.trace_analysis.comm_overlap_split` over a short jax.profiler
    capture). ``build_fn() -> (trainer, state, batch)`` must build a
    SACRIFICIAL trainer/state: the jitted step donates its input state, so
    a capture that dies mid-step consumes those buffers — they must never
    be the ones a timed run still needs. Returns the percentage, or None
    on any failure (the number is an observability nicety, never worth
    failing a measurement for).
    """
    import tempfile

    from ..telemetry.trace_analysis import (
        capture_step_trace, comm_overlap_split,
    )

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

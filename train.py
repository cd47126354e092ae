"""TPU-native distributed training entry point.

The orchestration layer — maps `main()` of the reference
(/root/reference/train_ddp.py:314-390) onto the TPU-native stack:

    reference                          here
    ---------                          ----
    parse_args (:315)                  utils.config.parse_args (same flags)
    setup_distributed NCCL (:318)      runtime.setup_distributed + build_mesh
    set_seed(seed+rank) (:319)         runtime.set_seed (same seed+rank rule for
                                       host RNG); device randomness from one
                                       shared PRNGKey(seed) on the global batch
    get_dataloaders (:332)             data.ShardedLoader (pad+mask, prefetch)
    build_model + DDP wrap (:335-336)  models.get_model + shard_pytree
    criterion/optimizer/scaler (:338)  training.make_optimizer (no scaler: bf16)
    epoch loop + CSV (:356-384)        identical stdout/CSV contract
    cleanup (:386)                     runtime.cleanup_distributed

Run: python train.py --epochs 2 --synthetic        (single chip or CPU)
"""

from __future__ import annotations

import sys
from pathlib import Path

# Allow `python train.py` from anywhere.
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_training_tpu import native
from distributed_pytorch_training_tpu.data import (
    CIFAR10_MEAN, CIFAR10_STD, IMAGENET_MEAN, IMAGENET_STD,
    ShardedLoader, get_dataset,
)
from distributed_pytorch_training_tpu.models import get_model
from distributed_pytorch_training_tpu.parallel import MeshSpec, barrier, build_mesh
from distributed_pytorch_training_tpu.parallel.mesh import (
    batch_shard_count, validate_mesh_usage,
)
from distributed_pytorch_training_tpu.runtime import (
    cleanup_distributed, enable_persistent_compile_cache, require_backend,
    set_seed, setup_distributed,
)
from distributed_pytorch_training_tpu.training import (
    TrainConfig, Trainer, make_optimizer, make_schedule,
)
from distributed_pytorch_training_tpu.training.tasks import ImageClassificationTask
from distributed_pytorch_training_tpu.utils import MetricsCSV, log_main, parse_args
from distributed_pytorch_training_tpu.utils.config import parse_model_overrides

IMAGE_STATS = {
    "cifar10": (CIFAR10_MEAN, CIFAR10_STD),
    "imagenet": (IMAGENET_MEAN, IMAGENET_STD),
}


def samples_per_step_list(n: int, global_batch: int, steps: int, drop_last: bool):
    """Host-known global sample count per step (for the throughput meter,
    ref :226 counts `batch_size * world_size` per step)."""
    counts = [global_batch] * steps
    if not drop_last and steps and n % global_batch:
        counts[-1] = n % global_batch
    return counts


def resolve_attention(requested: str, is_lm: bool, backend: str,
                      n_pipe: int, seq_len: int = 512) -> str:
    """Resolve ``--attention auto`` to the benched fast path: Pallas flash
    kernels on TPU (42% over the einsum for GPT-2 @ S=1024 on v5e); the XLA
    einsum elsewhere (CPU would run pallas in interpreter mode), inside
    pipeline stages (attention is a per-stage concern), for image models
    (no attention), and for sequence lengths the kernel has no usable block
    for — auto must never turn a previously-working default run into an
    error (an *explicit* --attention flash still fails loudly there)."""
    if requested != "auto":
        return requested
    from distributed_pytorch_training_tpu.ops.flash_attention import (
        flash_backend_supported, flash_supports_length,
    )

    return ("flash" if is_lm and flash_backend_supported(backend)
            and n_pipe == 1 and flash_supports_length(seq_len) else "xla")


def main(argv=None):
    args = parse_args(argv)
    if args.resume and not args.checkpoint_dir:
        raise ValueError("--resume requires --checkpoint-dir")
    if args.max_restarts > 0 and not args.checkpoint_dir:
        raise ValueError("--max-restarts requires --checkpoint-dir (the "
                         "supervisor restarts FROM checkpoints)")
    if args.max_restarts < 0:
        raise ValueError(f"--max-restarts must be >= 0, got "
                         f"{args.max_restarts}")
    if args.autopilot and args.max_restarts <= 0:
        raise ValueError("--autopilot requires --max-restarts (the "
                         "Supervisor owns the segment boundaries every "
                         "control decision is anchored at)")
    if args.autopilot and args.no_telemetry:
        raise ValueError("--autopilot requires telemetry: the control "
                         "plane's inputs AND its decision log are both "
                         "the stream (drop --no-telemetry)")
    if args.autopilot_tune and not args.autopilot:
        raise ValueError("--autopilot-tune requires --autopilot")

    # Preemption guard first: a SIGTERM during data load / compile must also
    # lead to a graceful stop, not a mid-init kill (preemption.py docstring).
    from distributed_pytorch_training_tpu.training.preemption import (
        PreemptionGuard,
    )
    from distributed_pytorch_training_tpu import telemetry

    guard = PreemptionGuard.install()
    try:
        _run(args, guard)
    except BaseException as e:
        # The flight recorder's train.py exit path: ANY abnormal exit
        # (unhandled exception, sys.exit) leaves a postmortem
        # flight_<ts>.json with the last events + cause. Done here rather
        # than via sys.excepthook so it runs BEFORE the finally below can
        # tear telemetry down. Clean SystemExit(0) is not abnormal.
        if not (isinstance(e, SystemExit) and e.code in (0, None)):
            telemetry.flush_flight(
                cause=f"{type(e).__name__}: {e}",
                detail="train.py abnormal exit",
                rc=e.code if isinstance(e, SystemExit) else 1)
        raise
    finally:
        # The hard-exit deadline must not outlive this invocation: an
        # embedder (sweep / notebook) that catches a failure mid-preemption
        # would otherwise be os._exit(143)-killed up to `grace` seconds
        # later with no warning. Normal completion disarms after cleanup
        # inside _run; this is the exception path.
        guard.disarm()
        # endpoint down before the stream closes — guarded on the module
        # actually having loaded, so the metrics-off path never imports
        # metrics_http at all (its zero-cost-when-off contract)
        if "distributed_pytorch_training_tpu.telemetry.metrics_http" \
                in sys.modules:
            telemetry.stop_metrics_server()
        telemetry.reset()  # close the JSONL (fsync) and drop the global


def _log_save_blocked(ckpt) -> None:
    """The save_blocked_ms instrument (training/checkpoint.py): how long
    the train loop actually stalled on checkpointing — under async saves
    this collapses to ~the device→host snapshot cost."""
    if ckpt is None or not ckpt.saves_started:
        return
    log_main(f"Checkpointing: blocked {ckpt.save_blocked_ms:.0f}ms total "
             f"(snapshot {ckpt.snapshot_ms:.0f}ms) across "
             f"{ckpt.saves_started} save(s)")


def _run(args, guard):
    Path(args.output_dir).mkdir(parents=True, exist_ok=True)  # ref :316

    # Deterministic fault injection (resilience/faults.py): armed ONLY when
    # --chaos is given — every injection hook below is None otherwise, so
    # the un-instrumented hot path is untouched.
    chaos = None
    if args.chaos:
        from distributed_pytorch_training_tpu.resilience.faults import (
            FaultInjector, FaultPlan,
        )
        chaos = FaultInjector(FaultPlan.parse(args.chaos), log=log_main)
        log_main(f"CHAOS: fault plan armed: {args.chaos}")

    ctx = setup_distributed()  # ref :318
    backend = require_backend()  # CPU only when JAX_PLATFORMS=cpu says so
    # Structured run telemetry (telemetry/): per-rank JSONL stream in the
    # output dir + the in-memory ring the flight recorder flushes on
    # abnormal exits. Rank 0 always streams (the historical
    # telemetry_rank0.jsonl, unchanged disk cost); other ranks stream
    # only under --telemetry-all-ranks / DPT_TELEMETRY_ALL_RANKS — the
    # per-rank inputs `telemetry aggregate` merges. Host-side only —
    # PARITY.md pins that the lowered HLO is identical with telemetry on
    # or off, live /metrics surface included.
    from distributed_pytorch_training_tpu import telemetry
    tele_rank = telemetry.rank_identity(ctx.process_index)
    if not args.no_telemetry and telemetry.should_stream(
            tele_rank, args.telemetry_all_ranks):
        telemetry.configure(
            str(Path(args.output_dir)
                / telemetry.stream_filename(tele_rank)),
            rank=tele_rank, gen=telemetry.generation_identity(),
            meta={"entry": "train.py", "model": args.model,
                  "mesh": args.mesh, "chaos": args.chaos or ""})
    # Live metrics endpoint (telemetry/metrics_http.py): a stdlib-only
    # background HTTP thread serving Prometheus /metrics + step-fence
    # /healthz, fed by an observer on the recorder. Off (the default)
    # resolves port 0 and starts ZERO threads.
    metrics_port = telemetry.resolve_metrics_port(args.metrics_port,
                                                  tele_rank)
    if metrics_port and telemetry.is_configured():
        # a bind failure returns None (stderr-noted) instead of raising:
        # the live surface must never take the training run down
        if telemetry.start_metrics_server(
                metrics_port, telemetry.get(),
                backend=backend) is not None:
            log_main(f"Telemetry: serving /metrics + /healthz on "
                     f":{metrics_port}")
    set_seed(args.seed, ctx.process_index)  # seed+rank rule, ref :76-78/:319
    mesh_spec = MeshSpec.parse(args.mesh)
    if args.slices > 1:
        # --slices folds the slow-tier/outer axis into the mesh spec; an
        # explicit slice=... in --mesh must agree (two sources of truth
        # silently disagreeing is how wrong topologies ship)
        import dataclasses as _dc
        if mesh_spec.slice not in (1, args.slices):
            raise ValueError(
                f"--slices {args.slices} conflicts with --mesh "
                f"{args.mesh!r} (slice={mesh_spec.slice}); set the slice "
                "factor in one place")
        mesh_spec = _dc.replace(mesh_spec, slice=args.slices)
    mesh = build_mesh(mesh_spec)
    n_batch_shards = batch_shard_count(mesh)
    global_batch = args.batch_size * n_batch_shards
    # the /metrics world-size gauge (elastic relaunches land at different
    # worlds — the scrape shows which one this process actually got)
    telemetry.gauge("world_size", mesh.size)
    # Warm-restart compilation cache: reuse compiles across CLI invocations
    # AND across supervisor/elastic restarts (the TPU analogue of the
    # reference's cudnn.benchmark=True autotune persistence, ref :329).
    # runtime.dist owns where it lives; "auto" refuses XLA:CPU.
    enable_persistent_compile_cache()

    # Banner ≙ ref :326-327 ("Using device: ..., world_size=..., amp=...").
    dev0 = mesh.devices.flat[0]
    log_main(
        f"Using device: {dev0.platform}:{dev0.id} "
        f"(mesh {dict(mesh.shape)}), world_size={mesh.size}, amp={args.amp}"
    )

    compute_dtype = jnp.bfloat16 if args.amp else jnp.float32
    overrides = parse_model_overrides(args.model_overrides)
    is_lm = args.model.startswith(("gpt2", "bert", "qwen3_next"))
    # `family` picks the token data and the task: every causal LM is "gpt2"
    family = "bert" if args.model.startswith("bert") else "gpt2"
    resolved_seq = args.seq_len or (512 if family == "bert" else 1024)
    attention = resolve_attention(args.attention, is_lm,
                                  backend, mesh.shape["pipe"], resolved_seq)
    # What the two "auto" kernel switches resolved to, said once in the
    # banner and once in the stream: a quiet fall to the einsum or to the
    # XLA-composed codec must be visible in the run's own record.
    from distributed_pytorch_training_tpu.ops.quantize import resolve_fused

    fused_quantize = {"auto": None, "on": True, "off": False}[
        args.fused_quantize]
    fused_resolved = resolve_fused(fused_quantize)
    log_main(f"Kernels: attention={attention} (--attention "
             f"{args.attention}), fused_quantize="
             f"{'pallas' if fused_resolved else 'xla'} "
             f"(--fused-quantize {args.fused_quantize}); host data path: "
             f"{native.describe()}")
    telemetry.emit("event", "kernel_paths", attention=attention,
                   attention_requested=args.attention,
                   fused_quantize=fused_resolved,
                   fused_quantize_requested=args.fused_quantize)
    if args.download and (is_lm or args.dataset.lower() != "cifar10"):
        # never let a user believe they trained on fetched data when the
        # flag was silently inapplicable
        raise ValueError(
            "--download supports --dataset cifar10 (the reference's "
            "workload); LM/imagenet configs read preprocessed data from "
            "--data-dir or use --synthetic")

    # Data (ref :332). Process 0 prepares first (it may extract an archive on
    # a shared filesystem); others wait at the barrier, then read — the exact
    # rank-0-download + barrier gating of the reference (ref :103-112).
    if is_lm:
        from distributed_pytorch_training_tpu.data.text import (
            TokenLoader, get_token_dataset,
        )

        seq_len = resolved_seq

        def _load_datasets():
            train_ds = get_token_dataset(family, seq_len, args.data_dir,
                                         train=True,
                                         synthetic_size=args.synthetic_size,
                                         seed=args.seed)
            val_ds = get_token_dataset(family, seq_len, args.data_dir,
                                       train=False,
                                       synthetic_size=(args.synthetic_size or 0) // 5 or None,
                                       seed=args.seed)
            return train_ds, val_ds
    else:
        def _load_datasets():
            # download only on process 0 (ref `download=(rank==0)`, :106);
            # non-main processes reach here after the barrier, files on disk
            train_ds = get_dataset(args.dataset, args.data_dir, train=True,
                                   synthetic=args.synthetic,
                                   synthetic_size=args.synthetic_size, seed=args.seed,
                                   download=args.download and ctx.is_main)
            val_ds = get_dataset(args.dataset, args.data_dir, train=False,
                                 synthetic=args.synthetic or train_ds.synthetic,
                                 synthetic_size=(args.synthetic_size or 0) // 5 or None,
                                 seed=args.seed)
            return train_ds, val_ds

    if ctx.is_main:
        train_ds, val_ds = _load_datasets()
        barrier("data_ready")
    else:
        barrier("data_ready")
        train_ds, val_ds = _load_datasets()
    if train_ds.synthetic:
        log_main(f"NOTE: using synthetic data ({train_ds.name}, n={len(train_ds)})")

    # Loaders + model + task (ref :131-148, :335-338).
    pipelined = False
    if is_lm:
        from distributed_pytorch_training_tpu.training.tasks import (
            LanguageModelingTask, MaskedLMTask, MoeLanguageModelingTask,
        )

        train_loader = TokenLoader(train_ds, mesh, args.batch_size, shuffle=True,
                                   seed=args.seed, drop_last=args.drop_last,
                                   fault_hook=(chaos.on_loader_batch
                                               if chaos else None))
        val_loader = TokenLoader(val_ds, mesh, args.batch_size, shuffle=False,
                                 seed=args.seed)
        lm_kwargs = dict(dtype=compute_dtype, remat=args.remat)
        if mesh.shape["model"] > 1:
            # Megatron-style vocab padding: GPT-2's 50257 (and BERT's 30522
            # beyond model=2) is indivisible by TP degrees, so without this
            # the (vocab, d) embedding — the largest param — would silently
            # replicate over `model` (VERDICT r4 weak #4). lcm(128, tp) keeps
            # the padded vocab lane-aligned AND divisible by the TP degree.
            import math

            lm_kwargs["pad_vocab_to_multiple_of"] = math.lcm(
                128, mesh.shape["model"])
        lm_kwargs.update(overrides)
        if attention != "xla":
            if family == "bert" and attention in ("ring", "ulysses"):
                raise ValueError("--attention ring/ulysses is causal-only; "
                                 "bert_base uses the XLA or flash path")
            if attention == "flash":
                from distributed_pytorch_training_tpu.ops import (
                    make_flash_attention_fn,
                )
                # BERT is bidirectional: flash with causal=False. Legal
                # because MaskedLMTask feeds no padding mask (the kernel
                # path owns the attention structure).
                lm_kwargs["attention_fn"] = make_flash_attention_fn(
                    causal=family != "bert", mesh=mesh)
            elif attention == "ulysses":
                from distributed_pytorch_training_tpu.ops import (
                    make_ulysses_attention_fn,
                )
                lm_kwargs["attention_fn"] = make_ulysses_attention_fn(
                    mesh, causal=True)
            else:  # ring
                from distributed_pytorch_training_tpu.ops import (
                    make_ring_attention_fn,
                )
                lm_kwargs["attention_fn"] = make_ring_attention_fn(
                    mesh, causal=True)
        n_pipe = mesh.shape["pipe"]
        if n_pipe > 1 and family == "gpt2" and "moe" not in args.model:
            # GPipe path: blocks stage-stacked over the `pipe` axis
            # (models/gpt2_pipe.py). Attention runs inside the stages via
            # the XLA path; kernel attention is a per-stage concern.
            if attention != "xla":
                raise ValueError("--mesh pipe>1 uses the XLA attention path "
                                 "inside pipeline stages; drop --attention")
            from distributed_pytorch_training_tpu.models.gpt2_pipe import (
                GPT2PipeLMHead,
            )

            pipelined = True
            # config holder for the named size (+ any CLI shrink overrides)
            cfg = get_model(args.model, **overrides)
            pipe_kwargs = dict(
                mesh=mesh, num_microbatches=args.microbatches,
                vocab_size=cfg.vocab_size, hidden_dim=cfg.hidden_dim,
                depth=cfg.depth, num_heads=cfg.num_heads,
                max_position=max(cfg.max_position, seq_len),
                dtype=compute_dtype, remat=args.remat)
            # overrides of pipe-model fields beyond the explicit list above
            # (e.g. layernorm_epsilon) must not be silently dropped
            import dataclasses as _dc

            pipe_fields = {f.name for f in _dc.fields(GPT2PipeLMHead)}
            pipe_kwargs.update({k: v for k, v in overrides.items()
                                if k in pipe_fields and k not in pipe_kwargs})
            model = GPT2PipeLMHead(**pipe_kwargs)
        else:
            model = get_model(args.model, **lm_kwargs)
        model_vocab = getattr(model, "vocab_size", None)
        if model_vocab and model_vocab < train_ds.vocab_size:
            # A model vocab shrunk below the dataset's stamped vocab can
            # index past the embedding, and out-of-range jnp gathers fill
            # with NaN instead of raising — a run that trains straight to
            # NaN loss with no hint. Scan the ids actually present (only in
            # this override case — the scan is the price of the shrink, not
            # of every startup): a byte-tokenized corpus loads under the
            # gpt2 stamp (50257) yet only uses ids < 256, which is fine.
            for split_ds, split in ((train_ds, "train"), (val_ds, "val")):
                max_id = int(split_ds.tokens.max()) if len(split_ds) else -1
                if max_id >= model_vocab:
                    raise ValueError(
                        f"{split} dataset {split_ds.name} contains token id "
                        f"{max_id}, which exceeds the model's vocab_size "
                        f"({model_vocab}): such ids index past the "
                        "embedding, which JAX fills with NaN. Align "
                        "--model-overrides vocab_size with the data (byte "
                        f"corpora: 256; full {family} tokens: "
                        f"{train_ds.vocab_size}).")
        if family == "bert":
            # The masking recipe samples replacement ids and inserts [MASK]:
            # both must stay inside the (possibly shrunk) embedding, or the
            # task itself manufactures the out-of-range ids the guard above
            # just excluded from the data.
            bert_vocab = min(model_vocab or train_ds.vocab_size,
                             train_ds.vocab_size)
            task = MaskedLMTask(vocab_size=bert_vocab,
                                compute_dtype=compute_dtype)
            if task.mask_token_id >= bert_vocab:
                raise ValueError(
                    f"vocab_size {bert_vocab} does not contain the [MASK] "
                    f"token id {task.mask_token_id}; use a vocab of at "
                    f"least {task.mask_token_id + 1}")
        elif "moe" in args.model:
            # MoE models add the Switch router load-balancing loss
            task = MoeLanguageModelingTask(compute_dtype=compute_dtype)
        else:
            task = LanguageModelingTask(compute_dtype=compute_dtype)
        sample_input = np.zeros((1, seq_len), np.int32)
    else:
        train_loader = ShardedLoader(train_ds, mesh, args.batch_size, shuffle=True,
                                     seed=args.seed, drop_last=args.drop_last,
                                     prefetch=max(2, args.workers // 2),
                                     fault_hook=(chaos.on_loader_batch
                                                 if chaos else None))
        val_loader = ShardedLoader(val_ds, mesh, args.batch_size, shuffle=False,
                                   seed=args.seed, prefetch=2)
        mean, std = IMAGE_STATS[args.dataset.lower()]
        model_kwargs = dict(num_classes=train_ds.num_classes, dtype=compute_dtype)
        model_kwargs.update(overrides)
        if args.model.startswith("resnet"):
            # explicit --model-overrides wins over the dedicated flag
            model_kwargs.setdefault("cifar_stem", args.cifar_stem)
            if args.remat:
                raise ValueError("--remat applies to transformer models "
                                 "(vit/bert/gpt2); ResNets are activation-light")
        elif args.remat:
            model_kwargs["remat"] = True
        model = get_model(args.model, **model_kwargs)
        task = ImageClassificationTask(mean=mean, std=std,
                                       augment=not args.no_augment,
                                       compute_dtype=compute_dtype)
        h, w = train_ds.images.shape[1:3]
        sample_input = np.zeros((1, h, w, 3), np.float32)

    # Optimizer (ref :339-344; schedule is an extension, ref is constant-LR).
    steps_per_epoch = len(train_loader)
    schedule = make_schedule(args.schedule, args.lr,
                             total_steps=steps_per_epoch * args.epochs,
                             warmup_steps=args.warmup_steps)
    from distributed_pytorch_training_tpu.parallel.mesh import BATCH_AXES

    # zero1/fsdp on a single batch shard run the replicated (non-shard_map)
    # update, where a shard-axes psum would hit unbound axis names — the
    # clip's shard awareness must follow the same passthrough condition.
    # The zero1 x model-axis composition runs the GSPMD update on GLOBAL
    # flat arrays (training/loop.py), so its clip stays stock too.
    model_axis = mesh.shape.get("model", 1) > 1
    # Explicit TP x FSDP (ISSUE 13): the update shards over
    # (model,) + batch axes — the clip's norm psum must ride all three,
    # with model-replicated leaves down-weighted 1/M (they are stored once
    # per model shard; parallel/sharding.tp_clip_weights).
    explicit_tp = args.fsdp_explicit and model_axis
    sharded_update = ((args.zero1 and not model_axis) or args.fsdp_explicit) \
        and (n_batch_shards > 1 or explicit_tp)
    shard_axes = None
    clip_weights = None
    rules = (type(model).partition_rules()
             if hasattr(type(model), "partition_rules") else None)
    if sharded_update:
        from distributed_pytorch_training_tpu.parallel.mesh import MODEL
        shard_axes = ((MODEL,) + BATCH_AXES) if explicit_tp else BATCH_AXES
    if explicit_tp and rules is not None:
        from distributed_pytorch_training_tpu.parallel.sharding import (
            tp_clip_weights_for_model,
        )
        clip_weights = tp_clip_weights_for_model(
            model, rules, mesh.shape["model"],
            np.zeros((mesh.shape["model"],) + tuple(sample_input.shape[1:]),
                     np.asarray(sample_input).dtype))
    tx = make_optimizer(args.optimizer, schedule, momentum=args.momentum,
                        weight_decay=args.weight_decay,
                        shard_axes=shard_axes,
                        clip_leaf_weights=clip_weights)
    # Refuse silently-wasted devices: every mesh axis > 1 must be one the
    # selected model/attention combination can actually use.
    validate_mesh_usage(mesh, rules=rules,
                        attention=attention if is_lm else "xla",
                        is_moe="moe" in args.model, pipelined=pipelined)

    trainer = Trainer(task, mesh,
                      TrainConfig(per_device_batch=args.batch_size,
                                  print_freq=args.print_freq, seed=args.seed,
                                  bf16=args.amp, grad_accum=args.grad_accum,
                                  zero1=args.zero1,
                                  fsdp_explicit=args.fsdp_explicit,
                                  bucket_cap_mb=args.bucket_cap_mb,
                                  wire_dtype=args.wire_dtype,
                                  slice_axis=args.slice_axis,
                                  overlap_grad_sync=not
                                  args.no_overlap_grad_sync,
                                  fused_quantize=fused_quantize),
                      rules=rules)
    if explicit_tp:
        log_main(f"TP x FSDP (explicit): megatron tensor parallelism over "
                 f"model={mesh.shape['model']} inside the FSDP shard_map "
                 f"(one psum per residual join); params + moments "
                 f"flat-sharded 1/{n_batch_shards * mesh.shape['model']} "
                 "at rest for TP-split tensors; per-layer gathers/scatters "
                 "ride the data axes over each shard's 1/"
                 f"{mesh.shape['model']} slice"
                 + (f"; {args.wire_dtype} wire" if args.wire_dtype != "fp32"
                    else ""))
    elif args.fsdp_explicit and n_batch_shards > 1:
        log_main(f"FSDP (explicit): params + moments flat-sharded "
                 f"{n_batch_shards}-way at rest; per-layer just-in-time "
                 "param gathers, gradients reduce-scattered into the shard "
                 "layout"
                 + (f"; {args.wire_dtype} wire" if args.wire_dtype != "fp32"
                    else ""))
    elif args.zero1 and n_batch_shards > 1:
        log_main(f"ZeRO-1: weight update sharded {n_batch_shards}-way over "
                 "the batch axes ("
                 + ("per-leaf GSPMD update — model-axis mesh"
                    if trainer._zero1_gspmd else
                    "reduce-scatter grads -> 1/N optimizer update -> "
                    "all-gather params")
                 + (f"; {args.wire_dtype} gradient wire"
                    if args.wire_dtype != "fp32" else "") + ")")
    elif trainer._grad_sync:
        log_main(f"Gradient sync: explicit bucketed reducer over "
                 f"{n_batch_shards} shards — bucket_cap_mb="
                 f"{args.bucket_cap_mb or 'inf (one bucket)'}, "
                 f"wire={args.wire_dtype}, overlap="
                 f"{'off' if args.no_overlap_grad_sync else 'on'}")
    if trainer._hier is not None:
        h = trainer._hier
        log_main(f"Two-tier wire (int8_hier): {h.n_slices} slices x "
                 f"{h.n_inner} replicas/slice — exact fp32 reduce-scatter "
                 f"inside the slice, s8+EF exchange across "
                 f"{h.slice_axis!r} (~2 B/element per slice on the slow "
                 "tier, slice-count independent)")

    if not args.no_telemetry:
        # anomaly watchdog fed by train_epoch's host-side timings + the
        # print-boundary losses; abort hook off unless asked (with
        # --max-restarts an abort is a restartable failure: restore+replay).
        # Detector knobs honor DPT_WATCHDOG_* env overrides — how an
        # orchestrator tunes warm-up/floors on children it cannot pass
        # flags to (the fleet's anomaly-capture story on short runs).
        from distributed_pytorch_training_tpu.telemetry.watchdog import (
            kwargs_from_env,
        )
        trainer.watchdog = telemetry.AnomalyWatchdog(
            abort=args.telemetry_abort, **kwargs_from_env())

    state = trainer.init_state(model, sample_input, tx,
                               jax.random.PRNGKey(args.seed))
    n_params = state.param_count()
    if trainer._fsdp and trainer._fsdp_template is not None:
        # report the model-shaped count, not the flat-padded at-rest sizes
        n_params = sum(
            int(np.prod(t.shape) or 1) for t in
            jax.tree_util.tree_leaves(trainer._fsdp_template))
    pad_extra = getattr(model, "vocab_pad_params", 0)
    if pad_extra:
        # Report the HF-exact count; padding rows are a TP layout artifact.
        log_main(f"Model {args.model}: {n_params - pad_extra:,} params "
                 f"(+{pad_extra:,} vocab-pad rows for TP)")
    else:
        log_main(f"Model {args.model}: {n_params:,} params")
    if trainer._grad_sync:
        from distributed_pytorch_training_tpu.parallel.grad_sync import (
            build_bucket_plan,
        )
        plan = build_bucket_plan(state.params, args.bucket_cap_mb)
        log_main(f"Gradient sync: {plan.n_buckets} bucket(s) over "
                 f"{plan.total_bytes / 2 ** 20:.1f} MB of fp32 gradient")
    if trainer._fsdp and trainer._fsdp_plan is not None:
        lp = trainer._fsdp_plan
        mb = lp.total_padded * 4 / 2 ** 20
        log_main(f"FSDP plan: {len(lp.groups)} layer gather group(s), "
                 f"{mb:.1f} MB padded fp32 params "
                 f"({mb / n_batch_shards:.1f} MB/replica at rest)")
    if telemetry.is_configured() and n_batch_shards > 1 and not args.zero1:
        # setup-time wire accounting counters (grad_sync/FSDP plans) —
        # the per-tier byte substrate `telemetry summary` reports.
        # zero1's split wire (compressed scatter + exact param gather) is
        # outside wire_bytes_for_config's conventions — omitted, exactly
        # as the bench harness omits it
        from distributed_pytorch_training_tpu.parallel.grad_sync import (
            emit_wire_accounting,
        )
        # fsdp states hold flat-sharded leaves; their padded totals match
        # the model-shaped ones (the harness records them the same way).
        # Explicit TP: the data-axis terms come from the TP-LOCAL template
        # (each model shard gathers/scatters its slice only — the 1/M
        # reduction), and the model-axis psum bytes land in their own
        # tier row (axis="model") so `telemetry summary` splits them.
        acct_params, acct_cfg = trainer.wire_accounting_inputs(
            state, dict(wire_dtype=args.wire_dtype,
                        bucket_cap_mb=args.bucket_cap_mb,
                        fsdp_explicit=args.fsdp_explicit,
                        slices=(trainer._hier.n_slices
                                if trainer._hier is not None else 1)),
            global_batch, seq_len if is_lm else 0)
        emit_wire_accounting(acct_params, acct_cfg, n_batch_shards)

    # MFU in the step log (TPU only — needs a known chip peak): analytic
    # matmul/conv FLOPs of one train step, traced once on a peeked batch.
    from distributed_pytorch_training_tpu.experiments import flops as flops_mod

    peak = flops_mod.chip_peak_tflops(dev0)
    if peak:
        try:
            peek = next(iter(train_loader.epoch(0)))
            fwd = flops_mod.jaxpr_matmul_flops(
                lambda s, b: task.loss_and_metrics(
                    s, trainer._fsdp_unflatten(s.params) if trainer._fsdp
                    else s.params, b, jax.random.PRNGKey(0), train=True)[0],
                state, peek)
            trainer.set_mfu_reference(3.0 * fwd / global_batch,
                                      peak * 1e12 * mesh.size)
        except Exception as e:  # MFU is a log nicety, never a crash
            log_main(f"NOTE: MFU logging disabled ({e})")

    # Checkpointing (extension; the reference has none — SURVEY.md §5).
    # Step-granular: labels are epoch * steps_per_epoch + step, so a
    # mid-epoch preemption save sorts between the epoch boundaries and
    # resume continues at that exact step (deterministic sampler).
    ckpt = None
    start_epoch = start_step = 0
    if args.checkpoint_dir:
        from distributed_pytorch_training_tpu.training.checkpoint import (
            CheckpointManager,
        )
        ckpt = CheckpointManager(
            args.checkpoint_dir,
            post_save_hook=chaos.on_save if chaos else None,
            pre_finalize_hook=chaos.on_save_finalize if chaos else None)
        if args.resume:
            from distributed_pytorch_training_tpu.training.checkpoint import (
                CheckpointWorldSizeMismatch,
            )
            try:
                restored = ckpt.restore_latest(
                    state, template_world_size=n_batch_shards)
            except CheckpointWorldSizeMismatch as mismatch:
                # Cross-PROCESS elastic resume (ISSUE 12): a fleet
                # relaunch at a different world size lands here — the
                # flat-padded layouts (zero1 moments, fsdp params, EF
                # residuals) changed shape with the DP degree. Restore
                # the newest valid checkpoint RAW (its own saved shapes
                # are the old-world template; this process cannot build
                # device templates for a mesh it doesn't have) and
                # reshard the host arrays into this run's layout. The
                # named error escapes only when there is genuinely
                # nothing reshardable (no valid checkpoint / no recorded
                # world — a foreign directory, not an elastic relaunch).
                known = getattr(mismatch, "label", None)
                raw = ckpt.restore_latest_raw(
                    among=None if known is None else {known})
                if raw is None or raw[2] is None:
                    raise
                from distributed_pytorch_training_tpu.resilience.elastic \
                    import reshard_raw_state
                arrays, label, saved_world, r_epoch, r_step = raw
                with telemetry.span("elastic_reshard",
                                    from_world=saved_world,
                                    to_world=n_batch_shards, label=label,
                                    cross_process=True):
                    state = reshard_raw_state(arrays, saved_world,
                                              n_batch_shards, trainer,
                                              state)
                restored = (state, r_epoch, r_step)
                log_main(f"ELASTIC RESUME: checkpoint {label} was laid "
                         f"out for world size {saved_world}; resharded "
                         f"to {n_batch_shards} (flat-padded re-slice + "
                         "EF row fold — sampler/step-fence/RNG schedule "
                         "unchanged)")
            except Exception as e:
                # Param SHAPES depend on the TP layout (vocab padding is
                # lcm(128, model-axis)): resuming under a different --mesh
                # builds a mismatched template and orbax fails opaquely.
                # Diagnose precisely from the saved shape metadata.
                hint = ("resume with the SAME --mesh, --zero1 and "
                        "--fsdp-explicit settings (vocab padding for TP "
                        "follows the model axis; zero1 stores optimizer "
                        "state flat-sharded, fsdp-explicit stores params "
                        "flat-sharded too, the replicated path stores "
                        "both param-shaped)")
                try:
                    meta = ckpt.latest_metadata()
                    saved_params = meta["params"] if meta else {}
                    for emb_name in ("wte", "token_embedding"):
                        if emb_name in saved_params:
                            saved_rows = saved_params[emb_name][
                                "embedding"].shape[0]
                            have = getattr(model, "padded_vocab",
                                           getattr(model, "vocab_size", "?"))
                            if saved_rows != have:
                                hint = (
                                    f"the checkpoint's {emb_name} has "
                                    f"{saved_rows} vocab rows but this run "
                                    f"built {have} — pass --model-overrides "
                                    f"pad_vocab_to_multiple_of=<m> (or the "
                                    f"original --mesh) so the padded vocab "
                                    f"matches {saved_rows}")
                except Exception:
                    pass  # metadata diagnosis is best-effort only
                raise RuntimeError(
                    f"checkpoint restore failed — {hint}: {e}") from e
            if restored is not None:
                state, start_epoch, start_step = restored
                if start_step >= steps_per_epoch:  # stale steps_per_epoch
                    start_epoch, start_step = start_epoch + 1, 0
                log_main(f"Resumed from epoch {start_epoch}"
                         + (f" step {start_step}" if start_step else ""))

    csv = MetricsCSV(args.output_dir)  # ref :349-354

    if args.max_restarts > 0:
        # Restart supervisor (resilience/supervisor.py): segments the epoch
        # loop, checkpoints every epoch, and on a step/save failure restores
        # the latest VALID checkpoint and replays behind the step fence.
        # Validation + the CSV row run per completed epoch via the callback
        # (identical stdout/CSV contract). --profile-dir and
        # --checkpoint-every are not threaded through the supervised loop
        # (it owns the save cadence); preemption drains exactly like the
        # plain loop: checkpoint + stop, relaunch resumes with --resume.
        if args.profile_dir:
            log_main("NOTE: --profile-dir is ignored under --max-restarts")
        from distributed_pytorch_training_tpu.resilience.supervisor import (
            RetryPolicy, Supervisor,
        )

        def state_factory():
            return trainer.init_state(model, sample_input, tx,
                                      jax.random.PRNGKey(args.seed))

        def epoch_end(epoch, st, train_loss, train_acc, epoch_time):
            val_loss, val_acc = trainer.evaluate(st, val_loader.epoch(0))
            log_main(
                f"[Epoch {epoch + 1}/{args.epochs}] "
                f"Train: loss={train_loss:.4f}, acc={train_acc:.2f}% | "
                f"Val: loss={val_loss:.4f}, acc={val_acc:.2f}% | "
                f"Epoch time: {epoch_time:.2f}s"
            )
            csv.append(epoch, train_loss, train_acc, val_loss, val_acc,
                       epoch_time)

        # Control-plane autopilot (ISSUE 20): constructed ONLY under
        # --autopilot — off means no object, no observer, no threads, and
        # a recorder stream/HLO byte-identical to a build without the
        # control package. Eviction decisions on this fixed-world
        # supervisor are refused by the re-plan surface (no replan_cb)
        # and logged as `refuse` records — the audit trail still shows
        # what the policy wanted; the chaos harness proves the applied
        # path on its elastic rig.
        autopilot = None
        retune_cb = None
        if args.autopilot:
            from distributed_pytorch_training_tpu.control import (
                Autopilot, PerfTuner,
            )
            if args.autopilot_tune:
                import dataclasses as _dc

                from distributed_pytorch_training_tpu.resilience.elastic \
                    import ElasticPlan

                def retune_cb(overrides):
                    # same world, same loader, same optimizer — only the
                    # TrainConfig re-plans; boundary_retune carries every
                    # state leaf the new config keeps the layout of
                    new_trainer = Trainer(
                        task, mesh,
                        _dc.replace(trainer.config, **overrides),
                        rules=rules)
                    return ElasticPlan(
                        trainer=new_trainer, loader=train_loader,
                        state_factory=lambda: new_trainer.init_state(
                            model, sample_input, tx,
                            jax.random.PRNGKey(args.seed)),
                        world=new_trainer.batch_shards)
            autopilot = Autopilot(
                tuner=PerfTuner() if args.autopilot_tune else None
            ).attach()

        # trust_existing=args.resume: a fresh run pointed at a directory
        # holding a previous run's checkpoints must never restore one
        # mid-recovery (only --resume opts into the directory's history)
        sup = Supervisor(trainer, ckpt, state_factory, train_loader,
                         retry=RetryPolicy(max_restarts=args.max_restarts),
                         guard=guard, injector=chaos,
                         trust_existing=args.resume,
                         epoch_end_cb=epoch_end,
                         control=autopilot, retune_cb=retune_cb)
        try:
            state, report = sup.run(args.epochs,
                                    initial=(state, start_epoch,
                                             start_step))
        finally:
            if autopilot is not None:
                autopilot.detach()
        if autopilot is not None and autopilot.decisions:
            acts = ", ".join(f"{d.action}"
                             + ("[applied]" if d.applied else "")
                             for d in autopilot.decisions)
            log_main(f"Autopilot: {len(autopilot.decisions)} control "
                     f"decision(s): {acts}")
        log_main(f"Supervisor: completed={report.completed} "
                 f"restarts={report.restarts} "
                 f"steps_replayed={report.steps_replayed} "
                 f"torn_checkpoints_skipped={report.checkpoints_skipped}"
                 + (f" faults_fired={report.faults_fired}"
                    if report.faults_fired else ""))
        ckpt.wait()
        _log_save_blocked(ckpt)
        ckpt.close()
        cleanup_distributed()  # ref :386
        guard.disarm()
        return

    # The device-time attribution plane (ISSUE 15): a re-armable
    # StepProfiler exists whenever --profile-dir names a static window OR
    # the live /metrics surface is up (captures then land under
    # <output-dir>/profiles). Armed three ways: the static
    # --profile-steps window, POST /profile?steps=K on the metrics port,
    # and the watchdog's anomaly capture hook (a step-time spike /
    # loader stall records its own trace while it happens). Every closed
    # window is ingested by telemetry/device.py into a typed
    # device_profile event — per-phase device ms, per-collective rollup,
    # exposed-comm ratio, measured MFU. With both surfaces off, no
    # profiler object exists and the loop's step_hook stays None — the
    # zero-per-step-cost contract (pinned by test) is structural.
    profiler = None
    profile_base = args.profile_dir
    if profile_base is None and metrics_port and telemetry.is_configured():
        profile_base = str(Path(args.output_dir) / "profiles")
    if profile_base is not None:
        from distributed_pytorch_training_tpu.telemetry import (
            device as tele_device,
        )
        from distributed_pytorch_training_tpu.utils.profiling import (
            StepProfiler,
        )

        start = stop = None
        if args.profile_dir:
            start, stop = (int(x) for x in args.profile_steps.split(","))

        def _mfu_ref():
            # lazily read: set_mfu_reference runs after this closure is
            # built, and only on backends with a known chip peak
            if trainer._flops_per_sample and trainer._peak_flops_total:
                return (trainer._flops_per_sample * global_batch,
                        trainer._peak_flops_total)
            return None

        profiler = StepProfiler(
            profile_base, start, stop,
            on_capture=tele_device.make_ingestor(mfu_ref=_mfu_ref))
        server = (telemetry.get_metrics_server()
                  if metrics_port and telemetry.is_configured() else None)
        if server is not None:
            server.profile_handler = profiler.request_capture
        if trainer.watchdog is not None:
            trainer.watchdog.capture_hook = (
                lambda name, step: profiler.request_capture(
                    2, reason=f"anomaly:{name}", trigger_step=step))
        log_main(f"Profiler: on-demand capture armed (traces under "
                 f"{profile_base}"
                 + (f"; static window steps {start}-{stop}"
                    if start is not None else "") + ")")

    # Context-managed: an exception (or preemption-path raise) mid-epoch
    # must still stop an open jax.profiler session — a leaked session
    # fails every later start_trace in the process and loses the trace.
    import contextlib

    with profiler if profiler is not None else contextlib.nullcontext():
        for epoch in range(start_epoch, args.epochs):  # ref :356
            counts = samples_per_step_list(len(train_ds), global_batch,
                                           steps_per_epoch, args.drop_last)
            fault_hook = None
            if chaos is not None:
                # absolute global-step fence for crash/sigterm injections
                base = epoch * steps_per_epoch + start_step
                fault_hook = (lambda i, _base=base: chaos.on_step(_base + i))
            state, train_loss, train_acc, epoch_time, steps_done = \
                trainer.train_epoch(
                    state, train_loader.epoch(epoch, start_step=start_step),
                    epoch, steps_per_epoch,
                    samples_per_step=counts[start_step:], step_hook=profiler,
                    start_step=start_step,
                    stop_fn=lambda: guard.should_stop,
                    fault_hook=fault_hook)
            abs_step = start_step + steps_done
            start_step = 0

            if guard.should_stop and abs_step < steps_per_epoch:
                # Preempted MID-epoch: persist (epoch, step) immediately — a
                # resume replays nothing (the r3 story lost up to an epoch,
                # VERDICT r3 #5). No CSV row: the epoch is incomplete.
                telemetry.flush_flight(
                    cause=f"preemption (sigterm) drained at epoch {epoch} "
                          f"step {abs_step}", rc=0)
                if ckpt:
                    ckpt.save(epoch * steps_per_epoch + abs_step, state,
                              wait=True, epoch=epoch, step_in_epoch=abs_step,
                              world_size=n_batch_shards)
                    log_main(f"Preempted: checkpointed epoch {epoch} step "
                             f"{abs_step}/{steps_per_epoch}; relaunch with "
                             "--resume to continue mid-epoch")
                else:
                    log_main("Preempted: stopping (no --checkpoint-dir, "
                             "nothing persisted beyond the metrics CSV)")
                break

            val_loss, val_acc = trainer.evaluate(state, val_loader.epoch(0))

            # Epoch summary + CSV row (ref :373-384, formats identical).
            log_main(
                f"[Epoch {epoch + 1}/{args.epochs}] "
                f"Train: loss={train_loss:.4f}, acc={train_acc:.2f}% | "
                f"Val: loss={val_loss:.4f}, acc={val_acc:.2f}% | "
                f"Epoch time: {epoch_time:.2f}s"
            )
            csv.append(epoch, train_loss, train_acc, val_loss, val_acc, epoch_time)
            if telemetry.is_configured() and \
                    jax.tree_util.tree_leaves(state.grad_sync):
                # int8-wire error-feedback health: the carried residual's
                # global norm (epoch boundary — a host fetch happens here
                # anyway). A norm that grows without bound means the
                # telescoping sum stopped telescoping.
                sq = sum(float(jnp.vdot(r.astype(jnp.float32),
                                        r.astype(jnp.float32)))
                         for r in jax.tree_util.tree_leaves(state.grad_sync))
                telemetry.gauge("ef_residual_norm", float(np.sqrt(sq)),
                                epoch=epoch)

            if ckpt and (epoch + 1) % args.checkpoint_every == 0:
                ckpt.save((epoch + 1) * steps_per_epoch, state, epoch=epoch + 1,
                          world_size=n_batch_shards)

            if guard.should_stop:
                telemetry.flush_flight(
                    cause=f"preemption (sigterm) drained at epoch boundary "
                          f"{epoch + 1}", rc=0)
                if ckpt:
                    if (epoch + 1) % args.checkpoint_every != 0:  # not saved above
                        ckpt.save((epoch + 1) * steps_per_epoch, state,
                                  epoch=epoch + 1,
                                  world_size=n_batch_shards)
                    ckpt.wait()
                    log_main(f"Preempted: checkpointed epoch {epoch + 1}; "
                             "relaunch with --resume to continue")
                else:
                    log_main("Preempted: stopping (no --checkpoint-dir, "
                             "nothing persisted beyond the metrics CSV)")
                break

    if ckpt:
        ckpt.wait()  # finalize async writes before exit
        _log_save_blocked(ckpt)
        ckpt.close()
    cleanup_distributed()  # ref :386
    # Only now is it safe to cancel the hard-exit deadline: a preempted
    # multi-host cleanup can itself wedge on a dead peer, and a lingering
    # process would hold its device claim — the scenario the deadline exists
    # to prevent.
    guard.disarm()


if __name__ == "__main__":
    main()

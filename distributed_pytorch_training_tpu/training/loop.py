"""Trainer: compiled train/eval steps + epoch loops.

TPU-native re-design of train_one_epoch/validate
(/root/reference/train_ddp.py:170-300). The reference's per-batch body —
H2D copy, zero_grad, autocast forward, backward with DDP bucketed all-reduce,
scaler step (ref :198-214) — becomes ONE jitted function ``state, batch ->
state, metrics``; gradient sync is implied by the batch being sharded over the
mesh's data axes, and bf16 replaces autocast+GradScaler (no loss scaling
needed; SURVEY.md §2b).

Improvements over the reference, by design:
* metrics accumulate on device; the host fetches only at print boundaries
  (the ref's per-step ``.item()`` is a sync bottleneck, ref :217/:220);
* validation is sharded over the mesh instead of replicated per rank
  (ref :266-300 evaluates the full set on every rank; SURVEY.md §3.3);
* the last partial batch is padded+masked, so one XLA program serves every
  step (ref's drop_last=False short batch would recompile, SURVEY.md §7).

The parallelism promises the step modes make here (zero1's
scatter/update/gather signature, the bucketed reducer's collective bound,
compressed wires really off fp32, donation aliasing, no host transfers in
the compiled step, no per-step ``.item()`` syncs) are ENFORCED by the
contract checker — ``analysis check`` lowers the canonical config matrix
and lints this file's step paths (analysis/hlo_rules.py,
analysis/ast_rules.py ``no-host-sync-in-step``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.collectives import all_gather, psum, psum_scatter, shard_map
from ..parallel.grad_sync import (
    EF_WIRE_DTYPES, WIRE_DTYPES, HierSpec, build_bucket_plan,
    build_layer_plan, compressed_psum_scatter, ef_state_bucketed,
    ef_state_fsdp, ef_state_zero1, flatten_tree, hier_delta_all_gather,
    hier_psum_scatter, hier_shard_all_gather, padded_total_size,
    quantized_delta_all_gather, quantized_shard_all_gather, reduce_flat,
    unflatten_tree,
)
from ..parallel.mesh import BATCH_AXES, MODEL, batch_shard_count
from ..parallel.sharding import (
    PartitionRules, batch_spec, dp_flat_specs, feasible_spec,
    flatten_pad, fsdp_flat_params, fsdp_tp_flat_params, shard_pytree,
    tp_flat_leaf, tp_local_struct, tp_split_dims, tp_unflatten_leaf,
    tree_specs,
)
from ..utils.logging import log_main
from ..utils.metrics import ThroughputMeter
from .. import telemetry
from .tasks import Task, add_metrics, summarize, zero_metrics
from .train_state import TrainState


@dataclasses.dataclass
class TrainConfig:
    """Loop knobs (CLI-facing subset mirrors ref defaults, train_ddp.py:19-46)."""

    per_device_batch: int = 128
    print_freq: int = 50
    seed: int = 42
    bf16: bool = False  # the --amp equivalent (ref :36-37)
    donate_state: bool = True
    # Gradient accumulation: split each global batch into this many
    # microbatches inside the jitted step (lax.scan), summing weighted
    # gradients — reference-scale global batches on few chips at
    # 1/grad_accum the activation memory. 1 = off.
    grad_accum: int = 1
    # ZeRO-1 cross-replica weight-update sharding (Xu et al., PAPERS.md):
    # gradients reduce-scatter over the data-parallel axes instead of
    # all-reducing, each replica updates 1/N of the (flattened) parameters
    # with 1/N of the optimizer state, and the new parameters all-gather
    # back to replicated — optimizer compute and moment memory divided by
    # the DP degree. Off = the replicated (DDP-equivalent) update. No-op on
    # a single batch shard (the collectives' passthrough convention).
    zero1: bool = False
    # -- explicit gradient synchronization (parallel/grad_sync.py) --------
    # bucket_cap_mb > 0 engages the bucketed reducer (the DDP bucket_cap_mb
    # analog): gradients flatten into ceil(total_bytes / cap) contiguous
    # fp32 buckets, each synced by ONE collective — O(buckets) large
    # transfers instead of XLA's O(leaves) small ones. 0 = the implicit
    # path (gradient sync left to XLA layout propagation). Incompatible
    # with zero1 (whose per-leaf flat-shard layout IS its optimizer-state
    # checkpoint format).
    bucket_cap_mb: float = 0.0
    # Gradient wire dtype: "fp32" (exact), "bf16" (half the wire bytes,
    # bf16 accumulation on the wire — bounded error), "int8" (per-bucket
    # max-abs scales + error feedback carrying the quantization residual
    # to the next step; the bucketed form is gather-based, a byte win at
    # small DP degrees), or "int8_multihop" (DynamiQ's two-hop form: s8
    # all-to-all reduce-scatter with hop-1 error feedback, requantize the
    # partial sums, s8 all-gather — 2 collectives/bucket, ~2 B/element
    # regardless of the DP degree; see grad_sync.py's accounting). Master
    # accumulation and the optimizer always run fp32. Any non-fp32 value
    # engages the explicit reducer; "bf16"/"int8" compose with zero1 (the
    # reduce-scatter half compresses via s8 all-to-all, n-independently).
    # zero1 + "int8_multihop" is the FULLY compressed zero1 wire: the
    # scatter half is the s8 all-to-all (already n-independent — same as
    # "int8", with error feedback), and the param all-gather compresses
    # too — each replica gathers s8 codes of its shard's UPDATE (new
    # params - old params) plus one fp32 scale per chunk and adds the
    # identical dequantized delta to the replicated old params (bounded
    # per-step error, exactly replica-identical, not fed back;
    # grad_sync.quantized_delta_all_gather documents the model).
    # "int8_hier" is the two-tier topology-aware form on a tiered mesh
    # (a `slice` axis times the intra-slice batch axes): per bucket, an
    # EXACT fp32 reduce-scatter inside the slice (the fast ICI tier),
    # the DynamiQ s8 two-hop exchange ACROSS slices (the slow DCN tier —
    # the only compressed, error-fed-back stage; ~2 B/element per slice
    # independent of the slice count), and an exact intra-slice
    # all-gather back (grad_sync._int8_hier_sum). On a mesh without a
    # multi-sized slice axis it resolves to the flat fp32 path
    # (bit-identical passthrough, logged). Composes with grad-accum
    # overlap, zero1 (hier scatter + s8-over-slice param gather), and
    # fsdp_explicit's per-layer cut; rejected with explicit TP (the
    # model axis owns its own wire).
    wire_dtype: str = "fp32"
    # The mesh axis named as the slow-tier/outer axis for "int8_hier"
    # (mesh.SLICE by default — `--slices N` populates it). Must be one of
    # the mesh's batch axes; axes of size 1 (or absent) make int8_hier a
    # flat-fp32 passthrough.
    slice_axis: str = "slice"
    # Explicit full-parameter FSDP (SimpleFSDP, PAPERS.md): params AND
    # optimizer moments live flat-sharded 1/N per replica AT REST (the
    # zero1 flat padded layout applied to the parameters themselves), each
    # layer's params are all-gathered just-in-time inside the shard_map'd
    # step — gathers chained one layer ahead so layer i+1's gather can
    # overlap layer i's compute — and gradients reduce-scatter directly
    # back into the shard layout (compressed_psum_scatter, per layer).
    # Parameter memory at rest divides by the batch-shard count; the
    # transient in-step working set still peaks at full params (the
    # gathered copies live through the backward), like zero1. Composes
    # with wire_dtype: bf16/int8 compress the gradient scatter
    # (int8 with error feedback, per layer group); "int8_multihop"
    # additionally compresses the param gathers as s8 codes + per-chunk
    # scales (grad_sync.quantized_shard_all_gather — bounded,
    # replica-identical per-step perturbation of the gathered WORKING copy
    # only; at-rest shards stay exact fp32). Incompatible with zero1 (this
    # IS zero1 plus sharded params) and bucket_cap_mb (the per-layer cut
    # owns the wire layout). Off = params replicated (DDP layout).
    fsdp_explicit: bool = False
    # In grad-accum mode, reduce microbatch i's buckets INSIDE the scan
    # body (no data dependency on microbatch i+1's compute, so XLA can
    # overlap comm with compute — DDP's backward-hook overlap). False =
    # accumulate locally and reduce once after the scan (exposes the comm;
    # exists to measure the overlap win).
    overlap_grad_sync: bool = True
    # Fused int8 codec kernels (ops/quantize.py): route the int8 wires'
    # quantize (absmax-scale + round/clip) and receive-side dequant-
    # accumulate through Pallas kernels instead of the XLA-composed op
    # chain — one VMEM pass per codec stage, bit-identical by contract
    # (PARITY.md). None = auto (TPU only, DPT_FUSED_QUANTIZE env
    # override); True forces the kernels (interpreter mode on CPU — the
    # parity-test configuration); False forces the XLA-composed reference.
    # A no-op unless wire_dtype is an int8 mode on a multi-shard mesh.
    fused_quantize: Optional[bool] = None


def split_microbatches(tree: Any, accum: int,
                       scope: str = "per-shard batch") -> Any:
    """Interleaved microbatch split of a batch pytree for the grad-accum
    scan: leading dim B -> (accum, B/accum, ...), microbatch i = rows
    i::accum. INTERLEAVED, not contiguous blocks: the batch is sharded
    over the data axes by contiguous row ranges, so a contiguous
    microbatch would live on 1/accum of the devices and every scan step
    would reshard; strided microbatches stay evenly spread over all
    shards. Scalars broadcast to (accum,). One splitter for every step
    mode (replicated / grad_sync / zero1 / fsdp — the four scan bodies
    must agree on the interleaving or their parity tests lie); ``scope``
    names the batch in the divisibility error ("global batch" on the
    replicated path, the per-shard default inside shard_map bodies)."""

    def split(x):
        if x.ndim == 0:
            return jnp.broadcast_to(x, (accum,))
        if x.shape[0] % accum:
            raise ValueError(
                f"{scope} {x.shape[0]} not divisible by "
                f"grad_accum={accum}")
        return x.reshape(x.shape[0] // accum, accum,
                         *x.shape[1:]).swapaxes(0, 1)

    return jax.tree_util.tree_map(split, tree)


class Trainer:
    """Owns the compiled steps for one (model task, mesh) pair."""

    def __init__(
        self,
        task: Task,
        mesh: Mesh,
        config: TrainConfig,
        rules: Optional[PartitionRules] = None,
    ):
        self.task = task
        self.mesh = mesh
        self.config = config
        self.rules = rules
        # optional MFU reference (set_mfu_reference): when present, the
        # throughput print lines also report model-FLOPs utilization
        self._flops_per_sample: Optional[float] = None
        self._peak_flops_total: Optional[float] = None
        # optional telemetry.AnomalyWatchdog fed per-step host timings and
        # print-boundary losses by train_epoch (train.py installs it; None
        # everywhere else — the hot path pays two perf_counter reads)
        self.watchdog = None

        if config.wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype {config.wire_dtype!r} is not one of "
                f"{WIRE_DTYPES}")
        if config.bucket_cap_mb < 0:
            raise ValueError(
                f"bucket_cap_mb must be >= 0, got {config.bucket_cap_mb}")
        if config.zero1 and config.bucket_cap_mb > 0:
            raise ValueError(
                "bucket_cap_mb is the bucketed reducer of the replicated "
                "update path; zero1's per-leaf flat-shard layout IS its "
                "optimizer-state (and checkpoint) format — use zero1 with "
                "wire_dtype compression, or the bucketed reducer without "
                "zero1, not both")
        if config.fsdp_explicit and config.zero1:
            raise ValueError(
                "fsdp_explicit IS zero1 plus flat-sharded parameters (the "
                "sharded update with per-layer just-in-time gathers) — "
                "pick one update mode, not both")
        if config.fsdp_explicit and config.bucket_cap_mb > 0:
            raise ValueError(
                "bucket_cap_mb cuts the replicated reducer's flat "
                "gradient; fsdp_explicit's wire layout is the per-layer "
                "cut of the parameter tree (grad_sync.build_layer_plan) — "
                "use fsdp_explicit with wire_dtype compression instead")
        explicit_sync = (config.bucket_cap_mb > 0
                         or config.wire_dtype != "fp32")
        self._zero1_n = batch_shard_count(mesh)
        multi = self._zero1_n > 1
        model_n = mesh.shape.get(MODEL, 1)
        # Explicit TP x FSDP (ISSUE 13): on a 2-D ("data","model") mesh the
        # fsdp step runs megatron column/row-split blocks inside the SAME
        # shard_map (one psum over `model` per residual join); the
        # per-layer gathers/scatters ride the data axes only, over the
        # TP-LOCAL parameter slices — wire bytes drop 1/M per replica.
        # Params + both AdamW moments live flat-sharded 1/(N*M) at rest
        # (model-major flat layout, parallel/sharding.py tp_flat_leaf).
        self._fsdp = bool(config.fsdp_explicit) and (multi or model_n > 1)
        self._tp_n = model_n if (self._fsdp and model_n > 1) else 1
        # zero1 x TP (the per-leaf composition): on meshes with a model
        # axis the manual shard_map path cannot run (the TP layers need
        # GSPMD inside the body, and jax 0.4.x partial-auto shard_map
        # rejects the collectives) — the update shards via per-leaf
        # flat-padded sharding CONSTRAINTS instead: gradients/params are
        # annotated P(batch axes) per leaf and GSPMD partitions the
        # optimizer update + inserts the scatter/gather movement.
        self._zero1_gspmd = bool(config.zero1) and multi and model_n > 1
        self._zero1 = (bool(config.zero1) and multi
                       and not self._zero1_gspmd)
        self._grad_sync = (explicit_sync and not config.zero1
                           and not config.fsdp_explicit and multi)
        # -- two-tier topology-aware wire (int8_hier) ---------------------
        # Resolve the EFFECTIVE wire dtype and the hierarchy spec ONCE;
        # every step path and init_state read self._wire / self._hier
        # (engagement above keys off the REQUESTED dtype, so a resolved
        # passthrough still runs the explicit reducer — at fp32).
        self._wire = config.wire_dtype
        self._hier: Optional[HierSpec] = None
        if config.wire_dtype == "int8_hier":
            slice_axis = config.slice_axis
            if slice_axis not in BATCH_AXES:
                raise ValueError(
                    f"int8_hier syncs over the batch axes {BATCH_AXES}; "
                    f"slice_axis={slice_axis!r} is not one of them — the "
                    "slow tier must be a data-parallel mesh axis "
                    "(mesh.SLICE by default, populated by --slices)")
            n_slices = mesh.shape.get(slice_axis, 1)
            if n_slices > 0 and self._zero1_n % n_slices:
                # unreachable when slice_axis is a real batch axis (the
                # world IS the product of the batch axes) — a loud guard
                # for hand-built meshes
                raise ValueError(
                    f"int8_hier: {self._zero1_n} batch shards do not "
                    f"factor into {n_slices} slices (world % slices != 0)")
            if self._tp_n > 1:
                raise ValueError(
                    "int8_hier does not compose with explicit TP: the "
                    "model axis runs megatron psums with their own wire "
                    "accounting, and the hier codec's fast-tier "
                    "reduce-scatter would have to thread through them — "
                    "use int8_multihop under fsdp_explicit x TP, or "
                    "int8_hier on a model-free mesh")
            if n_slices > 1:
                fast = tuple(a for a in BATCH_AXES
                             if a != slice_axis
                             and mesh.shape.get(a, 1) > 1)
                self._hier = HierSpec(
                    slice_axis=slice_axis, fast_axes=fast,
                    n_slices=n_slices,
                    n_inner=self._zero1_n // n_slices)
            else:
                # slices=1 passthrough: nothing crosses a slow link, the
                # hierarchy collapses to the flat EXACT path — bit-for-bit
                # the fp32 wire (pinned in tests/test_hier.py)
                self._wire = "fp32"
                log_main("NOTE: int8_hier requested without a multi-slice "
                         f"mesh (axis {slice_axis!r} size {n_slices}) — "
                         "running the flat fp32 wire (bit-identical "
                         "passthrough)")
        # the per-layer gather plan + unflatten template; built by
        # init_state for fsdp_explicit states (the step needs the original
        # shapes — flat leaves alone cannot be unflattened)
        self._fsdp_plan = None
        self._fsdp_template = None
        self._fsdp_sizes = None
        # explicit-TP state (built by init_state when _tp_n > 1): the
        # per-leaf model-axis split dims (tp_fsdp_rules read as layout),
        # the TP-local model clone whose apply the step body runs, and the
        # TP-local ShapeDtypeStruct template the per-layer gather
        # unflattens against
        self._tp_split_dims = None
        self._tp_model = None
        self._fsdp_local_template = None
        if config.zero1 or config.fsdp_explicit or explicit_sync:
            # These modes run the step in a shard_map over the batch axes
            # (zero1/grad_sync with replicated parameters, fsdp_explicit
            # with flat-sharded ones) — same mesh constraints, except
            # zero1 composes with a `model` axis via the GSPMD path above.
            mode = ("fsdp_explicit" if config.fsdp_explicit
                    else "zero1" if config.zero1
                    else "grad_sync (bucket_cap_mb/wire_dtype)")
            allowed = ({MODEL} if (config.zero1 or config.fsdp_explicit)
                       else set())
            bad = sorted(a for a, s in mesh.shape.items()
                         if s > 1 and a not in BATCH_AXES
                         and a not in allowed)
            if bad:
                raise ValueError(
                    f"{mode} runs gradient sync over the data-parallel "
                    f"axes {BATCH_AXES}; mesh axes {bad} > 1 need the "
                    "implicit path (SP/PP/EP collectives are per-layer, "
                    "not per-update; only zero1 and fsdp_explicit compose "
                    "with a model axis — zero1 via the per-leaf GSPMD "
                    "update, fsdp_explicit via explicit megatron TP)")
            if self._zero1_gspmd and config.wire_dtype != "fp32":
                raise ValueError(
                    "zero1 on a model-axis mesh runs the GSPMD sharded "
                    "update, where the scatter/gather are layout "
                    "constraints, not explicit collectives the codecs "
                    "could wrap — a compressed wire on a model-axis mesh "
                    "is --fsdp-explicit's job (explicit TP x FSDP owns "
                    "its wire layout end to end; PARITY.md records this "
                    "path as subsumed); use wire_dtype='fp32' here")
            if rules is not None:
                conflict = sorted(
                    rules.axes_used()
                    & {a for a in BATCH_AXES if mesh.shape[a] > 1})
                if conflict and config.fsdp_explicit:
                    raise ValueError(
                        "fsdp_explicit owns the parameter layout "
                        "(flat-sharded 1/N over the batch axes) and would "
                        f"silently drop the partition rules sharding "
                        f"params over {conflict} — use GSPMD rules with "
                        "the implicit path, or fsdp_explicit without "
                        "param-sharding rules, not both")
                if conflict:
                    raise ValueError(
                        f"{mode} assumes replicated parameters, but the "
                        f"partition rules shard params over {conflict} — "
                        "explicitly sharded params + explicit sync is "
                        "fsdp_explicit's job (TrainConfig.fsdp_explicit / "
                        "--fsdp-explicit); GSPMD fsdp rules need the "
                        "implicit path")
            if config.zero1 and not multi:
                log_main("NOTE: zero1 requested on a single batch shard — "
                         "running the replicated update (identity "
                         "passthrough, like single-process DDP)")
            if config.fsdp_explicit and not multi and model_n <= 1:
                log_main("NOTE: fsdp_explicit requested on a single batch "
                         "shard — nothing to shard; running the "
                         "replicated update (identity passthrough)")
            if (not config.zero1 and not config.fsdp_explicit
                    and explicit_sync and not self._grad_sync):
                log_main("NOTE: explicit gradient sync requested on a "
                         "single batch shard — nothing to synchronize; "
                         "running the implicit path (identity passthrough, "
                         "like single-process DDP)")

        donate = (0,) if config.donate_state else ()
        self._train_step = jax.jit(self._train_step_impl, donate_argnums=donate)
        self._eval_step = jax.jit(self._eval_step_impl)

    @property
    def batch_shards(self) -> int:
        """The DP world size this trainer's step was built for (product of
        the mesh's batch axes). The restart Supervisor records it in every
        checkpoint manifest and re-plans against it on an elastic resize —
        the per-step RNG (folded from ``state.step``) and the sampler
        (seeded by seed+epoch at a FIXED global batch) are world-size-
        independent, so a resharded restore replays the same trajectory
        behind the same step fence."""
        return self._zero1_n

    def tp_expected_model_collectives(self) -> Tuple[int, int]:
        """(model-axis psums, model-axis gathers) one explicit-TP train
        step legitimately spends on STRUCTURAL (hidden-activation-sized)
        collectives — the `tp-psum-signature` rule's budget
        (analysis/hlo_rules.py), derived from the TP model: per block, one
        psum per residual join in the forward (attention out + MLP out)
        and one backward psum per parallel-region input — 4 per block —
        plus the vocab-parallel embedding's lookup psum + head-input
        backward psum when engaged. Gathers are 0: the parallel-vocab
        cross-entropy (collectives.tp_parallel_cross_entropy) replaced
        the vocab-scale logits gather; its two (B, S, 2)-sized stat
        collectives are batch-shaped, not hidden-shaped, and are budgeted
        separately by `tp_expected_ce_stat_elements` so the rule can
        floor-filter them. (0, 0) when explicit TP is not engaged."""
        if self._tp_n <= 1 or self._tp_model is None:
            return (0, 0)
        depth = getattr(self._tp_model, "depth", None)
        if depth is None:
            return (0, 0)
        tp_vocab = bool(getattr(self._tp_model, "tp_vocab", False))
        return (4 * depth + (2 if tp_vocab else 0), 0)

    def tp_expected_ce_stat_elements(self, local_rows: int,
                                     seq_len: int) -> int:
        """Per-shard element count of EACH of the parallel-vocab CE's two
        model-axis stat collectives (the stop-gradient pmax and the
        stacked [sumexp, target-logit] psum — both deliberately
        (local_rows, seq-1, 2)-shaped so they share one census size
        class; collectives.tp_parallel_cross_entropy). The
        `tp-psum-signature` rule adds 2 to the psum budget iff this
        clears its census floor — the stats are batch-shaped, so whether
        a given artifact SEES them depends on batch x floor, unlike the
        hidden-sized structural psums. 0 when the vocab-parallel head is
        not engaged."""
        if self._tp_n <= 1 or self._tp_model is None:
            return 0
        if not bool(getattr(self._tp_model, "tp_vocab", False)):
            return 0
        return 2 * int(local_rows) * max(int(seq_len) - 1, 1)

    def tp_wire_bytes(self, local_batch: int, seq_len: int) -> int:
        """Per-replica model-axis wire bytes of one explicit-TP step
        (`grad_sync.tp_psum_bytes_per_step` fed from the TP model) — the
        TP tier term train.py and the experiments harness emit. 0 when explicit
        TP is not engaged."""
        from ..parallel.grad_sync import tp_psum_bytes_per_step

        if self._tp_n <= 1 or self._tp_model is None:
            return 0
        m = self._tp_model
        if getattr(m, "depth", None) is None:
            return 0
        return tp_psum_bytes_per_step(
            m.hidden_dim, m.depth, local_batch, seq_len, self._tp_n,
            tp_vocab=bool(getattr(m, "tp_vocab", False)),
            padded_vocab=getattr(m, "padded_vocab", 0))

    def wire_accounting_inputs(self, state: TrainState, base_cfg: dict,
                               global_batch: int, seq_len: int):
        """(params, cfg) for `grad_sync.emit_wire_accounting` — THE one
        assembly both train.py and the experiments harness use, so their rows
        cannot drift. Under explicit TP the data-axis terms come from the
        TP-LOCAL template (each model shard gathers/scatters only its 1/M
        slice) and the model-axis activation bytes ride ``tp_psum_bytes``
        (their own telemetry tier row); 1-D configs pass through
        unchanged."""
        cfg = dict(base_cfg)
        params = state.params
        if self._tp_n > 1:
            params = self._fsdp_local_template
            cfg["model_shards"] = self._tp_n
            cfg["tp_psum_bytes"] = self.tp_wire_bytes(
                global_batch // self._zero1_n, seq_len)
        if self._hier is not None:
            # the slice factorization lives in the MESH, not the config
            # dict callers hold — inject the resolved count so the
            # accounting records the tiered split (and a resolved
            # passthrough records the flat fp32 wire it actually runs)
            cfg["slices"] = self._hier.n_slices
        elif cfg.get("wire_dtype") == "int8_hier":
            cfg["wire_dtype"] = self._wire  # slices=1 passthrough: fp32
        return params, cfg

    def set_mfu_reference(self, flops_per_sample: float,
                          peak_flops_total: float) -> None:
        """Enable MFU in the step log: `flops_per_sample` is the analytic
        train-step cost of ONE sample (experiments/flops.py),
        `peak_flops_total` the summed peak FLOP/s of the mesh's devices.
        The reference's meter stops at samples/s (train_ddp.py:224-243);
        MFU is the same number made comparable across hardware."""
        self._flops_per_sample = flops_per_sample
        self._peak_flops_total = peak_flops_total

    # -- compiled bodies ---------------------------------------------------

    # A train step's named regions are scope paths in the compiled program:
    # flax opens one per module (`attn`, `mlp`, `wte`, `wpe`, `ln_f`), `head`
    # is opened in models/gpt2.py, `loss` in training/tasks.py, `optimizer`
    # wherever a step body runs `tx.update`. Trace-time metadata only; the
    # benchmark's `train_*_ms` read device time by them (`TRAIN_STEP` in
    # benchmark/layer_metrics/_regions.py lists them).

    def _train_step_impl(self, state: TrainState, batch, epoch_key):
        rng = jax.random.fold_in(epoch_key, state.step)
        accum = self.config.grad_accum

        if self._fsdp:
            return self._fsdp_step(state, batch, rng)
        if self._zero1:
            return self._zero1_step(state, batch, rng)
        if self._grad_sync:
            return self._grad_sync_step(state, batch, rng)

        if accum <= 1:
            def loss_fn(params):
                return self.task.loss_and_metrics(state, params, batch, rng,
                                                  train=True)

            grads, (metrics, new_stats) = jax.grad(
                loss_fn, has_aux=True)(state.params)
            # No explicit all-reduce: grads of a loss over the data-sharded
            # global batch are already the synchronized gradients (the DDP
            # reducer's job, ref :305-310, done by XLA layout propagation).
            if self._zero1_gspmd:
                return self._zero1_gspmd_apply(state, grads,
                                               new_stats), metrics
            new_state = state.apply_gradients(grads, batch_stats=new_stats)
            return new_state, metrics

        # -- gradient accumulation ----------------------------------------
        # The task loss is the weighted MEAN over its (micro)batch, so the
        # global-batch gradient is the weight-proportional combination:
        #   d(global mean)/dθ = Σ_i (w_i / W) · d(mean_i)/dθ.
        # We accumulate w_i-scaled microbatch grads in the scan carry and
        # divide by W once.
        #
        # Equivalence scope (vs the unaccumulated step on the same batch):
        # EXACT (up to fp reassociation) for deterministic per-sample losses
        # (causal LM with dropout 0 — the parity test). NOT bit-equal for:
        # * stochastic tasks (MLM masking, dropout, augmentation): each
        #   microbatch gets its own fold of the step RNG, so different
        #   positions mask — still an unbiased step, just a different draw;
        # * batch-statistic auxiliary losses (MoE load balancing): the
        #   accumulated objective is the w_i/W-weighted combination of
        #   per-microbatch aux losses, whereas grad_accum=1 computes routing
        #   statistics over the full batch. Inherent to accumulation, not a
        #   bug — per-microbatch balancing is itself a valid regularizer.
        # * BatchNorm models (ResNets): each microbatch normalizes by ITS
        #   OWN statistics (exactly torch's behavior under accumulation), so
        #   grads differ from the full-batch step by the (small, O(1/|mb|))
        #   between-microbatch variance. Running stats stay unbiased: every
        #   microbatch EMA starts from the SAME pre-step stats (state is
        #   closed over, not carried), so the weighted mean of the per-
        #   microbatch EMAs equals ONE EMA update with the weighted-mean
        #   batch statistics — not `accum` compounding updates.
        has_stats = bool(jax.tree_util.tree_leaves(state.batch_stats))

        micro_batches = split_microbatches(batch, accum,
                                           scope="global batch")

        def micro_grads(mb, key):
            def loss_fn(params):
                return self.task.loss_and_metrics(state, params, mb, key,
                                                  train=True)

            return jax.grad(loss_fn, has_aux=True)(state.params)

        def body(carry, xs):
            g_sum, s_sum, m_sum = carry
            mb, key = xs
            g, (m, new_stats) = micro_grads(mb, key)
            w = m["weight"]
            g_sum = jax.tree_util.tree_map(
                lambda a, b: a + w * b.astype(a.dtype), g_sum, g)
            if has_stats:
                s_sum = jax.tree_util.tree_map(
                    lambda a, b: a + w * b.astype(a.dtype), s_sum, new_stats)
            m_sum = add_metrics(m_sum, m)
            return (g_sum, s_sum, m_sum), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        s0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, jnp.float32), state.batch_stats)
        keys = jax.random.split(rng, accum)
        (g_sum, s_sum, metrics), _ = jax.lax.scan(
            body, (g0, s0, zero_metrics()), (micro_batches, keys))
        total_w = jnp.maximum(metrics["weight"], 1.0)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / total_w).astype(p.dtype), g_sum, state.params)
        if has_stats:
            # A fully-padded global batch (weight 0) must keep the old
            # stats, not zero them (grads are already a no-op then).
            new_stats = jax.tree_util.tree_map(
                lambda s, old: jnp.where(metrics["weight"] > 0, s / total_w,
                                         old.astype(jnp.float32)
                                         ).astype(old.dtype),
                s_sum, state.batch_stats)
        else:
            new_stats = state.batch_stats
        if self._zero1_gspmd:
            return self._zero1_gspmd_apply(state, grads, new_stats), metrics
        new_state = state.apply_gradients(grads, batch_stats=new_stats)
        return new_state, metrics

    # -- ZeRO-1 x TP: GSPMD-sharded weight update ----------------------------

    def _zero1_gspmd_apply(self, state: TrainState, grads, new_stats
                           ) -> TrainState:
        """The zero1 update on meshes with a `model` axis (the per-leaf
        composition): gradients arrive fully synchronized from the
        replicated path's implicit sync (TP params carry TP-sharded grads,
        DP sync is XLA's), and the UPDATE shards over the batch axes by
        layout constraint — each leaf's gradient, parameter view, and
        moments are flat-padded and annotated P(batch axes), so GSPMD
        partitions the elementwise optimizer chain 1/N per replica and
        inserts the scatter/gather data movement itself. Moments live
        flat-sharded from init (`optim.zero1_opt_state`), exactly like the
        manual zero1 path — same checkpoint layout, same memory division.

        Trade-offs vs the manual shard_map path (pure-DP meshes), stated
        honestly: the collective schedule is XLA's choice (no
        reduce-scatter signature contract), wire compression is
        unavailable (the scatter/gather are constraints, not explicit
        collectives the codecs could wrap), and the global-norm clip runs
        on GLOBAL flat arrays (stock optax — build the optimizer with
        shard_axes=None). Parity vs the replicated update is pinned at
        reassociation tolerance in tests/test_zero1.py."""
        from jax.sharding import NamedSharding

        mesh, n = self.mesh, self._zero1_n
        dp = NamedSharding(mesh, P(BATCH_AXES))

        def flat_dp(x):
            return lax.with_sharding_constraint(
                flatten_pad(x.astype(jnp.float32), n), dp)

        flat_g = jax.tree_util.tree_map(flat_dp, grads)
        p_flat = jax.tree_util.tree_map(flat_dp, state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = state.tx.update(flat_g, state.opt_state,
                                               p_flat)
            new_flat = optax.apply_updates(p_flat, updates)
        # back to model shapes, re-constrained to the rules' layout so the
        # updated params keep their TP sharding instead of whatever the
        # flat->full reshape propagates
        specs = tree_specs(state.params, self.rules)

        def unflatten(f, p, spec):
            full = f[:p.size].reshape(p.shape).astype(p.dtype)
            return lax.with_sharding_constraint(
                full, NamedSharding(
                    mesh, feasible_spec(spec, p.shape, mesh)))

        new_params = jax.tree_util.tree_map(unflatten, new_flat,
                                            state.params, specs)
        return state.replace(step=state.step + 1, params=new_params,
                             batch_stats=new_stats, opt_state=new_opt)

    # -- explicit bucketed / compressed gradient sync ------------------------

    def _grad_sync_step(self, state: TrainState, batch, rng):
        """The native DDP reducer (parallel/grad_sync.py): the step runs in
        a shard_map over the batch axes, each replica computes its LOCAL
        weight-scaled gradient sum, flattens it into the bucket plan's flat
        vector, and syncs bucket-by-bucket at the configured wire dtype;
        the (replicated) optimizer update consumes the fp32 global mean.
        In grad-accum mode with overlap on, each microbatch's buckets are
        reduced INSIDE the scan body — microbatch i's collectives have no
        data dependency on microbatch i+1's compute, so XLA's latency-
        hiding scheduler can run them concurrently (DDP's backward-hook
        overlap, done by dependence structure instead of hooks).

        Equivalence scope vs the implicit path, same batch:
        * The REASSOCIATION ORDER changes: the implicit path lets XLA
          contract the loss mean over the global batch; here each replica
          sums its local batch first and the psum combines replicas (and,
          under accumulation with overlap, per-microbatch psums sum
          instead of one psum of sums). Within a bucket, leaves keep
          `jax.tree_util.tree_leaves` order. Same real-number gradient,
          fp-rounding-level differences — the parity contract
          tests/test_grad_sync.py pins with tolerances and documents.
          Bucket BOUNDARIES never change math: per-element reductions are
          independent, so different bucket_cap_mb values produce
          bit-identical trajectories (also pinned).
        * bf16 wire: the cross-replica sum accumulates in bf16 — a bounded
          per-step perturbation, convergence pinned on the tiny-LM task.
        * int8 wire: per-bucket max-abs quantization with error feedback —
          biased per step, telescoping across steps; convergence pinned.
        * int8_multihop wire: TWO quantizations per bucket — hop 1
          per-destination-chunk with error feedback (telescoping, like
          int8), hop 2 on the requantized partial sum (a bounded per-step
          perturbation, identical on every replica, NOT fed back —
          grad_sync.py documents the bound); convergence pinned.
        * int8_hier wire: the intra-slice reduce-scatter and all-gather
          are EXACT fp32 (only reassociation changes vs flat fp32); all
          compression error comes from the cross-slice s8 multihop stage
          (hop-1 EF telescoping + hop-2 bounded, the int8_multihop model
          applied over the slice axis alone — PARITY.md "Exactness
          model: two-tier sync"); convergence pinned.
        * stochastic tasks / BatchNorm: the zero1 caveats verbatim (each
          shard folds its index into the step RNG; BN normalizes by
          per-shard statistics, torch DDP's per-GPU BN semantics).
        """
        mesh, accum, n = self.mesh, self.config.grad_accum, self._zero1_n
        axes = BATCH_AXES
        task, cfg = self.task, self.config
        wire, overlap = self._wire, cfg.overlap_grad_sync
        hier = self._hier if wire == "int8_hier" else None
        fusedq = cfg.fused_quantize  # tri-state; codecs resolve at trace
        has_stats = bool(jax.tree_util.tree_leaves(state.batch_stats))
        outer = state
        plan = build_bucket_plan(state.params, cfg.bucket_cap_mb)
        use_ef = wire in EF_WIRE_DTYPES
        if use_ef and not state.grad_sync:
            raise ValueError(
                f"wire_dtype={wire!r} needs error-feedback buffers — build "
                "the state via Trainer.init_state (TrainState.grad_sync is "
                "empty)")
        if use_ef:
            # The residual layout is plan-dependent for the multihop wire
            # (padded_bucket_bounds of THIS bucket_cap_mb): a checkpoint
            # resumed under a different cap would silently re-inject stale
            # error at the wrong elements — fail loudly on the size
            # mismatch instead. (Same-size different-layout collisions are
            # possible in principle; changing the cap across a multihop
            # resume is unsupported, documented at ef_state_bucketed.)
            if wire == "int8_multihop":
                expect = padded_total_size(plan, n)
            elif wire == "int8_hier":
                # one slow-tier residual slice per replica: the padded
                # layout divided by the intra-slice degree (the fast
                # reduce-scatter's output IS the compressed stage's input)
                expect = padded_total_size(plan, n) // hier.n_inner
            else:
                expect = plan.total_size
            got = state.grad_sync["ef"].shape[-1]
            if got != expect:
                raise ValueError(
                    f"error-feedback residual length {got} does not match "
                    f"the {wire!r} wire's layout for bucket_cap_mb="
                    f"{cfg.bucket_cap_mb} ({expect} elements) — the state "
                    "was built (or checkpointed) under a different bucket "
                    "plan; rebuild via Trainer.init_state or restore with "
                    "the original bucket_cap_mb")

        rep = P()
        batch_specs = jax.tree_util.tree_map(
            lambda x: batch_spec(jnp.ndim(x)), batch)
        ef_spec = P(axes)

        def body(params, opt_state, stats, lbatch, key, step, *maybe_ef):
            inner = outer.replace(step=step, params=params,
                                  batch_stats=stats, opt_state=opt_state)
            idx = lax.axis_index(axes)
            # local residual: (S,) for int8, (S_padded,) for int8_multihop
            ef_l = maybe_ef[0][0] if use_ef else None

            def micro_grads(mb, k):
                def loss_fn(p):
                    return task.loss_and_metrics(inner, p, mb, k, train=True)

                return jax.grad(loss_fn, has_aux=True)(params)

            if accum <= 1:
                key = jax.random.fold_in(key, idx)
                g, (m, stats_l) = micro_grads(lbatch, key)
                w = m["weight"]
                flat = flatten_tree(jax.tree_util.tree_map(
                    lambda a: w * a.astype(jnp.float32), g))
                flat, ef_l = reduce_flat(flat, plan, axes, n, wire, ef_l,
                                         fused=fusedq, hier=hier)
                s_sum = (jax.tree_util.tree_map(
                    lambda s: w * s.astype(jnp.float32), stats_l)
                    if has_stats else stats)
                m_local = m
            else:
                # the replicated path's interleaved LOCAL split (zero1's
                # argument verbatim: local rows i::accum are the shard's
                # part of global microbatch i)
                micro_batches = split_microbatches(lbatch, accum)
                keys = jax.random.split(key, accum)

                def mb_body(carry, xs):
                    acc, s_sum, m_sum, ef_c = carry
                    mb, k = xs
                    g, (m, stats_mb) = micro_grads(
                        mb, jax.random.fold_in(k, idx))
                    w = m["weight"]
                    flat = flatten_tree(jax.tree_util.tree_map(
                        lambda a: w * a.astype(jnp.float32), g))
                    if overlap:
                        # sync THIS microbatch's buckets now — the carry
                        # holds already-global sums, and the collective
                        # overlaps the next microbatch's compute
                        flat, ef_c = reduce_flat(flat, plan, axes, n,
                                                 wire, ef_c, fused=fusedq,
                                                 hier=hier)
                    acc = acc + flat
                    if has_stats:
                        s_sum = jax.tree_util.tree_map(
                            lambda a, b: a + w * b.astype(a.dtype),
                            s_sum, stats_mb)
                    m_sum = add_metrics(m_sum, m)
                    return (acc, s_sum, m_sum, ef_c), None

                acc0 = jnp.zeros((plan.total_size,), jnp.float32)
                s0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), stats)
                (flat, s_sum, m_local, ef_l), _ = lax.scan(
                    mb_body, (acc0, s0, zero_metrics(), ef_l),
                    (micro_batches, keys))
                if not overlap:
                    flat, ef_l = reduce_flat(flat, plan, axes, n, wire,
                                             ef_l, fused=fusedq, hier=hier)

            # metric fan-in (the zero1 comment verbatim: 3 scalar psums)
            metrics = jax.tree_util.tree_map(
                lambda v: psum(v, axes), m_local)
            total_w = jnp.maximum(metrics["weight"], 1.0)
            grads = unflatten_tree(flat / total_w, params)

            # replicated update from the synced global-mean gradient — the
            # optimizer must NOT carry shard_axes here (grads are already
            # global; a psum'd clip norm would count every replica n times)
            with jax.named_scope("optimizer"):
                updates, new_opt = outer.tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)

            if has_stats:
                new_stats = jax.tree_util.tree_map(
                    lambda s, old: jnp.where(
                        metrics["weight"] > 0,
                        psum(s, axes) / total_w,
                        old.astype(jnp.float32)).astype(old.dtype),
                    s_sum, stats)
            else:
                new_stats = stats
            out = (new_params, new_opt, new_stats, metrics)
            if use_ef:
                out += (ef_l[None],)
            return out

        in_specs = (rep, rep, rep, batch_specs, rep, rep)
        out_specs = (rep, rep, rep, rep)
        args = [state.params, state.opt_state, state.batch_stats, batch,
                rng, state.step]
        if use_ef:
            in_specs += (ef_spec,)
            out_specs += (ef_spec,)
            args.append(state.grad_sync["ef"])
        stepped = shard_map(body, mesh, in_specs=in_specs,
                            out_specs=out_specs)
        res = stepped(*args)
        new_params, new_opt, new_stats, metrics = res[:4]
        new_gs = {"ef": res[4]} if use_ef else state.grad_sync
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_stats, opt_state=new_opt,
                                  grad_sync=new_gs)
        return new_state, metrics

    # -- ZeRO-1 sharded weight update ---------------------------------------

    def _zero1_step(self, state: TrainState, batch, rng):
        """Cross-replica sharded update (Xu et al., PAPERS.md): the whole
        step runs in a shard_map over the batch axes, so the gradient sync
        is an explicit `psum_scatter` (a true reduce-scatter in the compiled
        HLO — half an all-reduce), the optimizer update touches only this
        replica's 1/N flat chunk of params + moments, and one `all_gather`
        rebuilds the replicated parameters. Collective payload per step
        stays ~2x params (all-reduce = reduce-scatter + all-gather), but
        the update compute and moment memory divide by N, and XLA can
        overlap the gather with the next step's forward.

        Semantics vs the replicated path, same batch:
        * deterministic tasks (causal LM, dropout 0): identical up to fp
          reassociation — the parity contract tests/test_zero1.py pins;
        * stochastic tasks: each shard folds its linear shard index into
          the step RNG, so draws are independent across shards but differ
          from the replicated path's single global stream (the grad-accum
          caveat, verbatim);
        * BatchNorm models: each shard normalizes by ITS OWN statistics —
          exactly torch DDP's per-GPU BatchNorm (ref train_ddp.py:305-310
          never syncs BN), where the replicated GSPMD path computes
          global-batch statistics. EMAs stay unbiased: the weighted mean
          of per-shard EMAs equals one EMA update with the weighted-mean
          batch statistics (the grad-accum argument, across space instead
          of time).

        Wire compression (TrainConfig.wire_dtype) composes here: the
        reduce-scatter half runs at bf16 or int8+error-feedback (one
        residual per leaf per replica, parallel/grad_sync.py) — the grads
        compress, the parameter all-gather stays exact. The residual is in
        weight-scaled-gradient units (scatter operands are w-scaled sums).
        "int8_multihop" compresses BOTH halves: the scatter is the same s8
        all-to-all as "int8" (with error feedback), and the param gather
        rides s8 too — each replica quantizes its shard's UPDATE (new
        shard - old shard) per chunk and all replicas add the identical
        dequantized delta to the replicated old params
        (grad_sync.quantized_delta_all_gather: bounded per-step error,
        replica-identical, not fed back — the hop-2 error model).
        "int8_hier" tiers both halves over the slice factorization: the
        scatter is an exact fp32 intra-slice reduce-scatter followed by
        the s8 cross-slice exchange with error feedback
        (grad_sync.hier_psum_scatter), and the param gather rides s8
        UPDATE codes across slices + an exact fp32 intra-slice gather
        (grad_sync.hier_delta_all_gather) — only the slow tier ever
        carries compressed bytes. Shard ownership is FAST-MAJOR
        (HierSpec.hier_axes): chunk j*n_slices+s belongs to (fast j,
        slice s), so the at-rest flat layout shards over
        fast_axes+(slice,) instead of the batch axes.
        """
        mesh, accum, n = self.mesh, self.config.grad_accum, self._zero1_n
        axes = BATCH_AXES
        task = self.task
        wire = self._wire
        hier = self._hier if wire == "int8_hier" else None
        fusedq = self.config.fused_quantize  # tri-state, resolved at trace
        # multihop's scatter half IS the int8 s8 all-to-all (already
        # n-independent); what multihop adds over "int8" here is the
        # compressed param gather below.
        scatter_wire = "int8" if wire == "int8_multihop" else wire
        use_ef = wire in EF_WIRE_DTYPES
        if use_ef and not state.grad_sync:
            raise ValueError(
                f"wire_dtype={wire!r} needs error-feedback buffers — build "
                "the state via Trainer.init_state (TrainState.grad_sync is "
                "empty)")
        has_stats = bool(jax.tree_util.tree_leaves(state.batch_stats))
        outer = state  # static fields (apply_fn/tx) for the inner rebuild

        rep = P()
        batch_specs = jax.tree_util.tree_map(
            lambda x: batch_spec(jnp.ndim(x)), batch)
        opt_specs = dp_flat_specs(
            state.opt_state,
            axes=hier.hier_axes if hier is not None else BATCH_AXES)

        def body(params, opt_state, stats, lbatch, key, step, *maybe_ef):
            inner = outer.replace(step=step, params=params,
                                  batch_stats=stats, opt_state=opt_state)
            idx = lax.axis_index(axes)  # linear replica index over the axes
            # chunk OWNERSHIP index: fast-major under the hier wire (the
            # fast psum_scatter hands fast-rank j chunk j, the slice
            # exchange hands slice s sub-chunk s), batch-linear otherwise
            own = (lax.axis_index(hier.hier_axes) if hier is not None
                   else idx)
            # per-leaf local residuals, (1, padded) -> (padded,)
            ef_l = (jax.tree_util.tree_map(lambda r: r[0], maybe_ef[0])
                    if use_ef else None)
            treedef = jax.tree_util.tree_structure(params)

            def micro_grads(mb, k):
                def loss_fn(p):
                    return task.loss_and_metrics(inner, p, mb, k, train=True)

                return jax.grad(loss_fn, has_aux=True)(params)

            def scatter_tree(gtree, ef_tree, combine=None, into=None):
                """Per-leaf compressed reduce-scatter of the w-scaled grad
                tree: returns (shard tree [combined into `into` via
                `combine` when given], new ef tree)."""
                g_leaves = treedef.flatten_up_to(gtree)
                ef_leaves = (treedef.flatten_up_to(ef_tree) if use_ef
                             else [None] * len(g_leaves))
                into_leaves = (treedef.flatten_up_to(into)
                               if into is not None else [None] * len(g_leaves))
                outs, new_efs = [], []
                for a, r, acc in zip(g_leaves, ef_leaves, into_leaves):
                    if hier is not None:
                        s, nr = hier_psum_scatter(
                            flatten_pad(a.astype(jnp.float32), n), hier,
                            r, fused=fusedq)
                    else:
                        s, nr = compressed_psum_scatter(
                            flatten_pad(a.astype(jnp.float32), n), axes, n,
                            scatter_wire, r, fused=fusedq)
                    outs.append(acc + s if combine else s)
                    new_efs.append(nr)
                return (jax.tree_util.tree_unflatten(treedef, outs),
                        (jax.tree_util.tree_unflatten(treedef, new_efs)
                         if use_ef else None))

            if accum <= 1:
                key = jax.random.fold_in(key, idx)
                g, (m, stats_l) = micro_grads(lbatch, key)
                w = m["weight"]
                g_sum, ef_l = scatter_tree(
                    jax.tree_util.tree_map(lambda a: w * a, g), ef_l)
                s_sum = (jax.tree_util.tree_map(
                    lambda s: w * s.astype(jnp.float32), stats_l)
                    if has_stats else stats)
                m_local = m
            else:
                # grad accumulation INSIDE the sharded step: the scan carry
                # holds w-scaled gradient *shards* ((padded/N,) fp32), so
                # the accumulation buffer is 1/N the replicated path's.
                # Split is over the LOCAL rows; with the local batch
                # divisible by accum, local rows i::accum are exactly the
                # shard's part of global microbatch i (the interleaved
                # global split of the replicated path).
                micro_batches = split_microbatches(lbatch, accum)
                keys = jax.random.split(key, accum)

                def mb_body(carry, xs):
                    g_sum, s_sum, m_sum, ef_c = carry
                    mb, k = xs
                    g, (m, stats_mb) = micro_grads(
                        mb, jax.random.fold_in(k, idx))
                    w = m["weight"]
                    g_sum, ef_c = scatter_tree(
                        jax.tree_util.tree_map(lambda b: w * b, g), ef_c,
                        combine=True, into=g_sum)
                    if has_stats:
                        s_sum = jax.tree_util.tree_map(
                            lambda a, b: a + w * b.astype(a.dtype),
                            s_sum, stats_mb)
                    m_sum = add_metrics(m_sum, m)
                    return (g_sum, s_sum, m_sum, ef_c), None

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(
                        (flatten_pad(p, n).size // n,), jnp.float32),
                    params)
                s0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), stats)
                (g_sum, s_sum, m_local, ef_l), _ = lax.scan(
                    mb_body, (g0, s0, zero_metrics(), ef_l),
                    (micro_batches, keys))

            # fan the per-shard metric sums in (the reference's 3 epoch
            # all-reduces, ref :251-253, here 3 scalar psums per step)
            metrics = jax.tree_util.tree_map(
                lambda v: psum(v, axes), m_local)
            total_w = jnp.maximum(metrics["weight"], 1.0)

            def pshard(p):
                flat = flatten_pad(p, n)
                k = flat.size // n
                return lax.dynamic_slice_in_dim(flat, own * k, k)

            p_shards = jax.tree_util.tree_map(pshard, params)
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / total_w).astype(p.dtype), g_sum, p_shards)

            # 1/N of the optimizer update — the whole point of zero1
            with jax.named_scope("optimizer"):
                updates, new_opt = outer.tx.update(grads, opt_state,
                                                   p_shards)
                new_p_shards = optax.apply_updates(p_shards, updates)
            if wire == "int8_multihop":
                # compressed param gather: s8 UPDATE codes + one fp32 scale
                # per chunk; every replica adds the identical dequantized
                # delta to the replicated old params, so exact replication
                # is preserved (grad_sync.quantized_delta_all_gather)
                new_params = jax.tree_util.tree_map(
                    lambda s, old, p: quantized_delta_all_gather(
                        s, old, flatten_pad(p, n), axes, fused=fusedq,
                    )[:p.size].reshape(p.shape).astype(p.dtype),
                    new_p_shards, p_shards, params)
            elif hier is not None:
                # two-tier param gather: s8 UPDATE codes + per-chunk fp32
                # scales cross the slices (bounded, replica-identical, not
                # fed back — the multihop hop-2 model), then an EXACT fp32
                # all-gather inside the slice; slice first, fast second,
                # inverting the fast-major chunk ownership
                new_params = jax.tree_util.tree_map(
                    lambda s, old, p: hier_delta_all_gather(
                        s, old, flatten_pad(p, n), hier, fused=fusedq,
                    )[:p.size].reshape(p.shape).astype(p.dtype),
                    new_p_shards, p_shards, params)
            else:
                new_params = jax.tree_util.tree_map(
                    lambda s, p: all_gather(s, axes)[:p.size].reshape(p.shape),
                    new_p_shards, params)

            if has_stats:
                # A fully-padded global batch (weight 0) keeps old stats
                # (grads are a no-op then), mirroring the accum path.
                new_stats = jax.tree_util.tree_map(
                    lambda s, old: jnp.where(
                        metrics["weight"] > 0,
                        psum(s, axes) / total_w,
                        old.astype(jnp.float32)).astype(old.dtype),
                    s_sum, stats)
            else:
                new_stats = stats
            out = (new_params, new_opt, new_stats, metrics)
            if use_ef:
                out += (jax.tree_util.tree_map(lambda r: r[None], ef_l),)
            return out

        in_specs = (rep, opt_specs, rep, batch_specs, rep, rep)
        out_specs = (rep, opt_specs, rep, rep)
        args = [state.params, state.opt_state, state.batch_stats, batch,
                rng, state.step]
        if use_ef:
            in_specs += (P(axes),)
            out_specs += (P(axes),)
            args.append(state.grad_sync["ef"])
        stepped = shard_map(body, mesh, in_specs=in_specs,
                            out_specs=out_specs)
        res = stepped(*args)
        new_params, new_opt, new_stats, metrics = res[:4]
        new_gs = {"ef": res[4]} if use_ef else state.grad_sync
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_stats, opt_state=new_opt,
                                  grad_sync=new_gs)
        return new_state, metrics

    # -- explicit full-parameter FSDP ---------------------------------------

    def _fsdp_unflatten(self, flat_params):
        """Model-shaped params from the flat-sharded at-rest layout via
        plain reshape/slice ops — OUTSIDE shard_map (eval, diagnostics)
        GSPMD inserts the gathers; inside the step the per-layer gather
        does it explicitly. Under explicit TP the at-rest layout is
        model-major (per-shard slices concatenated): split leaves
        re-concatenate along their split dim, replicated leaves take
        copy 0 (all copies bit-identical by construction)."""
        if self._fsdp_template is None:
            raise ValueError(
                "fsdp_explicit state has no unflatten template — build "
                "the state via Trainer.init_state (the flat leaves alone "
                "cannot recover the model shapes)")
        if self._tp_n > 1:
            from jax.sharding import NamedSharding

            # Replicate each flat leaf FIRST: jax 0.4.x GSPMD miscompiles
            # the reshape/slice/concat chain on an input whose dim 0 is
            # sharded over a multi-name axis tuple (wrong data movement,
            # found empirically) — an explicit resharding to replicated is
            # handled correctly and is work the unflatten forces anyway.
            rep = NamedSharding(self.mesh, P())
            return jax.tree_util.tree_map(
                lambda f, t, d: tp_unflatten_leaf(
                    lax.with_sharding_constraint(f, rep), t.shape, t.dtype,
                    d, self._tp_n),
                flat_params, self._fsdp_template, self._tp_split_dims)
        return jax.tree_util.tree_map(
            lambda f, t: f[:int(np.prod(t.shape) or 1)]
            .reshape(t.shape).astype(t.dtype),
            flat_params, self._fsdp_template)

    def _fsdp_step(self, state: TrainState, batch, rng):
        """Explicit full-parameter FSDP (SimpleFSDP, PAPERS.md): params and
        moments live flat-sharded 1/N at rest; the step, inside one
        shard_map over the batch axes, (1) rebuilds the full parameters
        with ONE all-gather per layer group — gathers chained one layer
        ahead via `lax.optimization_barrier`, so gather i+1 waits only on
        gather i (not on any compute) and the scheduler can run it under
        layer i's consumption — (2) computes this replica's local
        gradients against the gathered working copy, (3) reduce-scatters
        each layer's gradient straight into the shard layout
        (`compressed_psum_scatter` on the destination-major group row
        stacking), and (4) updates 1/N of params+moments per replica. The
        new param SHARDS are the step's output — nothing gathers back to
        replicated; the next step's forward re-gathers just-in-time.

        Equivalence scope vs the replicated path, same batch: the zero1
        semantics verbatim (the update pipeline is zero1's with the gather
        moved from epilogue to prologue) — fp32 parity at reassociation
        tolerance, per-shard RNG folds, per-shard BatchNorm statistics.
        Wire modes: bf16/int8 compress the scatter only (int8 with
        per-group error feedback; gathers stay exact fp32, like zero1's);
        "int8_multihop" also compresses the param gathers
        (`quantized_shard_all_gather`: bounded, replica-identical
        perturbation of the gathered WORKING copy — the at-rest shards
        stay exact, so the error does not accumulate into the stored
        parameters; convergence pinned, not parity). "int8_hier" tiers
        both wires over the slice factorization: per-layer scatters run
        the exact fp32 intra-slice reduce-scatter + s8 cross-slice
        exchange with error feedback (`grad_sync.hier_psum_scatter`),
        per-layer gathers ride s8 across slices + exact fp32 inside the
        slice (`grad_sync.hier_shard_all_gather`) — the zero1 hier
        composition applied per layer group, with FAST-MAJOR at-rest rows
        (`HierSpec.hier_axes`). Rejected with explicit TP.
        """
        mesh, accum, n = self.mesh, self.config.grad_accum, self._zero1_n
        axes = BATCH_AXES  # the FSDP wire: gathers/scatters ride data only
        tp = self._tp_n
        # explicit TP: the model axis joins the shard_map (megatron psums
        # bind it); the at-rest dim-0 layout is model-major
        axes_all = ((MODEL,) + BATCH_AXES) if tp > 1 else BATCH_AXES
        task, cfg = self.task, self.config
        wire = self._wire
        hier = self._hier if wire == "int8_hier" else None
        fusedq = cfg.fused_quantize  # tri-state, resolved at trace
        scatter_wire = "int8" if wire == "int8_multihop" else wire
        use_ef = wire in EF_WIRE_DTYPES
        plan = self._fsdp_plan
        if plan is None:
            raise ValueError(
                "fsdp_explicit needs the per-layer plan and unflatten "
                "template — build the state via Trainer.init_state")
        if use_ef and not state.grad_sync:
            raise ValueError(
                f"wire_dtype={wire!r} needs error-feedback buffers — build "
                "the state via Trainer.init_state (TrainState.grad_sync is "
                "empty)")
        if use_ef:
            for g in plan.groups:
                got = state.grad_sync["ef"][g.name].shape[-1]
                # hier: one slow-tier residual per replica per group —
                # the padded group row divided by the intra-slice degree
                expect = (n * g.row_size
                          // (hier.n_inner if hier is not None else 1))
                if got != expect:
                    raise ValueError(
                        f"error-feedback residual for layer group "
                        f"{g.name!r} has {got} elements, expected {expect} "
                        "— the state was built for a different model/mesh; "
                        "rebuild via Trainer.init_state")
        has_stats = bool(jax.tree_util.tree_leaves(state.batch_stats))
        if tp > 1:
            # the body computes with the TP-local model (megatron
            # column/row split, model-axis psums via the custom_vjp f/g
            # operators in parallel/collectives.py)
            outer = state.replace(apply_fn=self._tp_model.apply)
        else:
            outer = state  # static fields (apply_fn/tx) for inner rebuild
        local_template = self._fsdp_local_template
        template_leaves = jax.tree_util.tree_leaves(local_template)
        treedef = jax.tree_util.tree_structure(local_template)
        leaf_sizes = self._fsdp_sizes  # host-precomputed (init_state)

        rep = P()
        batch_specs = jax.tree_util.tree_map(
            lambda x: batch_spec(jnp.ndim(x)), batch)
        # hier wire: at-rest rows bind FAST-MAJOR (the scatter's chunk
        # ownership — see _zero1_step), so dim 0 shards over
        # fast_axes+(slice,) instead of the batch-axis order
        rest_axes = hier.hier_axes if hier is not None else axes_all
        param_specs = dp_flat_specs(state.params, axes=rest_axes)
        opt_specs = dp_flat_specs(state.opt_state, axes=rest_axes)

        def body(p_shards, opt_state, stats, lbatch, key, step, *maybe_ef):
            idx = lax.axis_index(axes)
            # per-group residuals, (1, G) local row -> (G,)
            ef_l = ({name: r[0] for name, r in maybe_ef[0].items()}
                    if use_ef else None)
            shard_leaves = treedef.flatten_up_to(p_shards)

            # -- per-layer just-in-time gather (the prologue) -------------
            full = [None] * len(template_leaves)
            prev = None
            for g in plan.groups:
                row = (jnp.concatenate([shard_leaves[s].astype(jnp.float32)
                                        for s in g.leaf_slots])
                       if len(g.leaf_slots) > 1
                       else shard_leaves[g.leaf_slots[0]]
                       .astype(jnp.float32))
                if prev is not None:
                    # prefetch chain: gather i+1 depends on gather i's
                    # COMPLETION only — never on layer i's compute — so
                    # the latency-hiding scheduler can issue it while
                    # layer i is being consumed, one layer ahead
                    row = lax.optimization_barrier((row, prev))[0]
                if wire == "int8_multihop":
                    flatg = quantized_shard_all_gather(row, axes,
                                                       fused=fusedq)
                elif hier is not None:
                    # s8 across slices, exact fp32 inside — slice first,
                    # fast second, inverting fast-major row ownership
                    flatg = hier_shard_all_gather(row, hier, fused=fusedq)
                else:
                    flatg = all_gather(row, axes)
                prev = flatg
                mat = flatg.reshape(n, g.row_size)
                off = 0
                for s, c in zip(g.leaf_slots, g.chunk_sizes):
                    t = template_leaves[s]
                    full[s] = (mat[:, off:off + c].reshape(-1)
                               [:leaf_sizes[s]]
                               .reshape(t.shape).astype(t.dtype))
                    off += c
            params = jax.tree_util.tree_unflatten(treedef, full)
            inner = outer.replace(step=step, params=params,
                                  batch_stats=stats, opt_state=opt_state)

            def micro_grads(mb, k):
                def loss_fn(p):
                    return task.loss_and_metrics(inner, p, mb, k, train=True)

                return jax.grad(loss_fn, has_aux=True)(params)

            def scatter_layers(gtree, ef_tree, into=None):
                """Per-layer compressed reduce-scatter of the w-scaled
                grad tree straight into the shard layout: returns
                (per-leaf chunk tree [+= into], new per-group ef dict)."""
                g_leaves = treedef.flatten_up_to(gtree)
                into_leaves = (treedef.flatten_up_to(into)
                               if into is not None else None)
                outs = [None] * len(g_leaves)
                new_ef = {}
                for g in plan.groups:
                    # destination-major stacking: row j = concat of every
                    # member leaf's chunk j, so the scatter lands each
                    # leaf's chunk on its owner in one collective
                    parts = [
                        flatten_pad(g_leaves[s].astype(jnp.float32), n)
                        .reshape(n, -1)
                        for s in g.leaf_slots]
                    v = (jnp.concatenate(parts, axis=1)
                         if len(parts) > 1 else parts[0]).reshape(-1)
                    r = ef_tree[g.name] if use_ef else None
                    if hier is not None:
                        s_out, nr = hier_psum_scatter(v, hier, r,
                                                      fused=fusedq)
                    else:
                        s_out, nr = compressed_psum_scatter(
                            v, axes, n, scatter_wire, r, fused=fusedq)
                    off = 0
                    for s, c in zip(g.leaf_slots, g.chunk_sizes):
                        chunk = lax.slice_in_dim(s_out, off, off + c)
                        outs[s] = (into_leaves[s] + chunk
                                   if into is not None else chunk)
                        off += c
                    if use_ef:
                        new_ef[g.name] = nr
                return (jax.tree_util.tree_unflatten(treedef, outs),
                        new_ef if use_ef else None)

            if accum <= 1:
                key = jax.random.fold_in(key, idx)
                g, (m, stats_l) = micro_grads(lbatch, key)
                w = m["weight"]
                g_sum, ef_l = scatter_layers(
                    jax.tree_util.tree_map(lambda a: w * a, g), ef_l)
                s_sum = (jax.tree_util.tree_map(
                    lambda s: w * s.astype(jnp.float32), stats_l)
                    if has_stats else stats)
                m_local = m
            else:
                # zero1's in-scan accumulation verbatim: the carry holds
                # per-leaf gradient SHARDS (1/N the replicated buffer),
                # and each microbatch's scatter overlaps the next
                # microbatch's compute
                micro_batches = split_microbatches(lbatch, accum)
                keys = jax.random.split(key, accum)

                def mb_body(carry, xs):
                    g_sum, s_sum, m_sum, ef_c = carry
                    mb, k = xs
                    g, (m, stats_mb) = micro_grads(
                        mb, jax.random.fold_in(k, idx))
                    w = m["weight"]
                    g_sum, ef_c = scatter_layers(
                        jax.tree_util.tree_map(lambda b: w * b, g), ef_c,
                        into=g_sum)
                    if has_stats:
                        s_sum = jax.tree_util.tree_map(
                            lambda a, b: a + w * b.astype(a.dtype),
                            s_sum, stats_mb)
                    m_sum = add_metrics(m_sum, m)
                    return (g_sum, s_sum, m_sum, ef_c), None

                g0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), p_shards)
                s0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), stats)
                (g_sum, s_sum, m_local, ef_l), _ = lax.scan(
                    mb_body, (g0, s0, zero_metrics(), ef_l),
                    (micro_batches, keys))

            metrics = jax.tree_util.tree_map(
                lambda v: psum(v, axes), m_local)
            total_w = jnp.maximum(metrics["weight"], 1.0)
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / total_w).astype(p.dtype), g_sum, p_shards)

            # 1/N of the optimizer update, on the at-rest shards — the
            # zero1 core, minus its epilogue gather: the new shards ARE
            # the output layout
            with jax.named_scope("optimizer"):
                updates, new_opt = outer.tx.update(grads, opt_state,
                                                   p_shards)
                new_p_shards = optax.apply_updates(p_shards, updates)

            if has_stats:
                new_stats = jax.tree_util.tree_map(
                    lambda s, old: jnp.where(
                        metrics["weight"] > 0,
                        psum(s, axes) / total_w,
                        old.astype(jnp.float32)).astype(old.dtype),
                    s_sum, stats)
            else:
                new_stats = stats
            out = (new_p_shards, new_opt, new_stats, metrics)
            if use_ef:
                out += ({name: r[None] for name, r in ef_l.items()},)
            return out

        in_specs = (param_specs, opt_specs, rep, batch_specs, rep, rep)
        out_specs = (param_specs, opt_specs, rep, rep)
        args = [state.params, state.opt_state, state.batch_stats, batch,
                rng, state.step]
        if use_ef:
            ef_specs = jax.tree_util.tree_map(lambda _: P(axes_all),
                                              state.grad_sync["ef"])
            in_specs += (ef_specs,)
            out_specs += (ef_specs,)
            args.append(state.grad_sync["ef"])
        stepped = shard_map(body, mesh, in_specs=in_specs,
                            out_specs=out_specs)
        res = stepped(*args)
        new_params, new_opt, new_stats, metrics = res[:4]
        new_gs = {"ef": res[4]} if use_ef else state.grad_sync
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  batch_stats=new_stats, opt_state=new_opt,
                                  grad_sync=new_gs)
        return new_state, metrics

    def _eval_step_impl(self, state: TrainState, batch):
        rng = jax.random.PRNGKey(0)  # unused: eval has no augmentation (ref :98-101)
        params = (self._fsdp_unflatten(state.params) if self._fsdp
                  else state.params)
        _, (metrics, _) = self.task.loss_and_metrics(
            state, params, batch, rng, train=False)
        return metrics

    # -- state construction ------------------------------------------------

    def init_state(self, model, sample_input, tx, init_rng: jax.Array) -> TrainState:
        """Initialize params, then place them on the mesh per the partition
        rules (replicated by default — the DDP broadcast moment, ref :305-310).
        `sample_input` is a (1, ...) array of the model's input shape/dtype
        (float images or int32 token ids)."""
        from ..parallel.mesh import batch_shard_count

        x = jnp.asarray(sample_input)
        # Models containing shard_map'd ops (ring attention) need the traced
        # batch dim divisible by the mesh batch axes; tile the sample up.
        n_shards = batch_shard_count(self.mesh)
        if x.shape[0] % n_shards:
            x = jnp.tile(x, (n_shards,) + (1,) * (x.ndim - 1))
        variables = model.init(init_rng, x, train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        # int8 gradient wires: zero-initialized error-feedback residuals,
        # attached AFTER mesh placement (they carry their own per-replica
        # sharding; the rules would replicate them). zero1 feeds back on
        # its scatter half under both int8 forms ("int8_multihop" scatters
        # via the same s8 all-to-all; only its param gather differs).
        use_ef = (self._wire in EF_WIRE_DTYPES
                  and (self._zero1 or self._grad_sync or self._fsdp))
        hier = self._hier if self._wire == "int8_hier" else None
        n_inner = hier.n_inner if hier is not None else 1
        if self._fsdp:
            # Explicit FSDP: params AND moments are born in the zero1 flat
            # padded layout, 1/N per replica at rest — the at-rest memory
            # division that is the mode's point. The model-shaped template
            # (shapes/dtypes only, host-side) is what the step's per-layer
            # gather unflattens against. With a model axis (explicit TP,
            # ISSUE 13) the layout is model-major: each leaf's TP-local
            # slice (or full copy, for model-replicated leaves) flat-padded
            # per model shard — 1/(N*M) at rest for every TP-split tensor.
            from .optim import zero1_opt_state

            n, tp = self._zero1_n, self._tp_n
            self._fsdp_template = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(jnp.shape(p),
                                               jnp.result_type(p)), params)
            if tp > 1:
                import dataclasses as _dc

                field_names = {f.name for f in _dc.fields(type(model))}
                if not {"tp_size", "tp_axis"} <= field_names:
                    raise ValueError(
                        f"mesh has model={tp} under fsdp_explicit, but "
                        f"{type(model).__name__} has no explicit-TP form "
                        "(tp_size/tp_axis fields) — gpt2_* models support "
                        "explicit TP; others need a 1-D mesh or the "
                        "implicit GSPMD path")
                heads = getattr(model, "num_heads", None)
                if heads is not None and heads % tp:
                    # the TP module raises the same at trace time; failing
                    # here keeps the error at state construction
                    raise ValueError(
                        f"num_heads={heads} not divisible by the mesh's "
                        f"model={tp} — explicit TP splits attention by "
                        "whole heads")
                rules = self.rules
                if rules is None and hasattr(type(model), "partition_rules"):
                    rules = type(model).partition_rules()
                if rules is None:
                    raise ValueError(
                        "explicit TP derives its layout from the model's "
                        "partition rules (tp_fsdp_rules) — pass rules= or "
                        "give the model a partition_rules() classmethod")
                self._tp_split_dims = tp_split_dims(self._fsdp_template,
                                                    rules, tp)
                self._tp_model = model.clone(tp_size=tp, tp_axis=MODEL)
                local_template = tp_local_struct(self._fsdp_template,
                                                 self._tp_split_dims, tp)
            else:
                local_template = self._fsdp_template
            self._fsdp_local_template = local_template
            # host-side leaf sizes (tree_leaves order) for the in-step
            # unflatten slicing — precomputed here so the traced step does
            # no int() shape math (the no-host-sync-in-step lint's scope)
            self._fsdp_sizes = tuple(
                int(np.prod(t.shape) or 1) for t in
                jax.tree_util.tree_leaves(local_template))
            self._fsdp_plan = build_layer_plan(local_template, n)
            if tp > 1:
                axes_all = (MODEL,) + BATCH_AXES
                split_dims = self._tp_split_dims
                opt_state = zero1_opt_state(
                    tx, params, self.mesh,
                    flatten_tree_fn=lambda p: jax.tree_util.tree_map(
                        lambda x, d: tp_flat_leaf(x, d, tp, n),
                        p, split_dims),
                    axes=axes_all)
                flat_params = fsdp_tp_flat_params(
                    params, self.mesh, n, tp, split_dims, axes_all)
            else:
                # hier wire: moments born in the fast-major row binding
                # the step's specs use (params reshard once, first step)
                opt_state = zero1_opt_state(
                    tx, params, self.mesh,
                    axes=hier.hier_axes if hier is not None else None)
                flat_params = fsdp_flat_params(params, self.mesh, n)
            state = TrainState.create(
                apply_fn=model.apply, params=params, tx=tx,
                batch_stats=batch_stats, opt_state=opt_state)
            placed = shard_pytree(state.replace(params={}, opt_state={}),
                                  self.mesh, None)
            placed = placed.replace(params=flat_params, opt_state=opt_state)
            if use_ef:
                placed = placed.replace(grad_sync=ef_state_fsdp(
                    local_template, self.mesh, n, model_n=tp,
                    n_inner=n_inner))
            return placed
        if self._zero1 or self._zero1_gspmd:
            # Params stay replicated (the DDP layout — zero1 shards only
            # the UPDATE); the optimizer state is born flat-padded-sharded
            # over the batch axes, 1/N per replica.
            from .optim import zero1_opt_state

            opt_state = zero1_opt_state(
                tx, params, self.mesh,
                axes=hier.hier_axes if (hier is not None and self._zero1)
                else None)
            state = TrainState.create(
                apply_fn=model.apply, params=params, tx=tx,
                batch_stats=batch_stats, opt_state=opt_state)
            placed = shard_pytree(state.replace(opt_state={}), self.mesh,
                                  self.rules)
            placed = placed.replace(opt_state=opt_state)
            if use_ef:
                placed = placed.replace(grad_sync=ef_state_zero1(
                    params, self.mesh, self._zero1_n, n_inner=n_inner))
            return placed
        state = TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, batch_stats=batch_stats)
        placed = shard_pytree(state, self.mesh, self.rules)
        if use_ef:
            placed = placed.replace(grad_sync=ef_state_bucketed(
                params, self.mesh, self._zero1_n,
                bucket_cap_mb=self.config.bucket_cap_mb,
                wire_dtype=self._wire,
                n_slices=hier.n_slices if hier is not None else 1))
        return placed

    # -- epoch loops -------------------------------------------------------

    def train_epoch(
        self,
        state: TrainState,
        batches: Iterable,
        epoch: int,
        steps_per_epoch: int,
        samples_per_step: Optional[Sequence[int]] = None,
        step_hook: Optional[Any] = None,
        start_step: int = 0,
        stop_fn: Optional[Any] = None,
        fault_hook: Optional[Any] = None,
    ) -> Tuple[TrainState, float, float, float, int]:
        """One epoch (maps train_one_epoch, ref :170-263). Returns
        (state, global mean loss, global top-1 %, epoch wall seconds,
        steps executed). `step_hook(step_index)` fires before each step
        (profiler windows). `start_step` labels a mid-epoch resume (the
        caller hands an already-offset batch iterator; the per-step RNG is
        folded from state.step, so the restored trajectory is identical).
        `stop_fn()` checked after every step: True breaks the loop — the
        step-granular preemption point (steps executed < full epoch).
        `fault_hook(step_index)` is the resilience/ step fence: it fires
        BEFORE the step executes (so a raise there means the optimizer
        never applied the step — the restart supervisor's restore point)
        and is None on every un-supervised run (the hot path pays
        nothing).

        Telemetry (host-side only — nothing here touches traced code, and
        the ``telemetry-emit-outside-traced`` AST rule keeps it that way):
        per-step ``data_wait`` (time blocked on the loader iterator) and
        ``step_dispatch`` (time inside the jitted-call dispatch — with
        donation backpressure this tracks device step time once the
        pipeline fills) spans, a ``device_sync`` span around the epoch's
        one block_until_ready, and epoch counters (``epoch_time_s``,
        ``steps``, ``samples``) — the totals ``telemetry summary`` checks
        its split against. ``self.watchdog`` (an AnomalyWatchdog) is fed
        the same timings plus print-boundary losses; with its abort hook
        on, a detection raises AnomalyAbort — under the Supervisor, a
        restartable step failure like any other."""
        cfg = self.config
        epoch_key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), epoch)

        epoch_metrics = None   # the first step's metrics name the sums
        counters_seen = (0, {})   # (steps, sums) at the last print boundary
        # perf_counter, not time.time(): an NTP step mid-epoch would
        # corrupt the CSV's epoch_time_seconds (the ThroughputMeter got
        # the same fix)
        t_epoch = time.perf_counter()
        meter = ThroughputMeter()
        steps_done = 0
        epoch_samples = 0
        watchdog = self.watchdog

        it = iter(batches)
        i = 0
        while True:
            t_wait = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                break
            data_wait_s = time.perf_counter() - t_wait
            telemetry.span_event("data_wait", data_wait_s,
                                 step=start_step + i, epoch=epoch)
            if fault_hook is not None:
                fault_hook(i)
            if step_hook is not None:
                # the GLOBAL step label (start_step + i) — the same
                # numbering the spans, the watchdog, and the straggler
                # table use, so an armed capture window's step range can
                # be lined up against a flagged step on a mid-epoch
                # resume (the profiler's static window triggers on its
                # own call count, not this label)
                step_hook(start_step + i)
            t_disp = time.perf_counter()
            state, metrics = self._train_step(state, batch, epoch_key)
            dispatch_s = time.perf_counter() - t_disp
            telemetry.span_event("step_dispatch", dispatch_s,
                                 step=start_step + i, epoch=epoch)
            if watchdog is not None:
                watchdog.observe_step(start_step + i,
                                      data_wait_s + dispatch_s,
                                      data_wait_s=data_wait_s)
            epoch_metrics = metrics if epoch_metrics is None \
                else add_metrics(epoch_metrics, metrics)
            steps_done = i + 1
            # sample count is host-known (sampler math), no device fetch:
            if samples_per_step is not None:
                n = samples_per_step[min(i, len(samples_per_step) - 1)]
                meter.update(n)
                epoch_samples += n

            if (i + 1) % cfg.print_freq == 0:
                # Host fetch happens only here (print boundary), mirroring the
                # reference cadence (ref :229-243) without its per-step syncs.
                # Like the reference, the printed loss/acc are the epoch
                # running averages (ref :230-231).
                avg_loss, avg_acc = summarize(epoch_metrics)
                counters_seen = self._emit_step_counters(
                    epoch_metrics, steps_done, counters_seen,
                    step=start_step + i, epoch=epoch)
                if watchdog is not None:
                    # the loop's only host fetch — the non-finite-loss
                    # detector rides it instead of adding a sync
                    watchdog.observe_loss(start_step + i, avg_loss)
                rate = meter.rate()
                mfu = ""
                if self._flops_per_sample and self._peak_flops_total:
                    mfu_pct = (100.0 * rate * self._flops_per_sample
                               / self._peak_flops_total)
                    mfu = f"  MFU: {mfu_pct:.1f}%"
                log_main(
                    f"Epoch [{epoch + 1}] "
                    f"Step [{start_step + i + 1}/{steps_per_epoch}] "
                    f"Loss: {avg_loss:.4f}  "
                    f"Acc: {avg_acc:.2f}%  "
                    f"Throughput: {rate:.2f} samples/s (global)" + mfu
                )
                meter.reset()

            if stop_fn is not None and stop_fn():
                break
            i += 1

        # Epoch totals: weighted sums are already global (the batch was the
        # global batch) — the reference needs 3 all-reduces here (ref :251-253);
        # we need none.
        if epoch_metrics is None:
            epoch_metrics = zero_metrics()
        with telemetry.span("device_sync", epoch=epoch):
            jax.block_until_ready(epoch_metrics["weight"])
        epoch_time = time.perf_counter() - t_epoch
        telemetry.counter("epoch_time_s", epoch_time, epoch=epoch)
        telemetry.counter("steps", steps_done, epoch=epoch)
        if epoch_samples:
            telemetry.counter("samples", epoch_samples, epoch=epoch)
        loss, acc = summarize(epoch_metrics)
        return state, loss, acc, epoch_time, steps_done

    @staticmethod
    def _emit_step_counters(epoch_metrics, steps_done, seen, **attrs):
        """At a print boundary, what the model's counters (`tasks.
        step_counters`) added since the last one: a name ending in
        ``_max_over_mean`` as a gauge of its mean over those steps, any other
        as a counter of its total with the number of steps beside it. Read
        from the sums the boundary fetches anyway; a model without counters
        costs nothing here."""
        sums = epoch_metrics.get("counters")
        if not sums or not telemetry.is_configured():
            return seen
        steps_before, before = seen
        steps = steps_done - steps_before
        now = {name: float(total) for name, total in sums.items()}
        for name, total in now.items():
            added = total - before.get(name, 0.0)
            if name.endswith("_max_over_mean"):
                telemetry.gauge(name, added / max(steps, 1), **attrs)
            else:
                telemetry.counter(name, added, steps=steps, **attrs)
        return steps_done, now

    def evaluate(self, state: TrainState, batches: Iterable) -> Tuple[float, float]:
        """Sharded validation (maps validate, ref :266-300)."""
        with telemetry.span("eval"):
            totals = None   # the first step's metrics name the sums
            for batch in batches:
                metrics = self._eval_step(state, batch)
                totals = metrics if totals is None \
                    else add_metrics(totals, metrics)
            return summarize(zero_metrics() if totals is None else totals)

"""Scaling / mixed-precision / gradient-sync experiments.

Produces, with data, every table the reference's README sketches as an empty
outline (/root/reference/README.md:27-35):

* ``scaling``  — global throughput and linear-scaling efficiency on 1..N-chip
  data-parallel meshes (the "Single vs multi-GPU" table; the BASELINE north
  star is >=90% efficiency at 8 chips).
* ``batch``    — throughput vs per-device batch size.
* ``amp``      — bf16 vs fp32 step time (the "AMP vs FP32" comparison; on TPU
  bf16 replaces CUDA AMP, no GradScaler — SURVEY.md §2b).
* ``zero1``    — replicated vs ZeRO-1 sharded weight update (reduce-scatter
  grads, 1/N optimizer update per replica, all-gather params — Xu et al.,
  PAPERS.md) on the same data-parallel mesh, with the static weight-update
  census proving which collectives each compiled step actually runs.
* ``grad_sync`` — the explicit bucketed/compressed reducer
  (parallel/grad_sync.py, the native DDP-reducer rebuild) vs the implicit
  XLA path: throughput, the static bucket/wire-dtype census of each
  compiled step, and the trace-derived exposed-comm fraction (overlap
  efficiency) per mode.
* ``hier``    — two-tier topology-aware sync (wire_dtype="int8_hier") on a
  slice=2 tiered mesh vs the flat wires: tier-classified collective census
  + per-tier wire bytes (the slow-tier slice-count-independence claim as
  recorded numbers).
* ``gradsync`` — the gradient-synchronization share of step time (the
  README's literal "~X%" placeholder, README.md:35). Three instruments:
  (a) measured: per-device-constant-batch step time on 1 chip vs N chips —
      the extra time at N is the communication/sync overhead DDP hides in
      hooks and XLA hides in fused collectives;
  (b) static: a census of collective ops (all-reduce/all-gather/...) in the
      optimized HLO of the compiled step, with operand bytes — read from the
      compiled executable the way the reference would read an nsys timeline;
  (c) trace-derived: a jax.profiler capture parsed by
      telemetry/trace_analysis.py, collective time summed against XLA-op
      busy time.
* ``pipeline`` — GPipe bubble measurement: pipelined-GPT-2 throughput vs
  microbatch count against the pure-DP layout of the same model
  (bubble fraction (P-1)/(M+P-1); parallel/pipeline.py).

Output: a markdown table on stdout + rows appended to a CSV so the scaling
plots can be regenerated. Honest-measurement notes: on a single host the
"chips" are members of one mesh (real ICI collectives on TPU, ring emulation
on the CPU test backend); multi-host DCN numbers require a pod run.
"""

from __future__ import annotations

import argparse
import csv as csv_mod
import sys
import time
from pathlib import Path
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.hlo_rules import collective_census, weight_update_census
from ..models.registry import is_lm_model
from ..runtime import require_backend
# One recipe for every table here (experiments/harness.py): the
# image-vs-LM dispatch (build_trainer / make_synth_batch) and the
# differenced timing windows.
from .harness import build_trainer, make_synth_batch, timed_steps

# CI smoke runs shrink LM architectures (full-size bert/gpt2 on the CPU test
# mesh costs minutes per build); real measurements never set this.
_LM_TINY = dict(hidden_dim=64, depth=2, num_heads=2, mlp_dim=128)


def _setup(devices, bf16: bool, args, per_device_batch=None, zero1=False,
           grad_sync=None):
    """(trainer, state, mesh, batch, global_batch) for args' config — the
    trainer and its batch are built together so they can never mismatch."""
    lm_kw = None
    if args.lm_tiny and is_lm_model(args.model):
        lm_kw = dict(_LM_TINY)
        if args.model.startswith("gpt2"):
            lm_kw.pop("mlp_dim")  # gpt2 derives mlp from hidden_dim
    trainer, state, mesh = build_trainer(devices, bf16, args.model,
                                         args.seq_len, lm_overrides=lm_kw,
                                         zero1=zero1, grad_sync=grad_sync)
    batch, gb = make_synth_batch(mesh, args.model,
                                 per_device_batch or args.batch_size,
                                 args.seq_len)
    return trainer, state, mesh, batch, gb


def _measure(trainer, state, batch, global_batch: int, args) -> Tuple[float, float]:
    """(steps/sec, samples/sec) for the jitted train step."""
    sps, samples = timed_steps(trainer._train_step, state, batch,
                               global_batch, args.steps,
                               repeats=args.repeats,
                               min_window_s=args.min_window_s)
    return sps, samples


def _emit(rows: List[dict], csv_path: Optional[str]) -> None:
    if not rows:
        return
    cols = list(rows[0].keys())
    widths = [max(len(str(r.get(c, ""))) for r in rows + [dict(zip(cols, cols))])
              for c in cols]
    line = "| " + " | ".join(c.ljust(w) for c, w in zip(cols, widths)) + " |"
    sep = "|-" + "-|-".join("-" * w for w in widths) + "-|"
    print(line)
    print(sep)
    for r in rows:
        print("| " + " | ".join(str(r.get(c, "")).ljust(w)
                                for c, w in zip(cols, widths)) + " |")
    if csv_path:
        path = Path(csv_path)
        path.parent.mkdir(parents=True, exist_ok=True)
        new = not path.exists()
        with open(path, "a", newline="") as f:
            w = csv_mod.DictWriter(f, fieldnames=cols)
            if new:
                w.writeheader()
            w.writerows(rows)
        print(f"\n(rows appended to {path})")


def run_scaling(args) -> List[dict]:
    devices = jax.devices()
    counts = [c for c in (1, 2, 4, 8, 16) if c <= len(devices)]
    rows = []
    base = None
    for c in counts:
        trainer, state, _, batch, gb = _setup(devices[:c], args.bf16, args)
        _, sps = _measure(trainer, state, batch, gb, args)
        base = base or sps
        rows.append({
            "chips": c,
            "global_samples_per_s": round(sps, 1),
            "per_chip_samples_per_s": round(sps / c, 1),
            "scaling_efficiency_pct": round(100.0 * sps / (base * c), 1),
        })
    return rows


def run_batch_sweep(args) -> List[dict]:
    devices = jax.devices()
    rows = []
    batches = (tuple(int(b) for b in args.batch_list.split(","))
               if args.batch_list else (32, 64, 128, 256, 512))
    for b in batches:
        trainer, state, _, batch, gb = _setup(devices, args.bf16, args,
                                              per_device_batch=b)
        _, sps = _measure(trainer, state, batch, gb, args)
        rows.append({"per_device_batch": b,
                     "global_samples_per_s": round(sps, 1)})
    return rows


def run_amp(args) -> List[dict]:
    devices = jax.devices()
    rows = []
    sps_by_prec = {}
    for bf16 in (False, True):
        trainer, state, _, batch, gb = _setup(devices, bf16, args)
        _, sps = _measure(trainer, state, batch, gb, args)
        sps_by_prec[bf16] = sps
        rows.append({"precision": "bf16" if bf16 else "fp32",
                     "global_samples_per_s": round(sps, 1)})
    rows.append({"precision": "bf16_speedup",
                 "global_samples_per_s":
                     round(sps_by_prec[True] / sps_by_prec[False], 3)})
    return rows


def run_gradsync(args) -> List[dict]:
    devices = jax.devices()
    n = len(devices)
    rows = []

    # (a) measured: constant per-device batch, 1 chip vs N chips
    trainer1, state1, _, batch1, gb1 = _setup(devices[:1], args.bf16, args)
    step1, _ = _measure(trainer1, state1, batch1, gb1, args)
    t1 = 1.0 / step1
    rows.append({"measurement": "step_time_1chip_ms", "value": round(t1 * 1e3, 3)})
    if n > 1:
        trainerN, stateN, _, batchN, gbN = _setup(devices, args.bf16, args)

        # (b) static: collective census of the compiled N-chip step.
        # Lower/compile BEFORE the timed run: _measure runs the donating
        # jitted step on stateN, after which its buffers are deleted on
        # backends that honor donation (TPU) — lowering afterwards would
        # depend on donated-away state (ADVICE r1).
        compiled = trainerN._train_step.lower(
            stateN, batchN, jax.random.PRNGKey(0)).compile()

        stepN, _ = _measure(trainerN, stateN, batchN, gbN, args)
        tN = 1.0 / stepN
        share = max(0.0, 1.0 - t1 / tN)
        rows.append({"measurement": f"step_time_{n}chip_ms",
                     "value": round(tN * 1e3, 3)})
        rows.append({"measurement": "grad_sync_share_1vsN_pct",
                     "value": round(100.0 * share, 1)})

        # (c) trace-derived: the jax.profiler timeline read-off the README
        # placeholder calls for (README.md:35). Fresh state: _measure donated
        # stateN's buffers.
        import tempfile

        from ..telemetry.trace_analysis import (
            capture_step_trace, collective_share,
        )

        trainerT, stateT, _, batchT, _gbT = _setup(devices, args.bf16, args)
        keyT = jax.random.PRNGKey(0)
        stateT, _ = trainerT._train_step(stateT, batchT, keyT)  # warmup
        with tempfile.TemporaryDirectory(prefix="gradsync_trace_") as td:
            capture_step_trace(trainerT._train_step, stateT, batchT, keyT,
                               td, steps=max(3, min(args.steps, 10)))
            trace = collective_share(td)
        rows.append({"measurement": "grad_sync_share_trace_pct",
                     "value": trace["share_pct"]})
        rows.append({"measurement": "trace_collective_ms",
                     "value": round(trace["collective_us"] / 1e3, 3)})
        rows.append({"measurement": "trace_xla_op_ms",
                     "value": round(trace["op_us"] / 1e3, 3)})
        print("\nTrace-derived collective time by op (jax.profiler):")
        for op, us in trace["by_op"].items() or {"(none)": 0.0}.items():
            print(f"  {op:<20} {us / 1e3:.3f} ms")

        census = collective_census(compiled.as_text())
        print("\nCollective ops in the compiled train step "
              "(the DDP reducer's all-reduces, as XLA scheduled them):")
        for c in census:
            print(f"  {c['count']:>3}x {c['op']:<20} {c['result_shape']}")
        if not census:
            print("  (none — single-device or fully fused)")
    return rows


def run_zero1(args) -> List[dict]:
    """Replicated vs ZeRO-1 sharded weight update on the same devices.

    The experiment the zero1 flag exists for (Xu et al., PAPERS.md): same
    model, same data-parallel mesh, once with the replicated DDP-style
    update and once with reduce-scatter/sharded-update/all-gather. Reports
    throughput plus the static weight-update census of each compiled step —
    the census must show the gradient all-reduces GONE in the zero1 arm
    (replaced by reduce-scatter + all-gather), or the mode is silently not
    engaged and the throughput comparison measures nothing.
    """
    devices = jax.devices()
    if len(devices) < 2:
        return [{"update": "skipped",
                 "global_samples_per_s": "needs >= 2 devices"}]
    rows = []
    sps_by_mode = {}
    for zero1 in (False, True):
        trainer, state, _, batch, gb = _setup(devices, args.bf16, args,
                                              zero1=zero1)
        # Lower/compile BEFORE the timed run (donation deletes state buffers
        # on backends that honor it — same ordering as run_gradsync).
        compiled = trainer._train_step.lower(
            state, batch, jax.random.PRNGKey(0)).compile()
        census = weight_update_census(compiled.as_text())
        _, sps = _measure(trainer, state, batch, gb, args)
        sps_by_mode[zero1] = sps
        rows.append({
            "update": "zero1" if zero1 else "replicated",
            "global_samples_per_s": round(sps, 1),
            "grad_all_reduce": census["all-reduce"],
            "reduce_scatter": census["reduce-scatter"],
            "all_gather": census["all-gather"],
        })
    rows.append({"update": "zero1_speedup",
                 "global_samples_per_s":
                     round(sps_by_mode[True] / sps_by_mode[False], 3),
                 "grad_all_reduce": "", "reduce_scatter": "",
                 "all_gather": ""})
    return rows


def run_grad_sync(args) -> List[dict]:
    """The explicit reducer (parallel/grad_sync.py) vs the implicit XLA
    path on the same devices: bucketed fp32, bf16, int8+EF and multi-hop
    int8 wire, each row carrying (a) throughput, (b) the static
    `grad_sync_census` of the compiled step — gradient-sized collective
    count and wire dtypes, the proof the mode is engaged — (c) the
    `wire_bytes_per_replica` accounting of the mode (the gather-form int8's
    ~(n-1)·S growth and the multihop form's flat ~2·S as RECORDED numbers,
    not docstring claims), and (d) the trace-derived exposed-comm fraction
    (`comm_overlap_split`), the overlap-efficiency number DDP users read
    off nsys timelines. `--bucket-cap-mb` sets the cap (default 25, DDP's
    default); `--grad-accum` > 1 exercises the in-scan overlap (plus a
    no-overlap arm isolating its win).
    """
    from ..analysis.hlo_rules import grad_sync_census, preopt_hlo_text
    from ..parallel.grad_sync import wire_bytes_for_config
    from ..parallel.mesh import batch_shard_count
    from .harness import trace_exposed_comm

    devices = jax.devices()
    if len(devices) < 2:
        return [{"mode": "skipped",
                 "global_samples_per_s": "needs >= 2 devices"}]
    cap = args.bucket_cap_mb
    accum = args.grad_accum
    modes = [("implicit", None),
             ("bucketed_fp32", dict(bucket_cap_mb=cap))]
    if accum > 1:
        modes.append(("bucketed_fp32_no_overlap",
                      dict(bucket_cap_mb=cap, overlap_grad_sync=False)))
    modes += [("bucketed_bf16", dict(bucket_cap_mb=cap, wire_dtype="bf16")),
              ("bucketed_int8", dict(bucket_cap_mb=cap, wire_dtype="int8")),
              ("bucketed_int8_multihop",
               dict(bucket_cap_mb=cap, wire_dtype="int8_multihop"))]

    rows = []
    for mode, gs in modes:
        gs_full = dict(gs or {}, grad_accum=accum) if (gs or accum > 1) \
            else gs
        trainer, state, mesh, batch, gb = _setup(devices, args.bf16, args,
                                                 grad_sync=gs_full)
        key = jax.random.PRNGKey(0)
        lowered = trainer._train_step.lower(state, batch, key)
        compiled = lowered.compile()
        census = grad_sync_census(compiled.as_text())
        # wire read: pre-optimization HLO (bf16 survives only there on CPU)
        # — except for the implicit mode, whose collectives are inserted by
        # GSPMD during compilation and don't exist pre-optimization
        wire = census["wire_dtypes"]
        try:
            pre = grad_sync_census(preopt_hlo_text(lowered))["wire_dtypes"]
            if pre:
                wire = pre
        except Exception:
            pass

        # time the SAME executable the census describes (AOT `compiled` —
        # re-timing trainer._train_step would pay a second compile AND
        # measure a different program than the one censused)
        _, sps = timed_steps(compiled, state, batch, gb, args.steps,
                             repeats=args.repeats,
                             min_window_s=args.min_window_s)

        # trace the same config with a sacrificial trainer/state (the
        # timed run donated this one's buffers)
        def _sacrificial(gs=gs_full):
            tr, st, _, ba, _ = _setup(devices, args.bf16, args, grad_sync=gs)
            return tr, st, ba

        exposed = trace_exposed_comm(_sacrificial, key=key)
        # the mode's per-replica wire accounting: the implicit path syncs
        # the same gradient bytes an uncapped fp32 reducer would
        wire_bytes = wire_bytes_for_config(state.params, gs_full,
                                           batch_shard_count(mesh))
        rows.append({
            "mode": mode,
            "global_samples_per_s": round(sps, 1),
            "grad_collectives": census["n_collectives"],
            "wire_dtypes": "+".join(sorted(wire)) or "-",
            "wire_bytes_per_replica": wire_bytes,
            "exposed_comm_pct": exposed if exposed is not None else "-",
        })
    return rows


def run_hier(args) -> List[dict]:
    """Two-tier topology-aware gradient sync (wire_dtype="int8_hier") vs
    the flat wires, on the same devices factored into a tiered
    slice=2 x data=N/2 mesh: per bucket an EXACT fp32 reduce-scatter
    inside the slice (fast ICI tier), the s8+EF multihop exchange across
    slices (slow DCN tier), and an exact intra-slice all-gather back.

    Each row carries (a) throughput, (b) the TIER-classified collective
    census of the compiled step (analysis/hlo_rules.replica_group_tier:
    intra-slice groups are consecutive-id runs, cross-slice groups are
    strided combs; "spanning" counts collectives riding the whole mesh —
    flat traffic that ignores the hierarchy), and (c) the per-replica
    wire bytes split by tier (`wire_bytes_split_for_config`) — the
    slow-tier ~2·S/n_inner B/replica (i.e. ~2·S per slice, independent
    of the slice count) as a RECORDED number next to the flat modes'
    all-one-tier totals."""
    from ..analysis.hlo_rules import grad_sync_census, replica_group_tier
    from ..parallel.grad_sync import wire_bytes_split_for_config
    from ..parallel.mesh import batch_shard_count

    devices = jax.devices()
    n = len(devices)
    if n < 4:
        return [{"mode": "skipped",
                 "global_samples_per_s":
                     "needs >= 4 devices (slice=2 x data>=2)"}]
    cap = args.bucket_cap_mb
    mesh_spec = f"slice=2,data={n // 2}"
    lm_kw = None
    if args.lm_tiny and is_lm_model(args.model):
        lm_kw = dict(_LM_TINY)
        if args.model.startswith("gpt2"):
            lm_kw.pop("mlp_dim")
    modes = [("flat_fp32", dict(bucket_cap_mb=cap)),
             ("flat_int8_multihop",
              dict(bucket_cap_mb=cap, wire_dtype="int8_multihop")),
             ("int8_hier", dict(bucket_cap_mb=cap, wire_dtype="int8_hier"))]
    if args.grad_accum > 1:
        modes.append(("int8_hier_accum",
                      dict(bucket_cap_mb=cap, wire_dtype="int8_hier",
                           grad_accum=args.grad_accum)))
    rows = []
    for mode, gs in modes:
        trainer, state, mesh = build_trainer(
            devices, args.bf16, args.model, args.seq_len, lm_overrides=lm_kw,
            grad_sync=gs, mesh_spec=mesh_spec)
        batch, gb = make_synth_batch(mesh, args.model, args.batch_size,
                                     args.seq_len)
        nb = batch_shard_count(mesh)
        n_slices = dict(mesh.shape).get("slice", 1)
        compiled = trainer._train_step.lower(
            state, batch, jax.random.PRNGKey(0)).compile()
        by_tier: dict = {}
        for r in grad_sync_census(compiled.as_text())["rows"]:
            t = replica_group_tier(r["replica_groups"], n_slices,
                                   nb // n_slices)
            t = t if t in ("ici", "dcn") else "spanning"
            by_tier[t] = by_tier.get(t, 0) + r["count"]
        split = wire_bytes_split_for_config(state.params,
                                            dict(gs, slices=n_slices), nb)
        _, sps = timed_steps(compiled, state, batch, gb, args.steps,
                             repeats=args.repeats,
                             min_window_s=args.min_window_s)
        rows.append({
            "mode": mode,
            "global_samples_per_s": round(sps, 1),
            "ici_collectives": by_tier.get("ici", 0),
            "dcn_collectives": by_tier.get("dcn", 0),
            "spanning_collectives": by_tier.get("spanning", 0),
            "wire_bytes_ici": split["ici"],
            "wire_bytes_dcn": split["dcn"],
        })
    return rows


def run_fsdp(args) -> List[dict]:
    """Replicated vs explicit full-parameter FSDP on the same devices
    (training/loop.py fsdp_explicit; SimpleFSDP, PAPERS.md): same model,
    same data-parallel mesh, once with replicated params (the DDP layout)
    and once with params + moments flat-sharded 1/N at rest, gathered
    just-in-time per layer — plus the fully compressed int8_multihop arm
    (s8 gradient scatter with EF + s8 param gathers).

    Each row carries (a) throughput, (b) the per-layer collective census
    of the compiled step — all-gather count must equal the LayerPlan's
    group count, scatters must land as 1/N chunks (the analysis/ fsdp
    contracts, read here as recorded numbers), (c) the at-rest per-replica
    parameter bytes — the memory-division claim as a number, not a
    docstring — and (d) `wire_bytes_per_replica` with its
    `fsdp_gather_bytes` term split out, so the gather-traffic cost of the
    mode is accounted per wire dtype (the int8_multihop gathers are
    ~1 B/element, n-independent; fp32 gathers are exact at ~4 B/element).
    `--grad-accum` > 1 exercises the in-scan per-layer scatter overlap."""
    from ..analysis.hlo_rules import grad_sync_census
    from ..parallel.grad_sync import fsdp_gather_bytes, wire_bytes_for_config
    from ..parallel.mesh import batch_shard_count

    devices = jax.devices()
    if len(devices) < 2:
        return [{"mode": "skipped",
                 "global_samples_per_s": "needs >= 2 devices"}]
    accum = args.grad_accum
    modes = [("replicated", None),
             ("fsdp_fp32", dict(fsdp_explicit=True)),
             ("fsdp_int8_multihop",
              dict(fsdp_explicit=True, wire_dtype="int8_multihop"))]
    rows = []
    for mode, gs in modes:
        gs_full = (dict(gs or {}, grad_accum=accum)
                   if (gs or accum > 1) else gs)
        trainer, state, mesh, batch, gb = _setup(devices, args.bf16, args,
                                                 grad_sync=gs_full)
        n = batch_shard_count(mesh)
        compiled = trainer._train_step.lower(
            state, batch, jax.random.PRNGKey(0)).compile()
        census = grad_sync_census(compiled.as_text())
        by_op = census["by_op"]
        # at-rest parameter residency per replica: fsdp's flat leaves are
        # sharded 1/N, the replicated arm holds every byte everywhere
        param_bytes = sum(
            int(leaf.size) * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(state.params))
        at_rest = param_bytes // n if trainer._fsdp else param_bytes
        wire = (gs or {}).get("wire_dtype", "fp32")
        gather_bytes = (fsdp_gather_bytes(state.params, wire, n)
                        if trainer._fsdp else 0)
        wire_bytes = wire_bytes_for_config(state.params, gs_full, n)
        _, sps = timed_steps(compiled, state, batch, gb, args.steps,
                             repeats=args.repeats,
                             min_window_s=args.min_window_s)
        rows.append({
            "mode": mode,
            "global_samples_per_s": round(sps, 1),
            "all_gathers": by_op.get("all-gather", 0),
            "grad_scatters": (by_op.get("reduce-scatter", 0)
                              + by_op.get("all-to-all", 0)),
            "grad_all_reduce": by_op.get("all-reduce", 0),
            "param_bytes_at_rest_per_replica": at_rest,
            "wire_bytes_per_replica": wire_bytes,
            "fsdp_gather_bytes": gather_bytes,
        })
    return rows


def run_tp(args) -> List[dict]:
    """Explicit TP x FSDP on the 2-D ("data","model") mesh vs 1-D layouts
    of the same LM on the same devices (ISSUE 13): replicated, fsdp
    (1-D), and fsdp x TP at model=2 (plus model=4 when the device count
    allows a data axis >= 2 beside it).

    Each row carries (a) throughput, (b) the axis-classified collective
    census of the compiled step — model-axis psums must equal the
    trainer's tp-psum-signature budget, param gathers/scatters must ride
    the data axes only (the analysis/ rules, read here as recorded
    numbers), (c) at-rest per-device parameter bytes (the 1/(N*M)
    division claim as a number), and (d) the wire split:
    `wire_bytes_per_replica` (data-axis, computed over the TP-LOCAL
    slices — the 1/M reduction) next to `tp_psum_bytes_per_replica`
    (model-axis activation traffic)."""
    from ..parallel.grad_sync import wire_bytes_for_config
    from ..parallel.mesh import batch_shard_count
    from .harness import build_lm_trainer, synth_token_batch
    from ..analysis.hlo_rules import replica_group_axis

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return [{"mode": "skipped",
                 "global_samples_per_s": "needs >= 2 devices"}]
    if not is_lm_model(args.model):
        return [{"mode": "skipped",
                 "global_samples_per_s": "tp is an LM experiment "
                                         "(--model gpt2_*)"}]
    lm_kw = None
    if args.lm_tiny:
        lm_kw = dict(_LM_TINY)
        if args.model.startswith("gpt2"):
            lm_kw.pop("mlp_dim")
    meshes = [("replicated", None, None),
              ("fsdp", dict(fsdp_explicit=True), None),
              ("fsdp_tp_m2", dict(fsdp_explicit=True), f"data={n // 2},model=2")]
    if n >= 8:
        meshes.append(("fsdp_tp_m4", dict(fsdp_explicit=True),
                       f"data={n // 4},model=4"))
    rows = []
    for mode, gs, mesh_spec in meshes:
        try:
            trainer, state, mesh = build_lm_trainer(
                devices, args.bf16, args.model, args.seq_len,
                model_kwargs=lm_kw, grad_sync=gs, mesh_spec=mesh_spec)
        except ValueError as e:
            # infeasible arm for this model/device combo (heads not
            # divisible by the TP degree, not enough devices): recorded,
            # never silently dropped
            rows.append({"mode": mode,
                         "global_samples_per_s": f"skipped ({e})"})
            continue
        batch, gb = synth_token_batch(mesh, args.batch_size, args.seq_len)
        nb = batch_shard_count(mesh)
        model_n = dict(mesh.shape).get("model", 1)
        compiled = trainer._train_step.lower(
            state, batch, jax.random.PRNGKey(0)).compile()
        by_axis: dict = {}
        for r in collective_census(compiled.as_text()):
            ax = (replica_group_axis(r["replica_groups"], nb, model_n)
                  if model_n > 1 else "data")
            key = (r["op"], ax)
            by_axis[key] = by_axis.get(key, 0) + r["count"]
        param_bytes = sum(
            int(leaf.size) * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(state.params))
        at_rest = sum(
            int(sh.data.size) * sh.data.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(state.params)
            for sh in leaf.addressable_shards[:1]) if trainer._fsdp \
            else param_bytes
        acct_params = (trainer._fsdp_local_template
                       if trainer._tp_n > 1 else state.params)
        cfg = dict(gs or {})
        tp_bytes = trainer.tp_wire_bytes(gb // nb, args.seq_len)
        wire_bytes = wire_bytes_for_config(acct_params, cfg, nb)
        _, sps = timed_steps(compiled, state, batch, gb, args.steps,
                             repeats=args.repeats,
                             min_window_s=args.min_window_s)
        rows.append({
            "mode": mode,
            "global_samples_per_s": round(sps, 1),
            "model_axis_psums": by_axis.get(("all-reduce", "model"), 0),
            "model_axis_gathers": by_axis.get(("all-gather", "model"), 0),
            "data_axis_gathers": by_axis.get(("all-gather", "data"), 0),
            "data_axis_scatters": (by_axis.get(("reduce-scatter", "data"), 0)
                                   + by_axis.get(("all-to-all", "data"), 0)),
            "param_bytes_at_rest_per_device": at_rest,
            "wire_bytes_per_replica": wire_bytes,
            "tp_psum_bytes_per_replica": tp_bytes,
        })
    return rows


def run_pipeline(args) -> List[dict]:
    """GPipe bubble measurement: pipelined GPT-2 throughput vs microbatch
    count, against the pure-DP layout of the same model on the same devices.

    The GPipe bubble fraction is (P-1)/(M+P-1) for P stages and M
    microbatches — throughput should approach the DP baseline as M grows.
    No analogue exists in the reference (DDP only); this quantifies the
    cost/benefit of the `pipe` mesh axis (parallel/pipeline.py).
    """
    import numpy as _np

    from ..models.gpt2_pipe import GPT2PipeLMHead
    from ..parallel import MeshSpec, build_mesh, shard_batch
    from ..training import TrainConfig, Trainer
    from ..training.optim import adamw
    from ..training.tasks import LanguageModelingTask

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return [{"config": "skipped", "samples_per_s": "needs >= 2 devices"}]

    p_stages = 2
    seq, vocab, hidden, depth, heads = 64, 256, 128, 4, 4
    gb = (n // p_stages) * 8  # local batch 8 per shard: M in {1,2,4,8} divides
    rng = _np.random.RandomState(0)
    raw = {
        "input_ids": rng.randint(0, vocab, (gb, seq)).astype(_np.int32),
        "weight": _np.ones(gb, _np.float32),
    }

    def measure(mesh, model, rules):
        trainer = Trainer(LanguageModelingTask(), mesh, TrainConfig(seed=0),
                          rules=rules)
        state = trainer.init_state(model, _np.zeros((1, seq), _np.int32),
                                   adamw(1e-3), jax.random.PRNGKey(0))
        batch = shard_batch(raw, mesh)
        sps, samples = timed_steps(trainer._train_step, state, batch, gb,
                                   args.steps, repeats=args.repeats,
                                   min_window_s=args.min_window_s)
        return samples

    rows = []
    # pure-DP baseline: same model as a plain scan over layers (pipe=1
    # degenerates to sequential), all devices on the batch
    mesh_dp = build_mesh(MeshSpec(data=n), devices=devices)
    model_dp = GPT2PipeLMHead(mesh=mesh_dp, num_microbatches=1,
                              vocab_size=vocab, hidden_dim=hidden,
                              depth=depth, num_heads=heads, max_position=seq)
    sps_dp = measure(mesh_dp, model_dp, GPT2PipeLMHead.partition_rules())
    rows.append({"config": f"dp={n} (baseline)", "microbatches": "-",
                 "samples_per_s": round(sps_dp, 1),
                 "bubble_predicted_pct": 0.0, "vs_dp_pct": 100.0})

    mesh_pp = build_mesh(MeshSpec(pipe=p_stages, data=n // p_stages),
                         devices=devices)
    for m in (1, 2, 4, 8):
        model_pp = GPT2PipeLMHead(mesh=mesh_pp, num_microbatches=m,
                                  vocab_size=vocab, hidden_dim=hidden,
                                  depth=depth, num_heads=heads,
                                  max_position=seq)
        sps = measure(mesh_pp, model_pp, GPT2PipeLMHead.partition_rules())
        bubble = (p_stages - 1) / (m + p_stages - 1)
        rows.append({
            "config": f"pipe={p_stages},data={n // p_stages}",
            "microbatches": m,
            "samples_per_s": round(sps, 1),
            "bubble_predicted_pct": round(100.0 * bubble, 1),
            "vs_dp_pct": round(100.0 * sps / sps_dp, 1),
        })
    return rows


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("experiment",
                   choices=["scaling", "batch", "amp", "gradsync",
                            "grad_sync", "hier", "zero1", "fsdp", "tp",
                            "pipeline"])
    p.add_argument("--model", default="resnet18")
    p.add_argument("--batch-size", default=128, type=int,
                   help="per-device batch (ref semantics, train_ddp.py:27)")
    p.add_argument("--steps", default=20, type=int)
    p.add_argument("--repeats", default=3, type=int)
    p.add_argument("--min-window-s", default=0.5, type=float,
                   help="minimum differenced timing window (lower it for "
                        "CI smoke runs)")
    p.add_argument("--batch-list", default=None, type=str,
                   help="comma-separated per-device batches for the 'batch' "
                        "sweep (default 32,64,128,256,512)")
    p.add_argument("--lm-tiny", action="store_true",
                   help="shrink LM architectures for CI smoke runs "
                        "(never use for real measurements)")
    p.add_argument("--seq-len", default=512, type=int,
                   help="sequence length for LM models (--model gpt2_*/"
                        "bert_base; e.g. the BERT-512 grad-sync profiling "
                        "run, BASELINE config 4)")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--bucket-cap-mb", default=25.0, type=float,
                   help="bucket cap for the 'grad_sync' experiment "
                        "(training/loop.py explicit reducer; DDP's "
                        "default is 25)")
    p.add_argument("--grad-accum", default=1, type=int,
                   help="gradient accumulation for the 'grad_sync' and "
                        "'fsdp' experiments (> 1 exercises the in-scan "
                        "overlap; grad_sync adds a no-overlap arm)")
    p.add_argument("--csv", default=None,
                   help="append rows to this CSV (plots regenerate from it)")
    args = p.parse_args(argv)

    fn = {"scaling": run_scaling, "batch": run_batch_sweep, "amp": run_amp,
          "gradsync": run_gradsync, "grad_sync": run_grad_sync,
          "hier": run_hier, "zero1": run_zero1, "fsdp": run_fsdp,
          "tp": run_tp, "pipeline": run_pipeline}[args.experiment]
    print(f"# {args.experiment} — {args.model}, "
          f"{'bf16' if args.bf16 else 'fp32'}, "
          f"{len(jax.devices())} device(s) [{require_backend()}]\n")
    rows = fn(args)
    _emit(rows, args.csv)


if __name__ == "__main__":
    main(sys.argv[1:])

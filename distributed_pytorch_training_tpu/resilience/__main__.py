"""``python -m distributed_pytorch_training_tpu.resilience chaos`` — run a
scripted fault schedule against a short CPU-mesh training run and report
recovery stats. The demo AND the test harness: tier-1 drives this same
entry point (tests/test_resilience.py).

Also installed as the ``resilience`` console script (pyproject.toml).

The run is a tiny ResNet on synthetic data under the restart supervisor,
with the full recovery chain engaged: step-fence fault hooks in the train
loop, the torn-checkpoint hook on the save path, the stall hook in the
loader, manifest-verified restores, and preemption drain (the SIGTERM
fault goes through the real ``PreemptionGuard``). ``--verify-parity``
(default on) then re-runs the same seed WITHOUT faults and checks the
final params are BITWISE equal — recovery that changed the trajectory is a
failure, not a recovery.

``--elastic`` (ISSUEs 11 + 12) arms the Supervisor's mesh re-planner AND
the capacity watch: the default schedule kills a replica mid-epoch
(``replica_death@step=3`` — the run re-plans to the largest feasible
world <= survivors, reshards the checkpoint, continues at the shrunken
size) and then RETURNS the capacity (``capacity_return@step=4`` — the
supervisor grows back to the full world at the next segment boundary:
drain, checkpoint, re-plan UP, live reshard). Elasticity is proven
BIDIRECTIONAL in one run: 8 -> 4 -> 8. The parity control is the
post-LAST-resize one: restore the SAME resize-anchor checkpoint
independently (probing the manifest's OWN recorded world), reshard it
through the same helpers, run the remaining steps clean at the final
world — the post-resize segment must be BITWISE equal. ``--layout
{replicated,zero1,fsdp}`` and ``--wire-dtype`` pick the state layout the
resize must re-slice (int8 wires include the EF residuals, whose rows
fold M -> N zero-extended on a grow — the telescoping total is
preserved).

``fleet`` (ISSUE 12) is the cross-PROCESS story: an external orchestrator
(resilience/fleet.py) launches train.py children, watches exit codes,
and relaunches with a DIFFERENT world size over the shared checkpoint
directory — kill -> relaunch at half world -> capacity return -> relaunch
at full world, with cross-world restores riding train.py's elastic
--resume (raw restore + reshard; never a CheckpointWorldSizeMismatch
escape) and a control child verifying the final segment bitwise.

Exit codes: 0 recovered (and parity held), 1 not.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path
from typing import List, Optional

# What an injected fault's flight artifact must say: fault KIND -> the
# substring its flight cause carries. The injected-crash causes quote the
# fault label verbatim ("FaultError: injected crash@step=3"); sigterm
# surfaces as the preemption drain; torn checkpoints as the integrity
# skip. loader_stall is absent by design: a stall is not an exit (the
# anomaly watchdog covers it as an `anomaly` event / optional abort).
FLIGHT_SIGNATURES = {
    "crash": "crash@step",
    "crash_during_save": "crash_during_save",
    "sigterm": "sigterm",
    "torn_ckpt": "torn_checkpoint",
    "replica_death": "replica_death",
}


def check_flights(flight_dir, fired: List[str],
                  ignore: Optional[set] = None) -> dict:
    """Verify every fired fault with a flight signature left a parseable
    ``flight_*.json`` whose cause matches — the chaos acceptance bar for
    the flight recorder (ISSUE 8). ``ignore`` holds flight paths that
    existed BEFORE the run: a reused ``--ckpt-dir`` must not let a
    previous run's postmortems satisfy (or a stale unparseable one fail)
    THIS run's verification."""
    flights = []
    for p in sorted(Path(flight_dir).glob("flight_*.json")):
        if ignore and p in ignore:
            continue
        try:
            body = json.loads(p.read_text())
            flights.append({"path": str(p), "cause": body.get("cause", ""),
                            "n_events": body.get("n_events")})
        except ValueError:
            flights.append({"path": str(p), "cause": None,
                            "error": "unparseable"})
    causes = [f["cause"] or "" for f in flights]
    missing = []
    for label in fired:
        sig = FLIGHT_SIGNATURES.get(label.split("@")[0])
        if sig is not None and not any(sig in c for c in causes):
            missing.append(label)
    ok = not missing and all(f["cause"] is not None for f in flights)
    return {"flights": flights, "flights_missing": missing,
            "flights_ok": ok}


def read_control_decisions(stream_path) -> List[dict]:
    """The control-plane audit trail, read BACK from the stream JSONL —
    the autopilot verdict must prove the decisions were RECORDED (the
    operator-facing artifact), not merely taken in memory."""
    from ..telemetry.recorder import CONTROL_DECISION_KIND

    out: List[dict] = []
    path = Path(stream_path)
    if not path.exists():
        return out
    for line in path.read_text().splitlines():
        try:
            ev = json.loads(line)
        except ValueError:
            continue
        if ev.get("kind") == CONTROL_DECISION_KIND:
            out.append(ev)
    return out


def _build_rig(mesh, seed: int, dataset_size: int, per_device_batch: int,
               fault_hook=None, layout: str = "replicated",
               wire_dtype: str = "fp32"):
    """(trainer, state_factory, loader) — the tiny-ResNet chaos workload
    (fp32 master, augmentation off: bitwise parity is the acceptance bar).
    ``layout`` picks the state layout a chaos/elastic run exercises:
    "replicated" (the DDP layout), "zero1" (flat-sharded moments) or
    "fsdp" (flat-sharded params + moments); an int8 ``wire_dtype`` adds
    the error-feedback residuals to the state (the elastic reshard must
    carry all of them)."""
    import jax
    import numpy as np

    from ..data.datasets import ArrayDataset
    from ..data.loader import ShardedLoader
    from ..models import get_model
    from ..training import TrainConfig, Trainer
    from ..training.optim import sgd
    from ..training.tasks import ImageClassificationTask

    rng = np.random.RandomState(seed)
    images = rng.randint(0, 256, (dataset_size, 8, 8, 3)).astype(np.uint8)
    labels = (images.astype(np.float32).mean(axis=(1, 2, 3)) > 127
              ).astype(np.int32)
    ds = ArrayDataset(images=images, labels=labels, num_classes=2,
                      name="chaos-synthetic", synthetic=True)
    task = ImageClassificationTask(mean=(0.5, 0.5, 0.5),
                                   std=(0.25, 0.25, 0.25), augment=False)
    if layout not in ("replicated", "zero1", "fsdp"):
        raise ValueError(f"unknown chaos layout {layout!r} "
                         "(replicated | zero1 | fsdp)")
    cfg = TrainConfig(seed=seed, print_freq=10_000, wire_dtype=wire_dtype,
                      zero1=layout == "zero1",
                      fsdp_explicit=layout == "fsdp")
    trainer = Trainer(task, mesh, cfg)
    # num_filters=8: a ~170k-param ResNet-18 — BatchNorm state and the full
    # recovery chain exercised, checkpoints small enough that the manifest
    # hashing and the several restores stay in tier-1 time
    model = get_model("resnet18", num_classes=2, cifar_stem=True,
                      num_filters=8)
    tx = sgd(0.05, momentum=0.9, weight_decay=5e-4)

    def state_factory():
        return trainer.init_state(model, np.zeros((1, 8, 8, 3), np.float32),
                                  tx, jax.random.PRNGKey(seed))

    loader = ShardedLoader(ds, mesh, per_device_batch, shuffle=True,
                           seed=seed, fault_hook=fault_hook)
    return trainer, state_factory, loader


def _elastic_control(args, ckpt_dir: str, report, rig_for):
    """The post-resize control trajectory: restore the LAST resize's
    checkpoint against its old-world template, reshard to the final world
    through the same helpers the supervisor used, and run the remaining
    steps clean (no faults fire — the injector's schedule is spent — and
    no supervisor segmentation). Returns the control state, or None when
    the resize restarted from scratch (nothing to pin a segment against).
    """
    from ..training.checkpoint import CheckpointManager
    from .elastic import reshard_train_state

    last = report.resizes[-1]
    label, to_w = last["label"], last["to_world"]
    if label is None:
        return None
    trainer_to, sf_to, loader_to = rig_for(to_w)
    ckpt = CheckpointManager(ckpt_dir, max_to_keep=64)
    try:
        # the checkpoint's OWN recorded world, not the resize record's
        # from_world: a second death before any post-resize save restores
        # a label still laid out for an earlier world
        saved_w = ckpt.checkpoint_world_size(label) or last["from_world"]
        _t, sf_from, _l = rig_for(saved_w)
        restored = ckpt.restore_latest(sf_from(), among={label})
    finally:
        ckpt.close()
    from_w = saved_w
    if restored is None:
        return None
    control, epoch_r, step_r = restored
    control = reshard_train_state(control, from_w, to_w, trainer_to,
                                  sf_to())
    spe = len(loader_to)
    for epoch in range(epoch_r, args.epochs):
        start = step_r if epoch == epoch_r else 0
        control, *_ = trainer_to.train_epoch(
            control, loader_to.epoch(epoch, start_step=start), epoch, spe,
            start_step=start)
    return control


def _add_fleet_args(p: argparse.ArgumentParser) -> None:
    """The `resilience fleet` scenario's own knobs (resilience/fleet.py);
    chaos ignores them. The shared knobs — --ckpt-dir, --seed, --layout,
    --wire-dtype, --epochs, --json, --no-verify-parity — apply to both
    commands."""
    p.add_argument("--global-batch", type=int, default=16,
                   help="fleet: the FIXED global batch every generation "
                        "splits over its world (the elastic invariant)")
    p.add_argument("--synthetic-size", type=int, default=64,
                   help="fleet: synthetic dataset rows (steps/epoch = "
                        "rows / global batch)")
    p.add_argument("--capacity", default="8,4,8",
                   help="fleet: available replicas per launch generation, "
                        "comma-separated (last value repeats) — the "
                        "scripted capacity feed: 8,4,8 is kill -> "
                        "half-world relaunch -> capacity-return relaunch")
    p.add_argument("--gen-chaos", default=None,
                   help="fleet: per-generation chaos specs "
                        "'GEN:SPEC[;GEN:SPEC...]' (default: generation 0 "
                        "crashes mid-epoch-1, generation 1 drains on "
                        "SIGTERM shortly before the end)")
    p.add_argument("--max-launches", type=int, default=8,
                   help="fleet: launch budget before giving up")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="fleet: stamp DPT_METRICS_PORT (+rank) into "
                        "every child so each serves live /metrics + "
                        "/healthz; the orchestrator smoke-scrapes it "
                        "while children run (telemetry/metrics_http.py). "
                        "Default off")
    p.add_argument("--federation-port", type=int, default=None,
                   help="fleet: additionally run ONE federated /metrics "
                        "fan-in on this port for the whole run "
                        "(telemetry/metrics_http.FederationServer): "
                        "every child series re-labelled with its "
                        "gen/rank (read from the child's own "
                        "dpt_build_info), exited generations kept in "
                        "the merge marked down. Requires --metrics-port")


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="resilience", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("command", choices=["chaos", "fleet"],
                   help="'chaos' runs the scripted in-process fault "
                        "schedule; 'fleet' runs the cross-process "
                        "relaunch scenario (resilience/fleet.py)")
    p.add_argument("--chaos", default=None,
                   help="fault plan (resilience/faults.py spec; default: "
                        "the full fixed-world schedule, or the "
                        "shrink-then-grow replica_death@step=3,"
                        "capacity_return@step=4 with --elastic)")
    p.add_argument("--elastic", action="store_true",
                   help="arm the Supervisor's mesh re-planner + capacity "
                        "watch: a replica_death fault restarts the run "
                        "resharded to the surviving replica count, a "
                        "capacity_return fault grows it back at the next "
                        "segment boundary, and the parity control "
                        "verifies the post-resize segment bitwise")
    p.add_argument("--autopilot", action="store_true",
                   help="close the control loop (ISSUE 20): attach the "
                        "control/ Autopilot to the telemetry stream and "
                        "let it evict a persistently slow rank at a "
                        "segment boundary (shrink via the elastic path; "
                        "implies --elastic). The default schedule stalls "
                        "the loader 3 consecutive steps on the same rank "
                        "and returns the capacity later — the verdict "
                        "requires the full detect -> evict -> grow "
                        "decision chain on the stream plus bitwise "
                        "post-resize parity")
    p.add_argument("--layout", default="replicated",
                   choices=["replicated", "zero1", "fsdp"],
                   help="state layout the run (and any reshard) exercises")
    p.add_argument("--wire-dtype", default="fp32",
                   help="gradient wire dtype (int8 wires add EF residuals "
                        "to the resharded state)")
    p.add_argument("--epochs", type=int, default=None,
                   help="training epochs (default: 2 for chaos; 3 for "
                        "fleet — one epoch per world phase)")
    p.add_argument("--per-device-batch", type=int, default=2)
    p.add_argument("--dataset-size", type=int, default=None,
                   help="synthetic dataset rows (default 64; 128 with "
                        "--autopilot — the eviction needs enough steps "
                        "per epoch for a 3-stall run plus the boundary "
                        "that convicts it)")
    p.add_argument("--checkpoint-every-steps", type=int, default=2)
    p.add_argument("--max-restarts", type=int, default=8)
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory (default: a fresh temp dir)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-verify-parity", action="store_true",
                   help="skip the no-fault same-seed control run")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable one-line report on stdout")
    _add_fleet_args(p)
    args = p.parse_args(argv)
    if args.command == "fleet":
        if args.epochs is None:
            args.epochs = 3
        from .fleet import fleet_main
        return fleet_main(args)
    if args.epochs is None:
        args.epochs = 2
    if args.autopilot:
        # the autopilot rides the elastic surface: eviction IS a shrink
        # re-plan, re-admission IS the boundary grow
        args.elastic = True
    if args.dataset_size is None:
        args.dataset_size = 128 if args.autopilot else 64
    if args.chaos is None and args.autopilot:
        # loop (1)'s proof schedule: the SAME rank stalls three
        # consecutive in-epoch steps (the policy's N) — no fault raises,
        # nothing crashes; the ONLY path to a resize is the autopilot
        # naming the straggler from data_wait spans and evicting it at
        # the boundary after the third stall. The capacity then returns
        # (absolute step 11, inside the shrunken world's epoch 1) and the
        # ordinary boundary grow re-admits it — detect -> evict -> grow.
        args.chaos = ("loader_stall@step=5:0.9s,loader_stall@step=6:0.9s,"
                      "loader_stall@step=7:0.9s,capacity_return@step=11")
    if args.chaos is None:
        # the default elastic schedule is BIDIRECTIONAL (ISSUE 12): kill
        # a replica at step 3 (8 -> 4 at the restart), return the
        # capacity at the step-4 fence (4 -> 8 at the next segment
        # boundary) — one run proves shrink, grow, and the EF fold both
        # ways
        args.chaos = ("replica_death@step=3,capacity_return@step=4"
                      if args.elastic else
                      "crash@step=3,torn_ckpt@save=2,"
                      "crash_during_save@save=2,sigterm@step=6")

    # The zero1/grad_sync trick reused: a CPU run asked for by name
    # (JAX_PLATFORMS=cpu) gets the 8-device virtual mesh.
    from ..analysis.__main__ import _ensure_test_mesh
    _ensure_test_mesh()

    import jax
    import numpy as np

    from ..parallel import MeshSpec, build_mesh
    from ..training.checkpoint import CheckpointManager
    from ..training.preemption import PreemptionGuard
    from .faults import FaultInjector, FaultPlan
    from .supervisor import RetryPolicy, Supervisor, SupervisorError

    mesh = build_mesh(MeshSpec(), devices=jax.devices())
    world0 = len(jax.devices())
    # the capacity registry (elastic runs): replica deaths debit it via
    # the Supervisor, the capacity_return fault credits it via the
    # injector, and the Supervisor's segment-boundary poll grows on it
    capacity = None
    if args.elastic:
        from .capacity import CapacityWatch
        capacity = CapacityWatch(total=world0)
    injector = FaultInjector(FaultPlan.parse(args.chaos),
                             capacity_watch=capacity)
    global_batch = args.per_device_batch * world0
    # one rig per world this run has trained at — the replan builds them
    # lazily over device SUBSETS (the in-process stand-in for a relaunch
    # on the surviving fleet), and the parity control reuses them
    rigs = {}

    def rig_for(world: int):
        # every rig carries the fault hook — the parity control stays
        # clean anyway because a completed run's schedule is spent (the
        # injector's takes are empty membership checks by then)
        if world not in rigs:
            sub = (mesh if world == world0 else
                   build_mesh(MeshSpec(), devices=jax.devices()[:world]))
            if global_batch % world:
                raise ValueError(
                    f"global batch {global_batch} does not divide over "
                    f"{world} replicas")
            rigs[world] = _build_rig(
                sub, args.seed, args.dataset_size, global_batch // world,
                fault_hook=injector.on_loader_batch,
                layout=args.layout, wire_dtype=args.wire_dtype)
        return rigs[world]

    trainer, state_factory, loader = rig_for(world0)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="dpt-chaos-")
    # Telemetry + flight recorder (telemetry/): the supervisor flushes a
    # flight_<ts>.json per failure/drain into this stream's directory —
    # the chaos run then VERIFIES every injected fault left its
    # postmortem (check_flights), not just that training recovered.
    from .. import telemetry
    telemetry.configure(str(Path(ckpt_dir) / "telemetry_rank0.jsonl"),
                        meta={"entry": "resilience chaos",
                              "chaos": args.chaos})
    # Warm-restart compilation cache (DPT_COMPILE_CACHE tri-state): off by
    # default on the CPU harness ("auto" refuses XLA:CPU — unsafe reloads),
    # measurable on accelerators where an elastic resize otherwise pays a
    # full recompile of the resized step.
    from ..runtime import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    # async saves ON (the production default): the schedule's
    # crash_during_save fault dies on the background writer and must
    # surface at the next save/wait barrier inside the recovery scope.
    # Elastic runs keep every label (max_to_keep): the parity control must
    # re-restore the exact resize-point checkpoint after the run.
    ckpt = CheckpointManager(ckpt_dir, post_save_hook=injector.on_save,
                             pre_finalize_hook=injector.on_save_finalize,
                             max_to_keep=(64 if args.elastic else 3))
    guard = PreemptionGuard.install()
    # flights already in the dir belong to a PREVIOUS run (user-supplied
    # --ckpt-dir reuse) — excluded from this run's verification
    pre_existing_flights = set(Path(ckpt_dir).glob("flight_*.json"))
    # fast, deterministic backoff: chaos is a harness, not a prod outage
    retry = RetryPolicy(max_restarts=args.max_restarts, backoff_base_s=0.01,
                        backoff_max_s=0.05, seed=args.seed)

    replan_cb = None
    if args.elastic:
        from .elastic import ElasticPlan, plan_elastic_world

        def replan_cb(survivors: int) -> "ElasticPlan":
            world = plan_elastic_world(survivors, global_batch)
            t, sf, ld = rig_for(world)
            return ElasticPlan(trainer=t, loader=ld, state_factory=sf,
                               world=world)

    autopilot = None
    if args.autopilot:
        # ISSUE 20: the policy layer rides the recorder as an observer
        # and is consulted by the Supervisor at clean segment boundaries;
        # nothing below this block exists when --autopilot is off.
        from ..control import Autopilot
        autopilot = Autopilot().attach()
    sup = Supervisor(trainer, ckpt, state_factory, loader, retry=retry,
                     guard=guard, injector=injector,
                     checkpoint_every_steps=args.checkpoint_every_steps,
                     resume_preempted=True, replan_cb=replan_cb,
                     capacity_watch=capacity, control=autopilot)
    error = None
    try:
        state, report = sup.run(args.epochs)
    except SupervisorError as e:
        state, report = None, e.report
        error = str(e)
    finally:
        guard.reset()
        ckpt.close()
        if autopilot is not None:
            autopilot.detach()
        telemetry.reset()  # close the JSONL; flights are already on disk
    flight_stats = check_flights(ckpt_dir, report.faults_fired,
                                 ignore=pre_existing_flights)
    decisions = (read_control_decisions(
        Path(ckpt_dir) / "telemetry_rank0.jsonl")
        if args.autopilot else [])

    parity = None
    if state is not None and not args.no_verify_parity:
        if report.resizes:
            # ELASTIC parity: the post-resize segment vs an independent
            # clean continuation at the shrunken world — restore the SAME
            # resize-point checkpoint with the old-world template, reshard
            # it through the same helpers, and train the remaining steps
            # with no supervisor segmentation. Bitwise equality proves the
            # reshard is a pure re-slice and the resumed sampler/RNG
            # schedule is the fixed-world-at-M one (PARITY.md).
            control = _elastic_control(args, ckpt_dir, report, rig_for)
        else:
            # control: same seed, same trainer (same compiled step), NO
            # faults, no supervisor segmentation — the uninterrupted
            # trajectory.
            _, _, control_loader = _build_rig(
                mesh, args.seed, args.dataset_size, args.per_device_batch,
                layout=args.layout, wire_dtype=args.wire_dtype)
            control = state_factory()
            spe = len(control_loader)
            for epoch in range(args.epochs):
                control, *_ = trainer.train_epoch(
                    control, control_loader.epoch(epoch), epoch, spe)
        parity = control is not None and all(
            bool(np.array_equal(np.asarray(jax.device_get(a)),
                                np.asarray(jax.device_get(b))))
            for a, b in zip(jax.tree_util.tree_leaves(state.params),
                            jax.tree_util.tree_leaves(control.params)))

    stats = {"metric": "chaos_recovery", "chaos": args.chaos,
             "epochs": args.epochs, "ckpt_dir": ckpt_dir,
             "elastic": args.elastic, "layout": args.layout,
             "wire_dtype": args.wire_dtype,
             "autopilot": args.autopilot,
             "control_decisions": [
                 {("action" if k == "name" else k): d.get(k)
                  for k in ("name", "rank", "epoch", "step", "world_from",
                            "world_to", "applied", "reason")
                  if d.get(k) is not None}
                 for d in decisions],
             "parity_bitwise": parity, "error": error,
             # the async-save instrument: loop-blocked ms vs snapshot ms
             "save_blocked_ms": round(ckpt.save_blocked_ms, 1),
             "snapshot_ms": round(ckpt.snapshot_ms, 1),
             **flight_stats,
             **report.as_dict()}
    # flights_ok is part of RECOVERED: a fault that left no postmortem
    # artifact would make the next real incident undiagnosable; an elastic
    # run that never resized (the schedule missed) proved nothing — and a
    # schedule whose capacity RETURNED but whose run never grew proved
    # only half of bidirectional elasticity
    grew = any(r.get("direction") == "grow"
               for r in report.resizes)
    capacity_returned = any(label.startswith("capacity_return")
                            for label in report.faults_fired)
    # the grow requirement binds only under --elastic: without a watch a
    # capacity_return fault fires into the void by design (faults.py) —
    # a fixed-world run that recovered must not be scored FAILED for it
    # the autopilot bar (ISSUE 20): the shrink must be the CONTROL
    # PLANE's doing (a resize whose cause is straggler_evict — no fault
    # raised in this schedule), and the full decision chain must be
    # readable back off the stream: a detect, an APPLIED evict, and the
    # accounting grow once capacity returned
    actions = [d.get("name") for d in decisions]
    evicted = any(r.get("cause") == "straggler_evict"
                  and r.get("direction") == "shrink"
                  for r in report.resizes)
    chain_ok = (not args.autopilot
                or (evicted and "detect" in actions and "grow" in actions
                    and any(d.get("name") == "evict" and d.get("applied")
                            for d in decisions)))
    ok = (report.completed and report.fence_violations == 0
          and parity is not False and error is None
          and flight_stats["flights_ok"]
          and (not args.elastic or bool(report.resizes))
          and (not args.elastic or not capacity_returned or grew)
          and chain_ok)
    if args.as_json:
        print(json.dumps(stats, sort_keys=True))
    else:
        for k in ("completed", "restarts", "preemptions_drained",
                  "checkpoints_skipped", "steps_run", "steps_replayed",
                  "fence_violations", "final_step", "parity_bitwise"):
            print(f"{k}: {stats[k]}")
        print(f"faults fired: {stats['faults_fired']}")
        for r in stats.get("resizes", []):
            print(f"elastic {r.get('direction', 'resize')}: "
                  f"{r['from_world']} -> {r['to_world']} replicas "
                  f"(available={r['survivors']}, anchor label "
                  f"{r['label']}, resumed epoch {r['epoch']} "
                  f"step {r['step']})")
        for d in stats["control_decisions"]:
            who = (f" rank {d['rank']}" if d.get("rank") is not None
                   else "")
            world = (f" world {d['world_from']}->{d['world_to']}"
                     if d.get("world_to") is not None else "")
            applied = " [applied]" if d.get("applied") else ""
            print(f"control {d['action']}:{who}{world}{applied} "
                  f"{d.get('reason', '')}")
        print(f"flight artifacts: {len(stats['flights'])} "
              f"(ok={stats['flights_ok']}"
              + (f", missing={stats['flights_missing']}"
                 if stats["flights_missing"] else "") + ")")
        if stats["faults_unfired"]:
            print(f"faults NEVER fired (schedule past the run?): "
                  f"{stats['faults_unfired']}")
        if error:
            print(f"error: {error}", file=sys.stderr)
        print("chaos: RECOVERED" if ok else "chaos: FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

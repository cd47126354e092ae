"""In-process restart supervisor: the epoch loop that survives its faults.

Wraps ``Trainer.train_epoch`` in segments of at most
``checkpoint_every_steps`` steps. After each segment it writes a
step-granular checkpoint (manifest-verified by ``training/checkpoint.py``);
when a segment raises — an injected :class:`~.faults.FaultError`, a real
step failure, a torn save — it restores the latest *valid* checkpoint and
replays behind the **step fence**:

* the checkpoint coordinate ``(epoch, step_in_epoch)`` decides where the
  data iterator resumes (the sampler is deterministic in seed+epoch, so the
  replayed batches are the exact batches of the lost steps);
* the restored ``state.step`` drives the per-step RNG fold, so the replayed
  steps draw the same randomness;
* the restored int8 error-feedback residuals (``TrainState.grad_sync``)
  re-enter the telescoping sum where it left off;
* the fence check ``int(state.step) == epoch * steps_per_epoch + step``
  catches the double-apply class: a restore whose optimizer step count
  disagrees with its data coordinate would replay an already-applied
  update (or skip one) — reported loudly, never silent.

Retries are bounded by :class:`RetryPolicy` (exponential backoff with
deterministic jitter); preemptions (the ``PreemptionGuard`` flag) are
DRAINED, not raced: the segment stops at the next step boundary, a
checkpoint is written, and the supervisor either returns (production: the
relaunch resumes with ``--resume``) or — in chaos harnesses with
``resume_preempted=True`` — simulates the relaunch by restoring its own
checkpoint and continuing.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Any, Callable, List, Optional, Tuple

from ..telemetry import flush_flight
from ..telemetry import recorder as _telemetry
from ..utils.logging import log_main
from .faults import ReplicaDeathError


class SupervisorError(RuntimeError):
    """The retry budget is exhausted; the last failure is the __cause__."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff with deterministic jitter.

    ``max_restarts`` bounds CONSECUTIVE restore-and-replay attempts: a
    completed clean segment (train + save + barrier, no exception) resets
    the counter — and with it the backoff exponent — back to zero
    (ISSUE 12; previously the counter only ever grew, so a long run with
    sporadic faults spread hours apart still exhausted the budget and
    died). Only a fault loop that cannot get one segment through gives
    up; ``RunReport.restarts`` still counts every restart over the whole
    run. Jitter is seeded so chaos runs are reproducible; consecutive
    attempt n sleeps ``min(base * factor^(n-1), max) * (1 + jitter * u)``
    with ``u ~ U[0, 1)`` from the policy's own RNG stream."""

    max_restarts: int = 3
    backoff_base_s: float = 0.25
    backoff_factor: float = 2.0
    backoff_max_s: float = 30.0
    jitter_frac: float = 0.25
    seed: int = 0

    def delay_s(self, restart_index: int, rng: random.Random) -> float:
        base = min(self.backoff_base_s
                   * self.backoff_factor ** max(0, restart_index - 1),
                   self.backoff_max_s)
        return base * (1.0 + self.jitter_frac * rng.random())


@dataclasses.dataclass
class RunReport:
    """Recovery stats of one supervised run (the chaos CLI's JSON body)."""

    completed: bool = False
    preempted: bool = False
    restarts: int = 0
    preemptions_drained: int = 0
    steps_run: int = 0        # train steps actually executed, incl. replays
    steps_replayed: int = 0   # executed more than once (lost to a restore)
    final_step: int = -1
    fence_violations: int = 0
    checkpoints_skipped: int = 0   # torn checkpoints integrity skipped
    faults_fired: List[str] = dataclasses.field(default_factory=list)
    faults_unfired: List[str] = dataclasses.field(default_factory=list)
    failures: List[str] = dataclasses.field(default_factory=list)
    # elastic resizes: one record per mesh re-plan — {from_world,
    # to_world, survivors, label, epoch, step, direction} where `label` is
    # the checkpoint anchoring the resize (the resharded restore's label
    # for a shrink; the boundary save's for a grow; None = no checkpoint
    # manager / restarted from scratch), (epoch, step) is where the run
    # resumed, and direction is "shrink" (replica_death restart) or
    # "grow" (capacity-return boundary re-plan, ISSUE 12)
    resizes: List[dict] = dataclasses.field(default_factory=list)
    # control-plane retunes (ISSUE 20): one record per applied
    # segment-boundary config re-plan — {epoch, step, overrides, label,
    # resets, cause} where `label` is the anchoring checkpoint and
    # `resets` names the state leaves the new config's template replaced
    # (wire-codec buffers; params/opt/step always carry over bitwise)
    retunes: List[dict] = dataclasses.field(default_factory=list)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class Supervisor:
    """Drive ``trainer`` over ``loader`` for N epochs, surviving failures.

    ``state_factory`` must build a FRESH initial TrainState (same seed/
    structure as the run's): it is both the restore template and the
    from-scratch fallback — after a failure the in-flight state's buffers
    may already be donated, so the supervisor never reuses them.
    ``ckpt`` is a ``training.checkpoint.CheckpointManager`` (or None: no
    persistence — a failure then restarts from scratch, which is still a
    correct trajectory, just a long replay). ``injector`` is an armed
    ``FaultInjector`` or None. ``epoch_end_cb(epoch, state, loss, acc,
    seconds)`` runs after each COMPLETED epoch (validation / CSV hooks).
    ``trust_existing=False`` restricts restores to checkpoints THIS run
    wrote: a fresh (non ``--resume``) run pointed at a directory holding a
    previous run's checkpoints must never restore one mid-recovery — the
    highest stale label could place the trajectory past ``epochs`` and the
    run would "complete" on another run's params (train.py passes
    ``args.resume``; harnesses with their own directories keep the
    default).

    ``capacity_watch`` (with ``replan_cb``) arms BIDIRECTIONAL elasticity
    (ISSUE 12): replica deaths debit the watch (its count feeds the
    shrink re-plan's survivors), and when returned capacity makes a
    larger feasible world available the supervisor GROWS at the next
    segment boundary — drain, checkpoint (the anchor label), re-plan UP,
    reshard the live state, continue. A grow is not a restart: nothing
    replays, no flight is flushed, the retry budget is untouched.

    Async saves: segment checkpoints ride the CheckpointManager's
    background writer (training continues over the orbax write + manifest
    hashing); a failed write surfaces at the next save/wait barrier, which
    is INSIDE the recovery try — "on a step/save failure, restore the
    latest valid checkpoint" covers the async window too, and the run's
    final save is flushed before ``run`` declares completion so a lost
    last save is a recovered failure, not a silent one.
    """

    def __init__(self, trainer, ckpt, state_factory: Callable[[], Any],
                 loader, *, retry: RetryPolicy = RetryPolicy(),
                 guard=None, injector=None,
                 checkpoint_every_steps: Optional[int] = None,
                 resume_preempted: bool = False,
                 trust_existing: bool = True,
                 epoch_end_cb: Optional[Callable[..., None]] = None,
                 replan_cb: Optional[Callable[[int], Any]] = None,
                 capacity_watch=None,
                 retune_cb: Optional[Callable[[dict], Any]] = None,
                 control=None,
                 sleep: Callable[[float], None] = time.sleep):
        if checkpoint_every_steps is not None and checkpoint_every_steps <= 0:
            raise ValueError("checkpoint_every_steps must be positive "
                             f"(got {checkpoint_every_steps})")
        self.trainer = trainer
        self.ckpt = ckpt
        self.state_factory = state_factory
        self.loader = loader
        self.retry = retry
        self.guard = guard
        self.injector = injector
        self.every = checkpoint_every_steps
        self.resume_preempted = resume_preempted
        self.trust_existing = trust_existing
        self.epoch_end_cb = epoch_end_cb
        # Elastic mode (ISSUE 11): ``replan_cb(survivors) -> ElasticPlan``
        # rebuilds the rig on the surviving-device mesh after a
        # ReplicaDeathError. The resize rides the NORMAL restart path —
        # one restart counted, one flight flushed, the same deterministic
        # RetryPolicy backoff — then the restore goes through a per-label
        # world-size template (restore_latest(template_factory=...)) and
        # reshards (resilience/elastic.py) when the checkpoint's world
        # differs from the new one. None = fixed-world behavior, verbatim.
        self.replan_cb = replan_cb
        # Grow side (ISSUE 12): a resilience.capacity.CapacityWatch the
        # replica deaths debit and capacity returns credit. Polled at
        # SEGMENT BOUNDARIES only (after the segment's checkpoint): when
        # available > current world AND the replan finds a larger
        # feasible world, the LIVE state reshards M -> N in place and the
        # run continues — no restart, no replay, one `elastic_grow` span.
        self.capacity_watch = capacity_watch
        # Control plane (ISSUE 20): ``retune_cb(overrides) -> ElasticPlan``
        # rebuilds the rig at the SAME world under a new training config
        # (the online tuner's apply path, `boundary_retune`), and
        # ``control`` is a control.Autopilot-shaped object whose
        # ``on_segment_boundary(supervisor=, report=, state=, epoch=,
        # step=)`` is consulted at every clean segment boundary — the
        # drained, checkpoint-anchored point where a decision may act.
        # Both default off; the None path is byte-identical to a build
        # without the control package.
        self.retune_cb = retune_cb
        self.control = control
        self.sleep = sleep
        # consecutive restore-and-replay attempts since the last CLEAN
        # segment — the RetryPolicy's budget/backoff index (resets to 0
        # after every completed segment; report.restarts never resets)
        self._consecutive_failures = 0
        self._last_saved_label: Optional[int] = None
        self._last_step_entered = -1
        self._saved_labels: set = set()
        self._skipped_labels: set = set()
        # world-size bookkeeping: the manifest records what each save was
        # laid out for, and _factories keeps one template factory per
        # world this run has ever trained at (elastic restores build the
        # OLD world's template, then reshard into the current one)
        self._world: Optional[int] = getattr(trainer, "batch_shards", None)
        self._factories = ({self._world: state_factory}
                           if self._world is not None else {})
        self._last_restore_label: Optional[int] = None

    @property
    def world_size(self) -> int:
        """Current data-parallel world (batch shards) — the number every
        control decision records its from/to transition against. 1 when
        the trainer exposes no shard count (single-device rigs)."""
        return int(self._world) if self._world is not None else 1

    # -- fence / bookkeeping hooks ----------------------------------------

    def _fault_hook(self, report: RunReport, seg_start_abs: int):
        """The per-step fence handed to train_epoch: records progress (so a
        restore can account the replay) and fires injected faults BEFORE
        the step executes — a crash here means the optimizer never applied
        this step."""
        injector = self.injector

        def hook(i: int) -> None:
            step = seg_start_abs + i
            self._last_step_entered = step
            if injector is not None:
                injector.on_step(step)
            report.steps_run += 1

        return hook

    def _segment_stop(self, seg_len: int):
        """stop_fn for one segment: break after seg_len steps, or at the
        next step boundary once a preemption was requested (the drain)."""
        count = [0]
        guard = self.guard

        def stop() -> bool:
            count[0] += 1
            if count[0] >= seg_len:
                return True
            return bool(guard is not None and guard.should_stop)

        return stop

    # -- checkpoint plumbing ----------------------------------------------

    def _save(self, epoch: int, step: int, spe: int, state) -> None:
        if self.ckpt is None:
            return
        if step >= spe:  # epoch-complete: the epoch-boundary label form
            label, save_epoch, in_epoch = (epoch + 1) * spe, epoch + 1, 0
        else:
            label, save_epoch, in_epoch = epoch * spe + step, epoch, step
        # async (snapshot-then-write): only the device→host copy blocks;
        # the orbax write + manifest overlap the next segment's training.
        # The manager itself joins any previous in-flight write first, so
        # an earlier failed save surfaces HERE — inside the recovery try.
        self.ckpt.save(label, state, epoch=save_epoch,
                       step_in_epoch=in_epoch, world_size=self._world)
        self._saved_labels.add(label)
        self._last_saved_label = label  # the grow anchor (resize record)

    def _replan(self, err: ReplicaDeathError, report: RunReport) -> dict:
        """The elastic resize: hand the surviving replica count to
        ``replan_cb`` and swap in the rig it builds. Invariants enforced
        loudly: the new loader must keep the old steps-per-epoch (the
        GLOBAL batch is fixed across resizes — the step fence, sampler
        permutation and per-step RNG all depend on it). Returns the
        resize record (label/epoch/step filled after the restore)."""
        old_world = self._world
        survivors = getattr(err, "survivors", None)
        if survivors is None:
            survivors = (old_world - 1) if old_world else None
        if survivors is not None and self.capacity_watch is not None:
            # keep the registry consistent with the shrink decision: a
            # death re-plans over the surviving ACTIVE replicas, so the
            # boundary poll must not see phantom idle capacity and grow
            # straight back mid-incident (capacity genuinely returning
            # goes through watch.restore — the capacity_return fault)
            self.capacity_watch.sync(survivors)
        if not survivors or survivors < 1:
            err2 = SupervisorError(
                f"replica death at world size {old_world} leaves no "
                "survivors to re-plan onto")
            err2.report = report  # the chaos CLI reports even a loss
            raise err2 from err
        with _telemetry.span("elastic_replan", from_world=old_world,
                             survivors=survivors):
            plan = self.replan_cb(survivors)
        if len(plan.loader) != len(self.loader):
            err2 = SupervisorError(
                f"elastic re-plan changed steps-per-epoch "
                f"({len(self.loader)} -> {len(plan.loader)}) — the replan "
                "must keep the GLOBAL batch fixed (grow the per-device "
                "batch), or the step fence and sampler schedule no longer "
                "describe the same trajectory")
            err2.report = report
            raise err2
        self.trainer = plan.trainer
        self.loader = plan.loader
        self.state_factory = plan.state_factory
        self._world = plan.world
        self._factories[plan.world] = plan.state_factory
        _telemetry.counter("elastic_resizes", 1, from_world=old_world,
                           to_world=plan.world, survivors=survivors)
        # the /metrics world-size gauge tracks every resize live
        _telemetry.gauge("world_size", plan.world)
        log_main(f"supervisor: elastic resize — mesh re-planned "
                 f"{old_world} -> {plan.world} replicas "
                 f"({survivors} survivor(s)); restoring and resharding")
        # a death restart normally shrinks, but capacity that returned
        # before the restart can make the re-plan land larger — direction
        # records what actually happened, not the trigger
        return {"from_world": old_world, "to_world": plan.world,
                "survivors": survivors,
                "direction": ("grow" if old_world is not None
                              and plan.world > old_world else "shrink")}

    def _maybe_grow(self, report: RunReport, state, epoch: int,
                    step: int):
        """Segment-boundary grow poll (ISSUE 12): when the capacity
        registry reports more replicas than the current world AND the
        re-plan finds a larger feasible world (divides the fixed global
        batch), reshard the LIVE state into the new world's layout and
        swap the rig — no restart, no replay, no data-order change (the
        sampler/fence/per-step RNG are world-independent by the elastic
        design). The just-written segment checkpoint anchors the resize
        record: the parity control restores THAT label at its recorded
        world and reshards the same way (``resilience chaos --elastic``).
        Returns the (possibly resharded) state."""
        avail = self.capacity_watch.poll_grow(self._world)
        if avail is None:
            return state
        plan = self.replan_cb(avail)
        if self._world is not None and plan.world <= self._world:
            # capacity returned in a quantity no feasible world can use
            # (e.g. 5 available, global batch 16): keep training at M —
            # the poll repeats at the next boundary
            return state
        if len(plan.loader) != len(self.loader):
            err = SupervisorError(
                f"elastic grow re-plan changed steps-per-epoch "
                f"({len(self.loader)} -> {len(plan.loader)}) — the replan "
                "must keep the GLOBAL batch fixed (shrink the per-device "
                "batch), or the step fence and sampler schedule no longer "
                "describe the same trajectory")
            err.report = report
            raise err
        if self.ckpt is not None:
            try:
                # the anchor must be DURABLE before the rig swaps: the
                # resize record names the just-saved label and the parity
                # control restores it — at a mid-epoch boundary that save
                # may still be on the async writer, and anchoring a grow
                # on a write that later fails would score a correct
                # recovery as a parity failure
                self.ckpt.wait()
            except Exception as e:  # noqa: BLE001 — the anchor save was
                # lost; its label is torn (pending marker) and later
                # restores skip it. Defer the grow: the capacity is still
                # there and the poll repeats at the next boundary, where
                # a fresh segment save anchors it.
                report.failures.append(
                    f"{type(e).__name__}: {e} (anchor save lost at a "
                    "grow boundary — grow deferred to the next segment)")
                log_main(f"supervisor: grow deferred — the boundary "
                         f"checkpoint's async write failed "
                         f"({type(e).__name__}: {e}); the label is torn "
                         "and the next boundary re-anchors")
                return state
        old_world = self._world
        from .elastic import reshard_train_state

        with _telemetry.span("elastic_grow", from_world=old_world,
                             to_world=plan.world, available=avail):
            state = reshard_train_state(state, old_world, plan.world,
                                        plan.trainer,
                                        plan.state_factory())
        self.trainer = plan.trainer
        self.loader = plan.loader
        self.state_factory = plan.state_factory
        self._world = plan.world
        self._factories[plan.world] = plan.state_factory
        _telemetry.counter("elastic_resizes", 1, from_world=old_world,
                           to_world=plan.world, direction="grow")
        _telemetry.gauge("world_size", plan.world)
        report.resizes.append({
            "from_world": old_world, "to_world": plan.world,
            "survivors": avail, "label": self._last_saved_label,
            "epoch": epoch, "step": step, "direction": "grow"})
        log_main(f"supervisor: elastic GROW — capacity returned "
                 f"({avail} available), mesh re-planned {old_world} -> "
                 f"{plan.world} replicas at epoch {epoch} step {step} "
                 f"(live reshard, anchor checkpoint "
                 f"{self._last_saved_label}; sampler/RNG unchanged)")
        return state

    # -- control-plane re-plan surface (ISSUE 20) --------------------------
    #
    # The two boundary methods below are the Supervisor's half of the
    # control loop: policy lives in control/, but the elastic invariants
    # (fixed global batch, steps-per-epoch, durable anchor before the rig
    # swaps) live HERE, where every other resize already enforces them.
    # Both return (state, applied, detail): a False apply is a refusal the
    # caller logs as a decision — never an exception, because a declined
    # control action must leave the run exactly as it was.

    def boundary_shrink(self, report: RunReport, state, *, epoch: int,
                        step: int, evicted_rank: Optional[int] = None,
                        cause: str = ""):
        """Evict one rank at a clean segment boundary: treat it as a
        capacity loss of exactly one replica — re-plan to the largest
        feasible smaller world, reshard the LIVE state (no restart, no
        replay, the `_maybe_grow` mechanics in the shrink direction), and
        debit the capacity watch so a later ``restore()`` re-admits the
        share through the normal grow poll."""
        if self.replan_cb is None:
            return state, False, ("no replan_cb armed (fixed-world "
                                  "supervisor cannot shrink)")
        if self._world is None:
            return state, False, "trainer exposes no world size"
        survivors = self._world - 1
        if survivors < 1:
            return state, False, "cannot shrink below one replica"
        plan = self.replan_cb(survivors)
        if plan.world >= self._world:
            return state, False, (
                f"no feasible world below {self._world} replicas for "
                f"{survivors} survivor(s) (global batch divisibility)")
        if len(plan.loader) != len(self.loader):
            return state, False, (
                f"eviction re-plan changed steps-per-epoch "
                f"({len(self.loader)} -> {len(plan.loader)}) — the replan "
                "must keep the GLOBAL batch fixed")
        if self.ckpt is not None:
            try:
                # same durable-anchor rule as a grow: the resize record
                # names the just-saved label and the parity control
                # restores it — never anchor on a write still in flight
                self.ckpt.wait()
            except Exception as e:  # noqa: BLE001 — anchor lost; defer
                report.failures.append(
                    f"{type(e).__name__}: {e} (anchor save lost at an "
                    "eviction boundary — eviction deferred)")
                return state, False, (
                    f"anchor save lost ({type(e).__name__}); eviction "
                    "deferred to the next boundary")
        old_world = self._world
        from .elastic import reshard_train_state

        with _telemetry.span("elastic_replan", from_world=old_world,
                             to_world=plan.world, survivors=survivors,
                             cause=cause or "straggler_evict"):
            state = reshard_train_state(state, old_world, plan.world,
                                        plan.trainer,
                                        plan.state_factory())
        self.trainer = plan.trainer
        self.loader = plan.loader
        self.state_factory = plan.state_factory
        self._world = plan.world
        self._factories[plan.world] = plan.state_factory
        if self.capacity_watch is not None:
            # the evicted rank is out of service until something
            # (capacity_return chaos, a real probe) restores it
            self.capacity_watch.sync(survivors)
        _telemetry.counter("elastic_resizes", 1, from_world=old_world,
                           to_world=plan.world, survivors=survivors,
                           direction="shrink")
        _telemetry.gauge("world_size", plan.world)
        report.resizes.append({
            "from_world": old_world, "to_world": plan.world,
            "survivors": survivors, "label": self._last_saved_label,
            "epoch": epoch, "step": step, "direction": "shrink",
            "cause": cause or "straggler_evict",
            "evicted_rank": evicted_rank})
        log_main(f"supervisor: control EVICTION — rank {evicted_rank} "
                 f"drained, mesh re-planned {old_world} -> {plan.world} "
                 f"replicas at epoch {epoch} step {step} (live reshard, "
                 f"anchor checkpoint {self._last_saved_label}; capacity "
                 f"watch debited to {survivors})")
        return state, True, ""

    def boundary_retune(self, report: RunReport, state, *, epoch: int,
                        step: int, overrides: dict, cause: str = ""):
        """Apply a contract-passed config re-plan at a clean segment
        boundary: rebuild the rig at the SAME world under the new
        TrainConfig (``retune_cb``), carry every state leaf whose
        layout the new config preserves (params, optimizer moments, the
        step counter — bitwise), and take the fresh template's value for
        leaves the new config re-shapes (wire-codec error-feedback
        buffers). The caller is responsible for gating: this method
        trusts that the overrides already passed their contract."""
        if self.retune_cb is None:
            return state, False, ("no retune_cb armed (this supervisor "
                                  "cannot rebuild its rig under a new "
                                  "config)")
        plan = self.retune_cb(dict(overrides))
        if self._world is not None and plan.world != self._world:
            return state, False, (
                f"retune re-plan changed the world ({self._world} -> "
                f"{plan.world}) — a retune must keep capacity fixed "
                "(evictions/grows own world changes)")
        if len(plan.loader) != len(self.loader):
            return state, False, (
                f"retune re-plan changed steps-per-epoch "
                f"({len(self.loader)} -> {len(plan.loader)})")
        if self.ckpt is not None:
            try:
                self.ckpt.wait()
            except Exception as e:  # noqa: BLE001 — anchor lost; defer
                report.failures.append(
                    f"{type(e).__name__}: {e} (anchor save lost at a "
                    "retune boundary — retune deferred)")
                return state, False, (
                    f"anchor save lost ({type(e).__name__}); retune "
                    "deferred to the next boundary")
        from .elastic import adopt_state

        with _telemetry.span("control_retune", cause=cause,
                             overrides=dict(overrides)):
            state, resets = adopt_state(state, plan.state_factory())
        self.trainer = plan.trainer
        self.loader = plan.loader
        self.state_factory = plan.state_factory
        self._factories[plan.world] = plan.state_factory
        _telemetry.counter("control_retunes", 1)
        report.retunes.append({
            "epoch": epoch, "step": step, "overrides": dict(overrides),
            "label": self._last_saved_label, "resets": list(resets),
            "cause": cause})
        log_main(f"supervisor: control RETUNE — config re-planned at "
                 f"epoch {epoch} step {step} with {overrides} (anchor "
                 f"checkpoint {self._last_saved_label}; "
                 f"{len(resets)} state leaf/leaves reset: {resets})")
        return state, True, ""

    def _template_for_world(self, world: Optional[int]):
        """Restore template for a checkpoint recorded at ``world`` batch
        shards (None = legacy manifest: assume the current world). Only
        worlds this run has trained at are known — a foreign world in the
        directory is a loud error, not a guess."""
        if world is None or world == self._world:
            return self.state_factory()
        factory = self._factories.get(world)
        if factory is None:
            raise RuntimeError(
                f"checkpoint was written at world size {world}, but this "
                f"supervisor only knows worlds {sorted(self._factories)} "
                "— checkpoints from another run's mesh need a matching "
                "template (train.py --resume with the original --mesh)")
        return factory()

    def _restore_or_fresh(self, report: RunReport, spe: int
                          ) -> Tuple[Any, int, int]:
        """Latest VALID checkpoint (torn ones are skipped by the manifest
        verification), or a fresh state when none exists. Returns
        ``(state, epoch, step_in_epoch)`` and enforces the step fence.
        In elastic mode the restore template is built at the CHECKPOINT's
        recorded world size and the state reshards into the current
        layout when the worlds differ (the N -> M re-slice)."""
        among = None if self.trust_existing else self._saved_labels
        self._last_restore_label = None
        if self.ckpt is None:
            restored = None
        elif self.replan_cb is not None:
            restored = self.ckpt.restore_latest(
                among=among, template_factory=self._template_for_world)
        else:
            restored = self.ckpt.restore_latest(self.state_factory(),
                                                among=among)
        if self.ckpt is not None:
            # a torn checkpoint is skipped by EVERY later restore; count
            # distinct labels, not skip events
            fresh_skips = sorted(set(self.ckpt.last_skipped)
                                 - self._skipped_labels)
            self._skipped_labels.update(self.ckpt.last_skipped)
            report.checkpoints_skipped = len(self._skipped_labels)
            if fresh_skips:
                # each NEWLY-discovered torn checkpoint leaves its own
                # postmortem (the torn_ckpt chaos fault's flight artifact)
                flush_flight(
                    cause=f"torn_checkpoint: labels {fresh_skips} failed "
                          "integrity verification",
                    detail="supervisor restore skipped torn checkpoint(s)")
        if restored is None:
            if self.ckpt is not None:
                log_main("supervisor: no valid checkpoint — "
                         "(re)starting from scratch")
            return self.state_factory(), 0, 0
        state, epoch, step = restored
        self._last_restore_label = self.ckpt.last_restored
        if self.replan_cb is not None:
            ckpt_world = self.ckpt.checkpoint_world_size(
                self._last_restore_label)
            if (ckpt_world is not None and self._world is not None
                    and ckpt_world != self._world):
                # the elastic re-slice: old-N flat-padded layouts re-chunk
                # into the new-M template, EF residual rows fold — exact
                # (pad regions are zeros), one leaf at a time
                from .elastic import reshard_train_state

                with _telemetry.span("elastic_reshard",
                                     from_world=ckpt_world,
                                     to_world=self._world,
                                     label=self._last_restore_label):
                    state = reshard_train_state(
                        state, ckpt_world, self._world, self.trainer,
                        self.state_factory())
                log_main(f"supervisor: resharded checkpoint "
                         f"{self._last_restore_label} from world "
                         f"{ckpt_world} to {self._world} (flat-padded "
                         "re-slice; sampler/RNG unchanged behind the "
                         "step fence)")
        expected = epoch * spe + step
        got = int(state.step)
        if got != expected:
            # The double-apply class: optimizer step count disagreeing with
            # the data coordinate means a replay would re-apply (or skip)
            # an update. Loud, counted, and resumed at the OPTIMIZER's
            # position (the authoritative trajectory coordinate).
            report.fence_violations += 1
            log_main(f"supervisor: STEP FENCE VIOLATION — restored "
                     f"optimizer step {got} != checkpoint coordinate "
                     f"epoch {epoch} * {spe} + step {step} = {expected}; "
                     "resuming at the optimizer's step to avoid a "
                     "double-apply")
            epoch, step = divmod(got, spe)
        return state, epoch, step

    # -- the loop ----------------------------------------------------------

    def run(self, epochs: int,
            initial: Optional[Tuple[Any, int, int]] = None):
        """Run to completion (or a drained preemption / exhausted retries).
        ``initial`` is an already-built ``(state, epoch, step)`` start
        point (train.py's --resume restore); default restores from the
        manager. Returns ``(final_state, RunReport)``."""
        spe = len(self.loader)
        report = RunReport()
        rng = random.Random(self.retry.seed)
        if initial is not None:
            state, epoch, step = initial
        else:
            state, epoch, step = self._restore_or_fresh(report, spe)

        while epoch < epochs:
            seg_start_abs = epoch * spe + step
            seg_len = (spe - step if self.every is None
                       else min(self.every, spe - step))
            try:
                state, loss, acc, seconds, done = self.trainer.train_epoch(
                    state, self.loader.epoch(epoch, start_step=step),
                    epoch, spe, start_step=step,
                    stop_fn=self._segment_stop(seg_len),
                    fault_hook=self._fault_hook(report, seg_start_abs))
                step += done
                # the save is inside the recovery scope too: "on a
                # step/SAVE failure, restore the latest valid checkpoint"
                self._save(epoch, step, spe, state)
                if self.ckpt is not None and step >= spe:
                    # Epoch-boundary barrier (the ISSUE-6 design: async
                    # saves barrier at epoch end): a failed background
                    # write must surface HERE, inside the recovery scope
                    # and before epoch_end_cb emits the epoch's
                    # validation/CSV row — otherwise the failure raises
                    # one segment late at the next save, the replay
                    # re-runs the epoch, and the cb fires twice for it
                    # (duplicate validation + duplicate CSV row). Also
                    # covers the run's last save: completing with a
                    # silently lost final checkpoint would not be
                    # completing.
                    self.ckpt.wait()
            except Exception as e:  # noqa: BLE001 — every step failure is
                # a restart candidate; non-restartable ones exhaust the
                # budget and re-raise as SupervisorError below.
                if self.guard is not None and self.guard.should_stop:
                    # A failure DURING the drain window: restarting now
                    # would race the preemption's hard-exit deadline.
                    # Leave whatever checkpoint exists; the relaunch
                    # resumes from it.
                    report.preempted = True
                    report.failures.append(
                        f"{type(e).__name__}: {e} (during preemption drain"
                        " — not restarted)")
                    flush_flight(
                        cause=f"{type(e).__name__}: {e}",
                        detail="failure during preemption (sigterm) drain "
                               "— not restarted", rc=1)
                    log_main("supervisor: failure during preemption drain; "
                             "stopping (relaunch resumes from the last "
                             "checkpoint)")
                    break
                report.restarts += 1
                self._consecutive_failures += 1
                report.failures.append(f"{type(e).__name__}: {e}")
                # the per-failure postmortem: the injected chaos faults'
                # flight artifacts carry the fault label verbatim in the
                # cause (e.g. "FaultError: injected crash@step=3")
                flush_flight(
                    cause=f"{type(e).__name__}: {e}",
                    detail=f"supervisor restart {report.restarts} "
                           f"(consecutive {self._consecutive_failures}/"
                           f"{self.retry.max_restarts})")
                _telemetry.counter("restarts", 1)
                if self._consecutive_failures > self.retry.max_restarts:
                    report.final_step = -1
                    if self.injector is not None:
                        report.faults_fired = list(self.injector.fired)
                        report.faults_unfired = self.injector.unfired()
                    flush_flight(
                        cause=f"supervisor abort: retry budget "
                              f"({self.retry.max_restarts}) exhausted; "
                              f"last failure: {type(e).__name__}: {e}",
                        detail="SupervisorError", rc=1)
                    err = SupervisorError(
                        f"giving up after {self.retry.max_restarts} "
                        f"consecutive restart(s); last failure: {e}")
                    err.report = report  # the chaos CLI reports even a loss
                    raise err from e
                delay = self.retry.delay_s(self._consecutive_failures, rng)
                log_main(f"supervisor: step failure ({type(e).__name__}: "
                         f"{e}) — restart {self._consecutive_failures}/"
                         f"{self.retry.max_restarts} in {delay:.2f}s")
                self.sleep(delay)
                # elastic resize rides THIS restart (already counted,
                # flighted, and backed off above — a resize is one
                # restart, never two): re-plan the mesh to the surviving
                # replica count, then restore-and-reshard below
                resize = None
                if (self.replan_cb is not None
                        and isinstance(e, ReplicaDeathError)):
                    resize = self._replan(e, report)
                state, epoch, step = self._restore_or_fresh(report, spe)
                if resize is not None:
                    resize.update(label=self._last_restore_label,
                                  epoch=epoch, step=step)
                    report.resizes.append(resize)
                restored_abs = epoch * spe + step
                if self._last_step_entered >= 0:
                    report.steps_replayed += max(
                        0, self._last_step_entered - restored_abs)
                continue

            # the segment completed CLEAN (train + save + barrier): the
            # retry budget and backoff exponent reset — max_restarts
            # bounds consecutive failures, not lifetime faults (a long
            # run with sporadic faults hours apart must not die on its
            # Nth isolated fault; only a loop that can't get one segment
            # through exhausts the budget)
            self._consecutive_failures = 0

            if step >= spe:
                # epoch complete — BEFORE the drain check: a preemption
                # landing exactly at the boundary must still emit the
                # finished epoch's validation/CSV row (the plain loop
                # does; the supervised path keeps the identical contract)
                if self.epoch_end_cb is not None:
                    self.epoch_end_cb(epoch, state, loss, acc, seconds)
                epoch, step = epoch + 1, 0

            if (self.control is not None and epoch < epochs
                    and not (self.guard is not None
                             and self.guard.should_stop)):
                # Control-plane boundary hook (ISSUE 20), BEFORE the grow
                # poll: the segment is drained and its checkpoint written
                # — the only anchor a decision may act on. An eviction
                # here debits the capacity watch, so the grow poll just
                # below cannot phantom-refill the evicted share; a dying
                # run (drain pending) never consults the control plane on
                # its way out.
                state = self.control.on_segment_boundary(
                    supervisor=self, report=report, state=state,
                    epoch=epoch, step=step)

            if (self.capacity_watch is not None
                    and self.replan_cb is not None and epoch < epochs
                    and not (self.guard is not None
                             and self.guard.should_stop)):
                # the GROW side of elasticity (ISSUE 12): the segment is
                # drained and its checkpoint written — the only place a
                # resize can anchor — so poll the capacity registry and
                # re-plan UP when returned capacity admits a larger
                # feasible world. A dying run (preemption drain pending
                # below) never grows on its way out.
                state = self._maybe_grow(report, state, epoch, step)

            if (self.guard is not None and self.guard.should_stop
                    and epoch < epochs):
                # (a preemption landing after the LAST epoch finished has
                # nothing left to drain — the run is simply complete)
                report.preemptions_drained += 1
                # sigterm's flight artifact (both branches: a drained stop
                # AND the chaos harness's simulated relaunch record what
                # was interrupted and where it resumes)
                flush_flight(
                    cause=f"preemption (sigterm) drained at epoch {epoch} "
                          f"step {step}/{spe}",
                    detail="supervisor drain"
                           + ("" if not self.resume_preempted
                              else " + simulated relaunch"), rc=0)
                if not self.resume_preempted:
                    report.preempted = True
                    log_main(f"supervisor: preempted — checkpointed epoch "
                             f"{epoch} step {step}/{spe}; relaunch with "
                             "--resume to continue")
                    break
                # chaos harness: simulate the relaunch in-process — reset
                # the guard (disarms its hard-exit deadline) and resume
                # from the checkpoint just written.
                log_main("supervisor: preemption drained; simulating "
                         "relaunch (restore + resume)")
                self.guard.reset()
                state, epoch, step = self._restore_or_fresh(report, spe)
                continue
        else:
            report.completed = True

        report.final_step = int(state.step)
        if self.injector is not None:
            report.faults_fired = list(self.injector.fired)
            report.faults_unfired = self.injector.unfired()
        return state, report

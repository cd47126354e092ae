"""resilience/: fault-tolerant supervisor, heartbeat, fault injection,
checkpoint integrity (ISSUE 5 acceptance).

The binding contracts:
* chaos recovery parity — a run with ``crash@step=k`` under the supervisor
  resumes from checkpoint and reaches final params BITWISE equal to an
  uninterrupted same-seed run (fp32, CPU mesh);
* step fence — a fault between the optimizer update and the checkpoint
  save does not advance the step counter twice after restore;
* checkpoint integrity — a truncated checkpoint on disk is skipped with a
  loud log and the previous valid one restores; legacy (manifest-less)
  checkpoints still restore.
"""

import json
import os
import socket
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from distributed_pytorch_training_tpu.resilience.faults import (
    FaultError, FaultInjector, FaultPlan,
)
from distributed_pytorch_training_tpu.resilience.heartbeat import (
    port_listening,
)
from distributed_pytorch_training_tpu.resilience.supervisor import (
    RetryPolicy, Supervisor, SupervisorError,
)
from distributed_pytorch_training_tpu.training.checkpoint import (
    CheckpointManager,
)

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# shared rig: one compiled tiny-ResNet trainer for every supervisor test
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rig(mesh8):
    """(trainer, state_factory, make_loader) — the chaos CLI's own tiny
    workload (resilience/__main__._build_rig), shared so the compile cost
    is paid once. `make_loader(fault_hook)` builds a fresh loader over the
    SAME dataset/seed (identical batch order) per test."""
    from distributed_pytorch_training_tpu.data.loader import ShardedLoader
    from distributed_pytorch_training_tpu.resilience.__main__ import (
        _build_rig,
    )

    trainer, state_factory, loader = _build_rig(
        mesh8, seed=0, dataset_size=64, per_device_batch=2)
    ds = loader.dataset

    def make_loader(fault_hook=None):
        return ShardedLoader(ds, mesh8, 2, shuffle=True, seed=0,
                             fault_hook=fault_hook)

    return trainer, state_factory, make_loader


def _control_params(trainer, state_factory, loader, epochs):
    """The uninterrupted same-seed trajectory (no supervisor, no faults)."""
    state = state_factory()
    spe = len(loader)
    for epoch in range(epochs):
        state, *_ = trainer.train_epoch(state, loader.epoch(epoch), epoch,
                                        spe)
    return state


def _assert_bitwise_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(x)),
                                      np.asarray(jax.device_get(y)))


_FAST_RETRY = RetryPolicy(max_restarts=4, backoff_base_s=0.01,
                          backoff_max_s=0.02, seed=0)


# ---------------------------------------------------------------------------
# FaultPlan / FaultInjector
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_parse_every_kind(self):
        plan = FaultPlan.parse("crash@step=7, sigterm@step=12,"
                               "torn_ckpt@save=2,loader_stall@step=5:2.5s")
        labels = [f.label() for f in plan.faults]
        assert labels == ["crash@step=7", "sigterm@step=12",
                          "torn_ckpt@save=2", "loader_stall@step=5:2.5s"]
        assert plan.faults[3].seconds == 2.5

    def test_empty_spec_is_empty_plan(self):
        assert not FaultPlan.parse(None)
        assert not FaultPlan.parse("")

    def test_parse_rejects_malformed(self):
        for bad, match in (
            ("explode@step=1", "unknown chaos fault kind"),
            ("crash@save=1", "triggers on"),
            ("torn_ckpt@step=1", "triggers on"),
            ("crash@step", "not kind@trigger"),
            ("loader_stall@step=5", "duration"),
            ("crash@step=5:2s", "no :SECs"),
        ):
            with pytest.raises(ValueError, match=match):
                FaultPlan.parse(bad)

    def test_injector_fires_once_and_reports(self):
        inj = FaultInjector(FaultPlan.parse("crash@step=3"),
                            log=lambda _m: None)
        inj.on_step(2)  # no match
        with pytest.raises(FaultError, match="crash@step=3"):
            inj.on_step(3)
        inj.on_step(3)  # the REPLAY of step 3 after restore must pass
        assert inj.fired == ["crash@step=3"]
        assert inj.unfired() == []

    def test_repeat_counts_parse_and_fire_per_occurrence(self):
        """ISSUE-11 satellite: `kind@trigger=N xK` fires K times, one per
        matching trigger occurrence (the elastic replay re-crosses the
        fence), then is spent; existing one-shot specs are unchanged."""
        plan = FaultPlan.parse("replica_death@step=3x2, crash@step=5")
        assert [f.count for f in plan.faults] == [2, 1]
        # the spec-form label reports the REMAINING repeats
        assert plan.faults[0].label(remaining=2) == "replica_death@step=3x2"
        inj = FaultInjector(plan, log=lambda _m: None)
        from distributed_pytorch_training_tpu.resilience.faults import (
            ReplicaDeathError,
        )

        for _ in range(2):
            with pytest.raises(ReplicaDeathError, match="replica_death"):
                inj.on_step(3)
        inj.on_step(3)  # spent: the third crossing passes
        assert inj.fired == ["replica_death@step=3"] * 2
        assert inj.unfired() == ["crash@step=5"]
        # space form parses too (the ISSUE's `kind@trigger=N xK` spelling)
        assert FaultPlan.parse("crash@step=3 x2").faults[0].count == 2

    def test_repeat_count_zero_is_loud(self):
        with pytest.raises(ValueError, match="repeat count"):
            FaultPlan.parse("crash@step=3x0")

    def test_capacity_return_parses_and_notifies_watch(self):
        """ISSUE-12: capacity_return@step=k is a non-raising fault — it
        credits the armed CapacityWatch back to the full registry at the
        step fence and records itself in `fired` like any other fault."""
        from distributed_pytorch_training_tpu.resilience.capacity import (
            CapacityWatch,
        )

        watch = CapacityWatch(total=8, available=5)
        inj = FaultInjector(FaultPlan.parse("capacity_return@step=2"),
                            log=lambda _m: None, capacity_watch=watch)
        inj.on_step(1)
        assert watch.available() == 5
        inj.on_step(2)  # no raise: capacity coming back is not a failure
        assert watch.available() == 8
        assert watch.returned.is_set()
        inj.on_step(2)  # spent
        assert inj.fired == ["capacity_return@step=2"]
        assert inj.unfired() == []

    def test_capacity_return_without_watch_is_harmless(self):
        logs = []
        inj = FaultInjector(FaultPlan.parse("capacity_return@step=0"),
                            log=logs.append)
        inj.on_step(0)
        assert inj.fired == ["capacity_return@step=0"]
        assert any("no CapacityWatch" in m for m in logs)

    def test_loader_stall_sleeps_once(self):
        inj = FaultInjector(FaultPlan.parse("loader_stall@step=1:0.15s"),
                            log=lambda _m: None)
        t0 = time.monotonic()
        inj.on_loader_batch(0)
        assert time.monotonic() - t0 < 0.1
        inj.on_loader_batch(1)
        assert time.monotonic() - t0 >= 0.15
        assert inj.fired == ["loader_stall@step=1:0.15s"]


# ---------------------------------------------------------------------------
# checkpoint integrity (manifest + verified restore)
# ---------------------------------------------------------------------------


def _truncate_largest(step_dir: Path) -> Path:
    files = sorted((p for p in step_dir.rglob("*") if p.is_file()),
                   key=lambda p: p.stat().st_size, reverse=True)
    with open(files[0], "r+b") as f:
        f.truncate(files[0].stat().st_size // 2)
    return files[0]


class TestCheckpointIntegrity:
    def test_truncated_checkpoint_skipped_loudly(self, rig, tmp_path,
                                                 capsys):
        """The acceptance case: tear the NEWEST checkpoint on disk —
        restore_latest must log loudly, skip it, and restore the previous
        valid one instead of crashing."""
        _trainer, state_factory, _ml = rig
        state = state_factory()
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(1, state, epoch=1)
        mgr.save(2, state, epoch=2)
        mgr.wait()  # tampering below simulates POST-finalize corruption
        _truncate_largest(tmp_path / "ckpt" / "2")

        restored = mgr.restore_latest(state_factory())
        mgr.close()
        assert restored is not None
        _state, epoch, step = restored
        assert (epoch, step) == (1, 0)  # the previous valid one
        assert mgr.last_skipped == [2]
        out = capsys.readouterr().out
        assert "CHECKPOINT INTEGRITY" in out and "truncated" in out

    def test_digest_corruption_detected(self, rig, tmp_path):
        """Same-size corruption (bit flips) must be caught by the sha256,
        not just the size check."""
        _trainer, state_factory, _ml = rig
        state = state_factory()
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(1, state, epoch=1)
        mgr.save(2, state, epoch=2)
        mgr.wait()  # corrupt the FINALIZED files, not an in-flight write
        files = sorted(((tmp_path / "ckpt" / "2").rglob("*")),
                       key=lambda p: p.stat().st_size if p.is_file() else 0,
                       reverse=True)
        blob = bytearray(files[0].read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        files[0].write_bytes(bytes(blob))
        assert "digest mismatch" in mgr.verify(2)
        restored = mgr.restore_latest(state_factory())
        mgr.close()
        assert restored is not None and restored[1] == 1

    def test_legacy_manifestless_checkpoint_restores(self, rig, tmp_path):
        """Checkpoints written before manifests existed have nothing to
        verify — they must restore exactly as before (no false skip)."""
        _trainer, state_factory, _ml = rig
        state = state_factory()
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(3, state, epoch=3)
        mgr.wait()
        manifest = tmp_path / "ckpt" / ".manifests" / "3.json"
        assert manifest.exists()
        manifest.unlink()
        assert mgr.verify(3) is None  # legacy: nothing to check
        restored = mgr.restore_latest(state_factory())
        mgr.close()
        assert restored is not None and restored[1] == 3
        assert mgr.last_skipped == []

    def test_all_checkpoints_torn_returns_none(self, rig, tmp_path, capsys):
        _trainer, state_factory, _ml = rig
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(1, state_factory(), epoch=1)
        mgr.wait()
        _truncate_largest(tmp_path / "ckpt" / "1")
        assert mgr.restore_latest(state_factory()) is None
        mgr.close()
        assert "failed verification" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# async (snapshot-then-write) checkpointing
# ---------------------------------------------------------------------------


class TestAsyncSave:
    """ISSUE 6 tentpole 1: ``save`` blocks only for the device→host
    snapshot; the orbax write + manifest run on a background writer. The
    async window must not widen the torn-checkpoint window silently, and a
    failed background write must surface at the next save/wait barrier."""

    def test_save_returns_before_write_finalizes(self, rig, tmp_path):
        """The overlap itself: save() returns while the writer still holds
        the un-finalized checkpoint (pending marker present, no manifest);
        wait() finalizes it and the manifest verifies clean."""
        _trainer, state_factory, _ml = rig
        gate, entered = threading.Event(), threading.Event()

        def hold(_label):
            entered.set()
            assert gate.wait(timeout=30.0)

        mgr = CheckpointManager(str(tmp_path / "ckpt"),
                                pre_finalize_hook=hold)
        mgr.save(1, state_factory(), epoch=1)
        # save() already returned; the writer is parked inside the hook
        # (after the orbax commit, before the manifest)
        assert entered.wait(timeout=30.0)
        manifests = tmp_path / "ckpt" / ".manifests"
        assert (manifests / "1.pending").exists()
        assert not (manifests / "1.json").exists()
        gate.set()
        mgr.wait()
        assert (manifests / "1.json").exists()
        assert not (manifests / "1.pending").exists()
        assert mgr.verify(1) is None
        mgr.close()

    def test_blocked_time_collapses_to_snapshot(self, rig, tmp_path):
        """The acceptance A/B (CPU mesh): with a 0.3s stall planted in the
        write path, the sync save blocks the caller >=300ms; the async save
        returns without paying it — blocked time ~= the snapshot cost."""
        _trainer, state_factory, _ml = rig
        state = state_factory()

        def stall(_label):
            time.sleep(0.3)

        sync = CheckpointManager(str(tmp_path / "sync"), async_save=False,
                                 pre_finalize_hook=stall)
        sync.save(1, state, epoch=1)
        sync_blocked = sync.save_blocked_ms
        sync.close()

        asyn = CheckpointManager(str(tmp_path / "async"),
                                 pre_finalize_hook=stall)
        asyn.save(1, state, epoch=1)
        async_blocked = asyn.save_blocked_ms  # before wait(): the loop's view
        asyn.wait()
        asyn.close()
        assert sync_blocked >= 300.0
        assert async_blocked <= sync_blocked - 250.0  # the stall moved off
        assert asyn.snapshot_ms <= async_blocked
        assert asyn.saves_started == sync.saves_started == 1

    def test_crash_between_commit_and_finalize_skipped_loudly(
            self, rig, tmp_path, capsys):
        """CI satellite: a crash injected between the orbax commit and the
        manifest finalize (the exact async window) leaves a checkpoint that
        restore_latest skips LOUDLY — never one that masquerades as a
        trusted legacy checkpoint — and a re-save over the torn label
        recovers it fully."""
        _trainer, state_factory, _ml = rig
        inj = FaultInjector(FaultPlan.parse("crash_during_save@save=1"),
                            log=lambda _m: None)
        mgr = CheckpointManager(str(tmp_path / "ckpt"),
                                pre_finalize_hook=inj.on_save_finalize)
        state = state_factory()
        mgr.save(1, state, epoch=1)
        with pytest.raises(FaultError, match="crash_during_save"):
            mgr.wait()  # the writer's death surfaces at the barrier
        manifests = tmp_path / "ckpt" / ".manifests"
        assert (manifests / "1.pending").exists()
        assert not (manifests / "1.json").exists()
        assert "never finalized" in mgr.verify(1)
        assert mgr.restore_latest(state_factory()) is None
        assert mgr.last_skipped == [1]
        assert "never finalized" in capsys.readouterr().out
        # the fault fired once: the replayed save must finalize normally
        mgr.save(1, state, epoch=1)
        mgr.wait()
        assert mgr.verify(1) is None
        restored = mgr.restore_latest(state_factory())
        mgr.close()
        assert restored is not None and restored[1] == 1

    def test_failed_async_write_surfaces_at_next_save(self, rig, tmp_path):
        """The other barrier: the NEXT save joins the failed write first
        and re-raises — a lost checkpoint is never silent, and the next
        attempt proceeds cleanly afterwards."""
        _trainer, state_factory, _ml = rig
        armed = {"on": True}

        def hook(_label):
            if armed["on"]:
                armed["on"] = False
                raise RuntimeError("disk gone")

        mgr = CheckpointManager(str(tmp_path / "ckpt"),
                                pre_finalize_hook=hook)
        state = state_factory()
        mgr.save(1, state, epoch=1)
        with pytest.raises(RuntimeError, match="disk gone"):
            mgr.save(2, state, epoch=2)
        mgr.save(2, state, epoch=2)  # the error was consumed at the barrier
        mgr.wait()
        assert mgr.verify(2) is None
        restored = mgr.restore_latest(state_factory())
        mgr.close()
        assert restored is not None and restored[1] == 2
        assert "never finalized" in mgr.verify(1)  # the lost save is torn


# ---------------------------------------------------------------------------
# CapacityWatch: the grow-side registry (ISSUE 12)
# ---------------------------------------------------------------------------


class TestCapacityWatch:
    def _watch(self, **kw):
        from distributed_pytorch_training_tpu.resilience.capacity import (
            CapacityWatch,
        )

        return CapacityWatch(**kw)

    def test_lose_restore_sync_bounds(self):
        w = self._watch(total=8)
        assert w.available() == 8
        assert w.lose(3) == 5
        assert w.lose(99) == 0     # floor at zero, never negative
        assert w.restore(2) == 2
        assert w.restore() == 8    # None = back to full
        assert w.restore(99) == 8  # ceiling at total
        assert w.sync(3) == 3      # absolute (the death-restart path)
        assert w.sync(99) == 8     # clamped both ways
        assert w.sync(-1) == 0

    def test_poll_grow_only_above_current_world(self):
        w = self._watch(total=8, available=4)
        assert w.poll_grow(4) is None      # nothing returned yet
        assert w.poll_grow(None) is None   # unknown world: never grow
        w.restore()
        assert w.poll_grow(4) == 8
        assert not w.returned.is_set()     # poll consumes the hint
        assert w.poll_grow(8) is None      # already at capacity

    def test_probe_feed_syncs_available(self):
        feed = {"n": 3}
        w = self._watch(total=8, probe=lambda: feed["n"])
        assert w.available() == 3
        feed["n"] = 12                     # clamped to the registry total
        assert w.available() == 8
        assert w.returned.is_set()

    def test_validation_is_loud(self):
        import pytest as _pytest

        with _pytest.raises(ValueError, match=">= 1 replica"):
            self._watch(total=0)
        with _pytest.raises(ValueError, match="must lie in"):
            self._watch(total=4, available=9)


# ---------------------------------------------------------------------------
# supervisor: crash recovery, step fence, torn-save recovery, preemption
# ---------------------------------------------------------------------------


class TestSupervisor:
    def test_crash_recovery_bitwise_parity(self, rig, tmp_path):
        """ISSUE-5 acceptance: crash@step=5 under the supervisor — the
        last checkpoint precedes the crash (step 4's update applied but
        unsaved: the fault sits BETWEEN optimizer update and save), so the
        supervisor must restore, replay exactly the lost step, and land
        bitwise where the uninterrupted run lands (fp32, CPU mesh). The
        final step counter equals the uninterrupted run's — no step
        double-applied, none skipped."""
        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("crash@step=5"),
                            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 post_save_hook=inj.on_save)
        sup = Supervisor(trainer, ckpt, state_factory,
                         make_loader(inj.on_loader_batch),
                         retry=_FAST_RETRY, injector=inj,
                         checkpoint_every_steps=2)
        state, report = sup.run(epochs=2)
        ckpt.close()
        assert report.completed and report.restarts == 1
        assert report.fence_violations == 0
        assert report.steps_replayed == 1  # step 4 ran twice, nothing else
        assert report.faults_fired == ["crash@step=5"]
        assert int(state.step) == 8  # 2 epochs x 4 steps, no double-apply

        control = _control_params(trainer, state_factory, make_loader(), 2)
        assert int(control.step) == 8
        _assert_bitwise_equal(state.params, control.params)
        _assert_bitwise_equal(state.batch_stats, control.batch_stats)

    def test_torn_save_skipped_then_bitwise_parity(self, rig, tmp_path):
        """torn_ckpt@save=2 tears the epoch-0 checkpoint AFTER its manifest
        was written; the later crash must restore PAST it (integrity skip)
        to the older valid save, replay the longer gap, and still land
        bitwise-equal."""
        trainer, state_factory, make_loader = rig
        inj = FaultInjector(
            FaultPlan.parse("torn_ckpt@save=2,crash@step=5"),
            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 post_save_hook=inj.on_save)
        sup = Supervisor(trainer, ckpt, state_factory,
                         make_loader(inj.on_loader_batch),
                         retry=_FAST_RETRY, injector=inj,
                         checkpoint_every_steps=2)
        state, report = sup.run(epochs=2)
        ckpt.close()
        assert report.completed and report.restarts == 1
        assert report.checkpoints_skipped == 1  # the torn save 2 (label 4)
        assert report.steps_replayed == 3       # restored at 2, crashed at 5
        assert int(state.step) == 8
        control = _control_params(trainer, state_factory, make_loader(), 2)
        _assert_bitwise_equal(state.params, control.params)

    def test_sigterm_drains_then_resumes_bitwise(self, rig, tmp_path):
        """sigterm@step=6 goes through the real PreemptionGuard: the
        segment stops at the next step boundary, checkpoints, and (chaos
        mode) the simulated relaunch resumes the exact trajectory."""
        from distributed_pytorch_training_tpu.training.preemption import (
            PreemptionGuard,
        )

        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("sigterm@step=6"),
                            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 post_save_hook=inj.on_save)
        guard = PreemptionGuard.install()
        try:
            sup = Supervisor(trainer, ckpt, state_factory,
                             make_loader(inj.on_loader_batch),
                             retry=_FAST_RETRY, guard=guard, injector=inj,
                             checkpoint_every_steps=2,
                             resume_preempted=True)
            state, report = sup.run(epochs=2)
        finally:
            guard.reset()
            ckpt.close()
        assert report.completed
        assert report.preemptions_drained == 1
        assert report.restarts == 0  # a drain is not a failure
        assert int(state.step) == 8
        control = _control_params(trainer, state_factory, make_loader(), 2)
        _assert_bitwise_equal(state.params, control.params)

    def test_crash_during_save_recovered_bitwise(self, rig, tmp_path):
        """ISSUE-6 acceptance: crash_during_save@save=2 kills the async
        BACKGROUND writer between orbax commit and manifest. The failure
        surfaces at the next save barrier — inside the recovery scope — so
        the supervisor restores past the half-born checkpoint (integrity
        skip via the pending marker), replays, and lands bitwise-equal to
        the uninterrupted same-seed run with async saves enabled."""
        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("crash_during_save@save=2"),
                            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 post_save_hook=inj.on_save,
                                 pre_finalize_hook=inj.on_save_finalize)
        sup = Supervisor(trainer, ckpt, state_factory,
                         make_loader(inj.on_loader_batch),
                         retry=_FAST_RETRY, injector=inj,
                         checkpoint_every_steps=2)
        state, report = sup.run(epochs=2)
        ckpt.close()
        assert report.completed and report.restarts == 1
        assert report.faults_fired == ["crash_during_save@save=2"]
        assert report.checkpoints_skipped == 1  # the half-born label 4
        assert report.fence_violations == 0
        assert int(state.step) == 8
        control = _control_params(trainer, state_factory, make_loader(), 2)
        _assert_bitwise_equal(state.params, control.params)
        _assert_bitwise_equal(state.batch_stats, control.batch_stats)

    def test_step_fence_detects_mismatched_coordinate(self, rig, tmp_path):
        """A checkpoint whose optimizer step disagrees with its (epoch,
        step) coordinate is the double-apply hazard: the supervisor must
        flag it and resume at the OPTIMIZER's position."""
        trainer, state_factory, make_loader = rig
        state = state_factory()  # step 0
        ckpt = CheckpointManager(str(tmp_path / "ckpt"))
        ckpt.save(3, state, epoch=0, step_in_epoch=3)  # lies: claims step 3
        sup = Supervisor(trainer, ckpt, state_factory, make_loader(),
                         retry=_FAST_RETRY)
        from distributed_pytorch_training_tpu.resilience.supervisor import (
            RunReport,
        )
        report = RunReport()
        _state, epoch, step = sup._restore_or_fresh(report, spe=4)
        ckpt.close()
        assert report.fence_violations == 1
        assert (epoch, step) == (0, 0)  # the optimizer's true position

    def test_gives_up_after_retry_budget(self, rig, tmp_path):
        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("crash@step=0,crash@step=1"),
                            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"))
        sup = Supervisor(trainer, ckpt, state_factory, make_loader(),
                         retry=RetryPolicy(max_restarts=1,
                                           backoff_base_s=0.01),
                         injector=inj, checkpoint_every_steps=2)
        with pytest.raises(SupervisorError, match="giving up"):
            sup.run(epochs=1)
        ckpt.close()

    def test_fresh_run_never_restores_stale_checkpoints(self, rig,
                                                        tmp_path):
        """trust_existing=False (train.py without --resume): a directory
        holding a PREVIOUS run's checkpoints must not leak into a fresh
        trajectory — a crash before the first in-run save restarts from
        scratch (the stale label, higher than anything this run wrote,
        would otherwise place the trajectory past `epochs` and the run
        would 'complete' on another run's params)."""
        trainer, state_factory, make_loader = rig
        stale = CheckpointManager(str(tmp_path / "ckpt"))
        stale.save(8, state_factory(), epoch=2)  # a finished 2-epoch run
        stale.close()

        inj = FaultInjector(FaultPlan.parse("crash@step=1"),
                            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 post_save_hook=inj.on_save)
        sup = Supervisor(trainer, ckpt, state_factory,
                         make_loader(inj.on_loader_batch),
                         retry=_FAST_RETRY, injector=inj,
                         checkpoint_every_steps=2, trust_existing=False)
        state, report = sup.run(epochs=2,
                                initial=(state_factory(), 0, 0))
        ckpt.close()
        assert report.completed and report.restarts == 1
        assert int(state.step) == 8  # trained 2 real epochs, not stale
        control = _control_params(trainer, state_factory, make_loader(), 2)
        _assert_bitwise_equal(state.params, control.params)

    def test_loader_stall_is_survived(self, rig, tmp_path):
        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("loader_stall@step=1:0.2s"),
                            log=lambda _m: None)
        sup = Supervisor(trainer, None, state_factory,
                         make_loader(inj.on_loader_batch),
                         retry=_FAST_RETRY, injector=inj)
        state, report = sup.run(epochs=1)
        assert report.completed and report.restarts == 0
        assert report.faults_fired == ["loader_stall@step=1:0.2s"]
        assert int(state.step) == 4

    @pytest.mark.slow  # ~7 s; restart/resize/flight accounting stays fast via the chaos CLI bidirectional e2e, jitter via the RetryPolicy unit legs
    def test_elastic_resize_one_restart_one_flight_deterministic_jitter(
            self, rig, tmp_path):
        """ISSUE-11 satellite: a restart that RESIZES rides the normal
        retry path — exactly one restart counted, one flight flushed (its
        cause quotes the replica_death label), and the RetryPolicy's
        deterministic jitter is the one backoff slept. The resize record
        lands in report.resizes (label None: no checkpoint manager, the
        restart is from scratch at the new world)."""
        import random

        from distributed_pytorch_training_tpu import telemetry
        from distributed_pytorch_training_tpu.parallel import (
            MeshSpec, build_mesh,
        )
        from distributed_pytorch_training_tpu.resilience.__main__ import (
            _build_rig,
        )
        from distributed_pytorch_training_tpu.resilience.elastic import (
            ElasticPlan,
        )

        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("replica_death@step=1"),
                            log=lambda _m: None)
        mesh4 = build_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
        # same GLOBAL batch (16): per-device batch doubles at world 4
        t4, sf4, l4 = _build_rig(mesh4, seed=0, dataset_size=64,
                                 per_device_batch=4)

        def replan(survivors):
            assert survivors == 7  # world 8 minus the dead replica
            return ElasticPlan(trainer=t4, loader=l4, state_factory=sf4,
                               world=4)

        sleeps = []
        telemetry.configure(str(tmp_path / "telemetry.jsonl"))
        try:
            sup = Supervisor(trainer, None, state_factory,
                             make_loader(inj.on_loader_batch),
                             retry=_FAST_RETRY, injector=inj,
                             replan_cb=replan, sleep=sleeps.append)
            state, report = sup.run(epochs=1)
        finally:
            telemetry.reset()
        assert report.completed and report.restarts == 1
        assert report.resizes == [{"from_world": 8, "to_world": 4,
                                   "survivors": 7, "label": None,
                                   "epoch": 0, "step": 0,
                                   "direction": "shrink"}]
        assert int(state.step) == 4  # the full epoch ran at world 4
        flights = sorted(tmp_path.glob("flight_*.json"))
        assert len(flights) == 1
        assert "replica_death@step=1" in flights[0].read_text()
        expect = _FAST_RETRY.delay_s(1, random.Random(_FAST_RETRY.seed))
        assert sleeps == [expect]  # jitter stays deterministic

    def test_retry_budget_resets_after_clean_segment(self, rig, tmp_path):
        """ISSUE-12 satellite: two isolated faults separated by clean
        segments must BOTH restart at consecutive-attempt 1 — max_restarts
        bounds consecutive failures, not lifetime faults. max_restarts=1
        here: before the reset existed, the second fault pushed the
        lifetime counter to 2 > 1 and a perfectly recoverable run died."""
        import random

        trainer, state_factory, make_loader = rig
        inj = FaultInjector(FaultPlan.parse("crash@step=1,crash@step=5"),
                            log=lambda _m: None)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 post_save_hook=inj.on_save)
        retry = RetryPolicy(max_restarts=1, backoff_base_s=0.01,
                            backoff_max_s=0.02, seed=0)
        sleeps = []
        sup = Supervisor(trainer, ckpt, state_factory,
                         make_loader(inj.on_loader_batch),
                         retry=retry, injector=inj,
                         checkpoint_every_steps=2, sleep=sleeps.append)
        state, report = sup.run(epochs=2)
        ckpt.close()
        assert report.completed and report.restarts == 2
        assert report.faults_fired == ["crash@step=1", "crash@step=5"]
        # both backoffs are ATTEMPT-1 delays (the exponent reset with the
        # budget); the jitter stream still advances deterministically
        rng = random.Random(retry.seed)
        assert sleeps == [retry.delay_s(1, rng), retry.delay_s(1, rng)]
        assert int(state.step) == 8
        control = _control_params(trainer, state_factory, make_loader(), 2)
        _assert_bitwise_equal(state.params, control.params)

    def test_supervisor_grows_at_segment_boundary(self, rig, tmp_path):
        """ISSUE-12 tentpole: capacity returning mid-segment grows the
        run at the NEXT segment boundary — no restart, no replay, no
        flight; the resize record anchors on the boundary checkpoint and
        the run finishes at the grown world."""
        from distributed_pytorch_training_tpu import telemetry
        from distributed_pytorch_training_tpu.parallel import (
            MeshSpec, build_mesh,
        )
        from distributed_pytorch_training_tpu.resilience.__main__ import (
            _build_rig,
        )
        from distributed_pytorch_training_tpu.resilience.capacity import (
            CapacityWatch,
        )
        from distributed_pytorch_training_tpu.resilience.elastic import (
            ElasticPlan, plan_elastic_world,
        )

        mesh4 = build_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
        # the run STARTS shrunken (world 4, per-device batch 4) — the
        # fleet lost half its replicas before this process launched
        t4, sf4, l4 = _build_rig(mesh4, seed=0, dataset_size=64,
                                 per_device_batch=4)
        trainer8, state_factory8, make_loader = rig
        watch = CapacityWatch(total=8, available=4)
        inj = FaultInjector(FaultPlan.parse("capacity_return@step=1"),
                            log=lambda _m: None, capacity_watch=watch)
        worlds_asked = []

        def replan(available):
            worlds_asked.append(available)
            world = plan_elastic_world(available, 16)
            assert world == 8
            return ElasticPlan(trainer=trainer8,
                               loader=make_loader(inj.on_loader_batch),
                               state_factory=state_factory8, world=8)

        ckpt = CheckpointManager(str(tmp_path / "ckpt"))
        telemetry.configure(str(tmp_path / "telemetry.jsonl"))
        try:
            sup = Supervisor(t4, ckpt, sf4, l4, retry=_FAST_RETRY,
                             injector=inj, checkpoint_every_steps=2,
                             replan_cb=replan, capacity_watch=watch)
            state, report = sup.run(epochs=1)
            events = telemetry.get().tail(512)
        finally:
            telemetry.reset()
            ckpt.close()
        assert report.completed and report.restarts == 0
        assert report.resizes == [{"from_world": 4, "to_world": 8,
                                   "survivors": 8, "label": 2,
                                   "epoch": 0, "step": 2,
                                   "direction": "grow"}]
        assert worlds_asked == [8]
        assert int(state.step) == 4
        assert not list(tmp_path.glob("flight_*.json"))  # a grow is not
        # an abnormal exit
        names = [e["name"] for e in events if e["kind"] == "span"]
        assert "elastic_grow" in names and "capacity_watch" in names

    def test_grow_skipped_when_no_larger_world_is_feasible(self, rig,
                                                           tmp_path):
        """Capacity returning in a quantity no feasible world can use
        (6 available, global batch 16 -> largest divisor is still 4)
        must keep the run at its current world, resize-free."""
        from distributed_pytorch_training_tpu.parallel import (
            MeshSpec, build_mesh,
        )
        from distributed_pytorch_training_tpu.resilience.__main__ import (
            _build_rig,
        )
        from distributed_pytorch_training_tpu.resilience.capacity import (
            CapacityWatch,
        )
        from distributed_pytorch_training_tpu.resilience.elastic import (
            ElasticPlan, plan_elastic_world,
        )

        mesh4 = build_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
        t4, sf4, l4 = _build_rig(mesh4, seed=0, dataset_size=64,
                                 per_device_batch=4)
        # only 6 replicas ever exist: restore() tops out at 6, whose
        # largest batch-dividing world is still 4
        watch = CapacityWatch(total=6, available=4)
        inj = FaultInjector(FaultPlan.parse("capacity_return@step=1"),
                            log=lambda _m: None, capacity_watch=watch)

        def replan(available):
            world = plan_elastic_world(available, 16)
            return ElasticPlan(trainer=t4, loader=l4, state_factory=sf4,
                               world=world)

        sup = Supervisor(t4, None, sf4, l4, retry=_FAST_RETRY,
                         injector=inj, checkpoint_every_steps=2,
                         replan_cb=replan, capacity_watch=watch)
        state, report = sup.run(epochs=1)
        assert report.completed and report.resizes == []
        assert int(state.step) == 4

    def test_grow_deferred_when_anchor_save_is_lost(self, rig, tmp_path):
        """A grow must anchor on a DURABLE checkpoint: when the boundary
        save's async write fails, the grow is deferred (recorded in
        failures, no resize), the torn label is skipped by later
        restores, and the run still completes at the original world."""
        from distributed_pytorch_training_tpu.parallel import (
            MeshSpec, build_mesh,
        )
        from distributed_pytorch_training_tpu.resilience.__main__ import (
            _build_rig,
        )
        from distributed_pytorch_training_tpu.resilience.capacity import (
            CapacityWatch,
        )
        from distributed_pytorch_training_tpu.resilience.elastic import (
            ElasticPlan,
        )

        mesh4 = build_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
        t4, sf4, l4 = _build_rig(mesh4, seed=0, dataset_size=64,
                                 per_device_batch=4)
        trainer8, state_factory8, make_loader = rig
        watch = CapacityWatch(total=8, available=4)
        inj = FaultInjector(FaultPlan.parse("capacity_return@step=1"),
                            log=lambda _m: None, capacity_watch=watch)
        armed = {"on": True}

        def lose_first_save(_label):
            if armed["on"]:
                armed["on"] = False
                raise RuntimeError("disk gone under the anchor")

        def replan(available):
            return ElasticPlan(trainer=trainer8, loader=make_loader(),
                               state_factory=state_factory8, world=8)

        ckpt = CheckpointManager(str(tmp_path / "ckpt"),
                                 pre_finalize_hook=lose_first_save)
        sup = Supervisor(t4, ckpt, sf4, l4, retry=_FAST_RETRY,
                         injector=inj, checkpoint_every_steps=2,
                         replan_cb=replan, capacity_watch=watch)
        state, report = sup.run(epochs=1)
        ckpt.close()
        assert report.completed and report.resizes == []
        assert any("grow deferred" in f for f in report.failures)
        assert "never finalized" in ckpt.verify(2)  # the lost anchor
        assert int(state.step) == 4  # finished at world 4, undisturbed

    def test_retry_policy_backoff_is_bounded_and_jittered(self):
        import random

        pol = RetryPolicy(max_restarts=10, backoff_base_s=0.5,
                          backoff_factor=2.0, backoff_max_s=3.0,
                          jitter_frac=0.5, seed=7)
        rng = random.Random(pol.seed)
        delays = [pol.delay_s(i, rng) for i in range(1, 9)]
        assert all(d >= 0.5 for d in delays)
        assert all(d <= 3.0 * 1.5 for d in delays)  # cap + max jitter
        assert delays[3] > delays[0]  # grows before the cap
        rng2 = random.Random(pol.seed)
        assert delays == [pol.delay_s(i, rng2)
                          for i in range(1, 9)]  # deterministic


# ---------------------------------------------------------------------------
# the chaos CLI (the demo IS the harness) + packaging
# ---------------------------------------------------------------------------


def test_chaos_cli_recovers_and_verifies_parity(tmp_path, capsys):
    """`python -m ...resilience chaos` on a fast plan: recovery stats on
    stdout, parity verified against the no-fault control run, rc 0."""
    from distributed_pytorch_training_tpu.resilience.__main__ import main

    rc = main(["chaos", "--chaos", "crash@step=2", "--epochs", "1",
               "--checkpoint-every-steps", "2", "--max-restarts", "2",
               "--ckpt-dir", str(tmp_path / "ckpt"), "--json"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    stats = json.loads(out)
    assert rc == 0
    assert stats["completed"] is True
    assert stats["parity_bitwise"] is True
    assert stats["restarts"] == 1
    assert stats["faults_fired"] == ["crash@step=2"]
    assert stats["fence_violations"] == 0
    # the flight recorder's chaos contract (ISSUE 8): the injected fault
    # left a parseable postmortem whose cause quotes the fault label
    assert stats["flights_ok"] is True
    assert any("crash@step=2" in (f["cause"] or "")
               for f in stats["flights"])


@pytest.mark.slow  # ~10 s; narrow edge case — the recover/bidirectional chaos legs keep the CLI path fast
def test_chaos_cli_fixed_world_capacity_return_is_harmless(tmp_path,
                                                           capsys):
    """A capacity_return fault in a FIXED-world schedule (no --elastic,
    no watch) fires into the void by design — a fully-recovered run must
    still be scored RECOVERED (the grow requirement binds only under
    --elastic)."""
    from distributed_pytorch_training_tpu.resilience.__main__ import main

    rc = main(["chaos", "--chaos", "crash@step=2,capacity_return@step=3",
               "--epochs", "1", "--checkpoint-every-steps", "2",
               "--max-restarts", "2",
               "--ckpt-dir", str(tmp_path / "ckpt"), "--json"])
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert stats["completed"] and stats["parity_bitwise"] is True
    assert stats["faults_fired"] == ["crash@step=2",
                                     "capacity_return@step=3"]
    assert stats["resizes"] == []


def _chaos_elastic(tmp_path, capsys, *extra):
    from distributed_pytorch_training_tpu.resilience.__main__ import main

    rc = main(["chaos", "--elastic", "--ckpt-dir", str(tmp_path / "ckpt"),
               "--json", *extra])
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return rc, stats


def test_chaos_cli_elastic_bidirectional_bitwise_parity(tmp_path, capsys):
    """ISSUE-11 + ISSUE-12 acceptance (the tier-1 elastic smoke): the
    default `resilience chaos --elastic` schedule is now BIDIRECTIONAL —
    replica_death mid-epoch shrinks 8 -> 4 (7 survivors; 4 is the largest
    divisor of the fixed global batch), capacity_return at the step-4
    fence grows it back 4 -> 8 at the next segment boundary (one run, one
    restart, zero restarts for the grow), both resizes are recorded with
    their anchor checkpoints, the death leaves its flight, and the
    post-GROW segment is BITWISE equal to a clean same-seed continuation
    at the full world (restore the grow-anchor label at its recorded
    world, reshard, run the remainder clean)."""
    rc, stats = _chaos_elastic(tmp_path, capsys)
    assert rc == 0
    assert stats["completed"] is True
    assert stats["parity_bitwise"] is True
    assert stats["restarts"] == 1
    assert stats["faults_fired"] == ["replica_death@step=3",
                                     "capacity_return@step=4"]
    assert stats["resizes"] == [
        {"from_world": 8, "to_world": 4, "survivors": 7, "label": 2,
         "epoch": 0, "step": 2, "direction": "shrink"},
        {"from_world": 4, "to_world": 8, "survivors": 8, "label": 6,
         "epoch": 1, "step": 2, "direction": "grow"}]
    assert stats["flights_ok"] is True
    assert any("replica_death" in (f["cause"] or "")
               for f in stats["flights"])


@pytest.mark.slow
def test_chaos_cli_elastic_zero1_int8_ef_residuals(tmp_path, capsys):
    """The elastic reshard carries the FULL zero1 state across the resize
    — flat-padded moments AND the int8 wire's error-feedback residuals —
    and the post-resize segment still pins bitwise (the acceptance's
    'EF residuals included').

    Slow tier (~39 s: a multi-process chaos run with two training
    segments): the state-level half is pinned fast by test_elastic's
    zero1-int8 reshard tests, and elastic chaos-CLI parity by the
    bidirectional / fixed-world legs above."""
    rc, stats = _chaos_elastic(tmp_path, capsys,
                               "--layout", "zero1",
                               "--wire-dtype", "int8")
    assert rc == 0
    assert stats["completed"] and stats["parity_bitwise"] is True
    assert stats["resizes"] and stats["resizes"][0]["to_world"] == 4


@pytest.mark.slow
def test_chaos_cli_elastic_fsdp_int8(tmp_path, capsys):
    """Explicit FSDP across a resize: flat-sharded params + moments +
    per-group EF residuals all re-slice, post-resize bitwise parity."""
    rc, stats = _chaos_elastic(tmp_path, capsys,
                               "--layout", "fsdp",
                               "--wire-dtype", "int8")
    assert rc == 0
    assert stats["completed"] and stats["parity_bitwise"] is True


@pytest.mark.slow
def test_chaos_cli_elastic_double_resize(tmp_path, capsys):
    """The repeat-count schedule `replica_death@step=3x2`: the replay
    re-crosses the fence, the mesh shrinks twice (8 -> 4 -> 2), two
    flights land, and the post-LAST-resize segment pins bitwise (the
    control probes the checkpoint's OWN recorded world — the restored
    label may predate the first resize)."""
    rc, stats = _chaos_elastic(tmp_path, capsys,
                               "--chaos", "replica_death@step=3x2",
                               "--layout", "zero1",
                               "--wire-dtype", "int8")
    assert rc == 0
    assert stats["completed"] and stats["parity_bitwise"] is True
    assert [r["to_world"] for r in stats["resizes"]] == [4, 2]
    assert stats["restarts"] == 2
    causes = [f["cause"] or "" for f in stats["flights"]]
    assert sum("replica_death" in c for c in causes) == 2


@pytest.mark.slow
def test_chaos_cli_full_default_schedule(tmp_path, capsys):
    """The full default schedule (crash + torn save + sigterm) across two
    epochs — the CLI's own acceptance run."""
    from distributed_pytorch_training_tpu.resilience.__main__ import main

    rc = main(["chaos", "--ckpt-dir", str(tmp_path / "ckpt"), "--json"])
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert stats["completed"] and stats["parity_bitwise"]
    assert set(stats["faults_fired"]) == {
        "crash@step=3", "torn_ckpt@save=2", "crash_during_save@save=2",
        "sigterm@step=6"}
    assert stats["faults_unfired"] == []
    # EVERY fault in the default schedule leaves a parseable flight whose
    # cause matches the injected fault (the ISSUE 8 acceptance bar)
    assert stats["flights_ok"] is True
    causes = [f["cause"] or "" for f in stats["flights"]]
    for sig in ("crash@step=3", "crash_during_save@save=2",
                "torn_checkpoint", "sigterm"):
        assert any(sig in c for c in causes), (sig, causes)


def test_resilience_console_script_declared():
    """pyproject registers the `resilience` entry point next to `analysis`
    and it resolves to the CLI main."""
    pyproject = (REPO / "pyproject.toml").read_text()
    assert ('resilience = "distributed_pytorch_training_tpu.resilience.'
            '__main__:main"') in pyproject
    from distributed_pytorch_training_tpu.resilience.__main__ import main
    assert callable(main)


# ---------------------------------------------------------------------------
# TokenLoader fault hook (the LM loader's loader_stall injection point)
# ---------------------------------------------------------------------------


class TestTokenLoaderFaultHook:
    """ISSUE-6 satellite (ROADMAP-carried): the LM TokenLoader carries the
    same ``fault_hook`` / ``loader_stall`` support ShardedLoader has, with
    the chaos injector driving it."""

    def _loader(self, mesh, fault_hook=None):
        from distributed_pytorch_training_tpu.data.text import (
            TokenLoader, synthetic_token_dataset,
        )

        ds = synthetic_token_dataset(32, 16, 128, seed=0)
        return TokenLoader(ds, mesh, per_device_batch=2, shuffle=True,
                           seed=0, fault_hook=fault_hook)

    def test_loader_stall_fires_and_batches_unchanged(self, mesh8):
        """The chaos fault stalls exactly the targeted step and perturbs
        NOTHING about the produced batches (deterministic sampler order is
        the bitwise-parity foundation)."""
        inj = FaultInjector(FaultPlan.parse("loader_stall@step=1:0.15s"),
                            log=lambda _m: None)
        plain = list(self._loader(mesh8).epoch(0))
        t0 = time.monotonic()
        stalled = list(self._loader(mesh8, inj.on_loader_batch).epoch(0))
        assert time.monotonic() - t0 >= 0.15
        assert inj.fired == ["loader_stall@step=1:0.15s"]
        assert len(plain) == len(stalled) == 2  # 32 rows / global 16
        for a, b in zip(plain, stalled):
            np.testing.assert_array_equal(np.asarray(a["input_ids"]),
                                          np.asarray(b["input_ids"]))
            np.testing.assert_array_equal(np.asarray(a["weight"]),
                                          np.asarray(b["weight"]))

    def test_hook_sees_resume_offset(self, mesh8):
        """A supervisor resume enters the epoch at start_step > 0: the hook
        must see ABSOLUTE in-epoch indices (ShardedLoader's convention), or
        a loader_stall@step=k fault would re-target after a restart."""
        seen = []
        list(self._loader(mesh8, seen.append).epoch(0, start_step=1))
        assert seen == [1]

    def test_train_py_wires_the_hook(self):
        """train.py really passes the chaos injector into the LM loader
        (the constraint was carried precisely because it didn't)."""
        src = (REPO / "train.py").read_text()
        lm_loader = src.split("train_loader = TokenLoader", 1)[1]
        assert "fault_hook=(chaos.on_loader_batch" in lm_loader[:400]


# ---------------------------------------------------------------------------
# heartbeat: TCP port liveness (what the control plane's capacity probe reads)
# ---------------------------------------------------------------------------


class TestPortLiveness:
    def test_port_listening_probe(self):
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(8)
        try:
            assert port_listening(srv.getsockname()[1], timeout=0.5)
        finally:
            srv.close()
        bound = socket.socket()
        bound.bind(("127.0.0.1", 0))  # bound but NOT listening
        try:
            assert not port_listening(bound.getsockname()[1], timeout=0.2)
        finally:
            bound.close()

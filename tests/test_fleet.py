"""resilience/fleet.py (ISSUE 12): the cross-process orchestrator.

Fast tests drive the orchestrator with STUB children (tiny scripts, no
jax): worlds planned from the capacity feed, resume decisions from the
manifest progress probe, generation/rank env stamping, exit-code
interpretation, mismatch-escape detection, launch-budget exhaustion, and
the per-generation flight accounting. The real train.py e2e — kill at
full world -> relaunch at half world -> capacity return -> relaunch at
full world, cross-world zero1 restores through train.py's elastic
--resume, final checkpoint bitwise vs an uninterrupted control child —
is the slow test at the bottom (also: `resilience fleet`).
"""

import json
import sys
from pathlib import Path

import pytest

from distributed_pytorch_training_tpu.resilience.fleet import (
    DIST_COORD_ENV, DIST_NPROC_ENV, DIST_PROC_ID_ENV,
    FLEET_GENERATION_ENV, FLEET_RANK_ENV, FleetOrchestrator,
    _xla_flags_for, check_fleet_flights, checkpoint_progress,
)

REPO = Path(__file__).resolve().parent.parent

# One scripted child: reads its generation from the env, records what it
# saw (argv tail + env) into the checkpoint dir, optionally fakes
# checkpoint progress by writing a manifest, optionally prints a line,
# and exits with the scripted rc.
STUB = """\
import json, os, sys
from pathlib import Path

gen = int(os.environ["{gen_env}"])
ckpt = Path(sys.argv[1])
plans = json.loads(Path(sys.argv[2]).read_text())
plan = plans[min(gen, len(plans) - 1)]
ckpt.mkdir(parents=True, exist_ok=True)
(ckpt / "seen_gen{{}}.json".format(gen)).write_text(json.dumps({{
    "args": sys.argv[3:],
    "rank": os.environ.get("{rank_env}"),
    "xla": os.environ.get("XLA_FLAGS", ""),
    "platform": os.environ.get("JAX_PLATFORMS", ""),
}}))
if plan.get("step") is not None:
    mdir = ckpt / ".manifests"
    mdir.mkdir(exist_ok=True)
    (mdir / "{{}}.json".format(plan["label"])).write_text(json.dumps(
        {{"step": plan["step"], "world_size": plan.get("world")}}))
if plan.get("print"):
    print(plan["print"])
sys.exit(plan["rc"])
""".format(gen_env=FLEET_GENERATION_ENV, rank_env=FLEET_RANK_ENV)


def _orchestrator(tmp_path, plans, capacity, *, global_batch=16,
                  target_step=12, max_launches=8, on_child_exit=None):
    stub = tmp_path / "stub_child.py"
    stub.write_text(STUB)
    plan_file = tmp_path / "plans.json"
    plan_file.write_text(json.dumps(plans))
    ckpt = tmp_path / "ckpt"

    def argv_for(world, generation, resume):
        return [sys.executable, str(stub), str(ckpt), str(plan_file),
                f"world={world}", f"resume={resume}"]

    return FleetOrchestrator(
        argv_for, ckpt, global_batch=global_batch,
        target_step=target_step, capacity_for=capacity,
        max_launches=max_launches, on_child_exit=on_child_exit,
        log=lambda _m: None), ckpt


def _seen(ckpt, generation):
    return json.loads((ckpt / f"seen_gen{generation}.json").read_text())


class TestCheckpointProgress:
    def test_empty_and_missing_dir(self, tmp_path):
        assert checkpoint_progress(tmp_path) == (-1, None)
        assert checkpoint_progress(tmp_path / "nope") == (-1, None)

    def test_newest_finalized_label_wins(self, tmp_path):
        mdir = tmp_path / ".manifests"
        mdir.mkdir()
        (mdir / "4.json").write_text(json.dumps({"step": 4,
                                                 "world_size": 8}))
        (mdir / "10.json").write_text(json.dumps({"step": 10,
                                                  "world_size": 4}))
        assert checkpoint_progress(tmp_path) == (10, 4)

    def test_torn_and_foreign_manifests_ignored(self, tmp_path):
        mdir = tmp_path / ".manifests"
        mdir.mkdir()
        (mdir / "4.json").write_text(json.dumps({"step": 4}))
        (mdir / "12.json").write_text("{ torn")       # unparseable
        (mdir / "notes.json").write_text("{}")        # non-integer stem
        assert checkpoint_progress(tmp_path) == (4, None)


class TestXlaFlags:
    def test_replaces_inherited_device_count(self):
        out = _xla_flags_for(
            4, "--xla_cpu_foo=1 --xla_force_host_platform_device_count=8")
        assert out == ("--xla_cpu_foo=1 "
                       "--xla_force_host_platform_device_count=4")
        assert _xla_flags_for(2) == \
            "--xla_force_host_platform_device_count=2"


class TestOrchestrator:
    def test_kill_shrink_grow_scenario(self, tmp_path):
        """The canonical sequence with stub children: gen0 crashes at
        world 8 having checkpointed step 4; gen1 (capacity 4 -> world 4,
        --resume) drains at step 10; gen2 (capacity back to 8) completes
        at step 12. Worlds follow plan_elastic_world(capacity), resume
        follows the manifest probe, every child is stamped with its
        generation/rank and a world-sized device count."""
        events = []
        plans = [
            {"rc": 1, "label": 4, "step": 4, "world": 8},
            {"rc": 0, "label": 10, "step": 10, "world": 4},
            {"rc": 0, "label": 12, "step": 12, "world": 8},
        ]
        orch, ckpt = _orchestrator(
            tmp_path, plans, [8, 4, 8],
            on_child_exit=lambda gen, launch: events.append(
                (gen, launch.outcome)))
        report = orch.run()
        assert report.completed is True
        assert report.relaunches == 2
        assert [l["world"] for l in report.launches] == [8, 4, 8]
        assert [l["outcome"] for l in report.launches] == \
            ["crashed", "drained", "completed"]
        assert [l["resume"] for l in report.launches] == \
            [False, True, True]
        assert report.final_step == 12 and report.final_world == 8
        assert report.mismatch_escapes == 0 and report.errors == []
        assert events == [(0, "crashed"), (1, "drained"),
                          (2, "completed")]
        for gen, world in ((0, 8), (1, 4), (2, 8)):
            seen = _seen(ckpt, gen)
            assert seen["rank"] == "0"
            assert seen["platform"] == "cpu"
            assert (f"--xla_force_host_platform_device_count={world}"
                    in seen["xla"])
            assert seen["args"] == [f"world={world}",
                                    f"resume={gen > 0}"]

    def test_capacity_feed_callable_and_non_divisor(self, tmp_path):
        """A callable capacity feed, and a non-divisor capacity (7 of
        global batch 16) planning down to the largest feasible world."""
        plans = [{"rc": 0, "label": 12, "step": 12, "world": 4}]
        orch, _ckpt = _orchestrator(tmp_path, plans, lambda gen: 7)
        report = orch.run()
        assert report.completed
        assert [l["world"] for l in report.launches] == [4]
        assert report.launches[0]["available"] == 7

    def test_mismatch_escape_is_counted(self, tmp_path):
        """A CheckpointWorldSizeMismatch surfacing in a child's output is
        the exact failure the orchestrator exists to absorb — counted as
        a hard error (the acceptance gate: zero escapes)."""
        plans = [
            {"rc": 1, "print": "CheckpointWorldSizeMismatch: checkpoint "
                               "was written at world size 8"},
            {"rc": 0, "label": 12, "step": 12, "world": 8},
        ]
        orch, _ckpt = _orchestrator(tmp_path, plans, [8])
        report = orch.run()
        assert report.completed  # the fleet still recovered...
        assert report.mismatch_escapes == 1  # ...but the gate must fail
        assert any("CheckpointWorldSizeMismatch" in e
                   for e in report.errors)

    def test_launch_budget_exhaustion(self, tmp_path):
        plans = [{"rc": 0}]  # exits clean, never makes progress
        orch, _ckpt = _orchestrator(tmp_path, plans, [8], max_launches=3)
        report = orch.run()
        assert not report.completed
        assert len(report.launches) == 3
        assert all(l["outcome"] == "drained" for l in report.launches)
        assert any("did not reach step" in e for e in report.errors)


# Multi-host stub child (ISSUE 20): every rank records the rendezvous
# contract it was stamped with; only rank 0 writes checkpoint progress
# (as in a real run, where rank 0 owns the manifest). Per-rank exit
# codes come from the plan's "rcs" list.
MH_STUB = """\
import json, os, sys
from pathlib import Path

gen = int(os.environ["{gen_env}"])
rank = int(os.environ.get("{proc_env}", "0"))
ckpt = Path(sys.argv[1])
plans = json.loads(Path(sys.argv[2]).read_text())
plan = plans[min(gen, len(plans) - 1)]
ckpt.mkdir(parents=True, exist_ok=True)
(ckpt / "mh_gen{{}}_rank{{}}.json".format(gen, rank)).write_text(
    json.dumps({{
        "args": sys.argv[3:],
        "coord": os.environ.get("{coord_env}"),
        "nproc": os.environ.get("{nproc_env}"),
        "proc_id": os.environ.get("{proc_env}"),
        "fleet_rank": os.environ.get("{rank_env}"),
        "xla": os.environ.get("XLA_FLAGS", ""),
    }}))
if rank == 0 and plan.get("step") is not None:
    mdir = ckpt / ".manifests"
    mdir.mkdir(exist_ok=True)
    (mdir / "{{}}.json".format(plan["label"])).write_text(json.dumps(
        {{"step": plan["step"], "world_size": plan.get("world")}}))
rcs = plan.get("rcs") or [plan.get("rc", 0)]
sys.exit(rcs[min(rank, len(rcs) - 1)])
""".format(gen_env=FLEET_GENERATION_ENV, rank_env=FLEET_RANK_ENV,
           coord_env=DIST_COORD_ENV, nproc_env=DIST_NPROC_ENV,
           proc_env=DIST_PROC_ID_ENV)


class TestMultiHostGenerations:
    """hosts > 1 (ISSUE 20): one generation spans `hosts` processes
    rendezvousing through the stamped DPT_COORDINATOR_ADDRESS /
    DPT_NUM_PROCESSES / DPT_PROCESS_ID contract."""

    PORT = 7310

    def _mh_orchestrator(self, tmp_path, plans, capacity, *, hosts=2,
                         target_step=12, max_launches=8):
        stub = tmp_path / "mh_stub_child.py"
        stub.write_text(MH_STUB)
        plan_file = tmp_path / "plans.json"
        plan_file.write_text(json.dumps(plans))
        ckpt = tmp_path / "ckpt"

        def argv_for(world, generation, resume, rank):
            # multi-host argv_for receives the child's rank explicitly
            return [sys.executable, str(stub), str(ckpt), str(plan_file),
                    f"world={world}", f"resume={resume}", f"rank={rank}"]

        return FleetOrchestrator(
            argv_for, ckpt, global_batch=16, target_step=target_step,
            capacity_for=capacity, max_launches=max_launches,
            hosts=hosts, coordinator_port=self.PORT,
            log=lambda _m: None), ckpt

    @staticmethod
    def _mh_seen(ckpt, generation, rank):
        return json.loads(
            (ckpt / f"mh_gen{generation}_rank{rank}.json").read_text())

    def test_requires_coordinator_port(self, tmp_path):
        with pytest.raises(ValueError, match="coordinator_port"):
            FleetOrchestrator(
                lambda **_kw: [sys.executable, "-c", "pass"],
                tmp_path / "ckpt", global_batch=16, target_step=12,
                capacity_for=[8], hosts=2)

    def test_topology_stamped_and_peers_collected(self, tmp_path):
        """Every rank of a 2-host generation sees the same coordinator
        address, nproc=2, its own process id, and world//hosts local
        devices; rank 1's rc is collected into peer_rcs and its output
        lands in a per-rank log."""
        plans = [{"rc": 0, "label": 12, "step": 12, "world": 8}]
        orch, ckpt = self._mh_orchestrator(tmp_path, plans, [8])
        report = orch.run()
        assert report.completed is True
        assert len(report.launches) == 1
        assert report.launches[0]["peer_rcs"] == [0]
        for rank in (0, 1):
            seen = self._mh_seen(ckpt, 0, rank)
            assert seen["coord"] == f"127.0.0.1:{self.PORT}"
            assert seen["nproc"] == "2"
            assert seen["proc_id"] == str(rank)
            # one generation at world 8 over 2 hosts: 4 local devices
            assert ("--xla_force_host_platform_device_count=4"
                    in seen["xla"])
            assert f"rank={rank}" in seen["args"]
        # FLEET_RANK stays the single-host restart-lineage rank (0 for
        # every child of the generation); the collective rank is
        # DPT_PROCESS_ID
        assert self._mh_seen(ckpt, 0, 1)["fleet_rank"] == "1"
        assert (ckpt / "fleet_logs" / "gen0_rank1.log").exists()

    def test_peer_crash_downgrades_and_port_advances(self, tmp_path):
        """Rank 0 exiting clean does not absolve a dead peer: the
        generation is crashed and relaunched — and the relaunch
        rendezvouses on coordinator_port + generation, never racing the
        previous coordinator's socket."""
        plans = [
            {"rcs": [0, 1], "label": 4, "step": 4, "world": 8},
            {"rcs": [0, 0], "label": 12, "step": 12, "world": 8},
        ]
        orch, ckpt = self._mh_orchestrator(tmp_path, plans, [8])
        report = orch.run()
        assert report.completed is True
        assert [l["outcome"] for l in report.launches] == \
            ["crashed", "completed"]
        assert [l["peer_rcs"] for l in report.launches] == [[1], [0]]
        assert [l["resume"] for l in report.launches] == [False, True]
        for gen in (0, 1):
            for rank in (0, 1):
                assert self._mh_seen(ckpt, gen, rank)["coord"] == \
                    f"127.0.0.1:{self.PORT + gen}"


class TestFleetFlights:
    def _flight(self, d, name, cause, gen):
        (d / name).write_text(json.dumps(
            {"cause": cause, "fleet_generation": gen}))

    def test_one_flight_per_abnormal_exit(self, tmp_path):
        self._flight(tmp_path, "flight_1_0.json",
                     "FaultError: injected crash@step=6 "
                     "[fleet gen=0 rank=0]", "0")
        self._flight(tmp_path, "flight_2_0.json",
                     "preemption (sigterm) drained at epoch 2 step 2 "
                     "[fleet gen=1 rank=0]", "1")
        launches = [
            {"generation": 0, "outcome": "crashed"},
            {"generation": 1, "outcome": "drained"},
            {"generation": 2, "outcome": "completed"},
        ]
        stats = check_fleet_flights(tmp_path, launches)
        assert stats["flights_ok"] is True
        assert stats["flight_problems"] == []

    def test_missing_and_surplus_flights_flag(self, tmp_path):
        self._flight(tmp_path, "flight_3_0.json",
                     "stray [fleet gen=2 rank=0]", "2")
        launches = [
            {"generation": 0, "outcome": "crashed"},   # no flight: bad
            {"generation": 2, "outcome": "completed"},  # flight: bad
        ]
        stats = check_fleet_flights(tmp_path, launches)
        assert stats["flights_ok"] is False
        assert len(stats["flight_problems"]) == 2

    def test_pre_existing_flights_are_ignored(self, tmp_path):
        """A reused --ckpt-dir's stale postmortems (a previous fleet run)
        must neither satisfy nor fail THIS run's accounting — the same
        guard the chaos harness applies."""
        stale = tmp_path / "flight_0_0.json"
        self._flight(tmp_path, "flight_0_0.json",
                     "old crash [fleet gen=0 rank=0]", "0")
        launches = [{"generation": 0, "outcome": "completed"}]
        # without the exclusion the completed gen-0 'left' a flight: bad
        assert check_fleet_flights(tmp_path, launches)["flights_ok"] \
            is False
        stats = check_fleet_flights(tmp_path, launches, ignore={stale})
        assert stats["flights_ok"] is True and stats["flights"] == []

    def test_drained_flight_must_name_preemption(self, tmp_path):
        self._flight(tmp_path, "flight_4_0.json",
                     "something else [fleet gen=0 rank=0]", "0")
        stats = check_fleet_flights(
            tmp_path, [{"generation": 0, "outcome": "drained"}])
        assert stats["flights_ok"] is False
        assert "not a preemption" in stats["flight_problems"][0]


class TestWatchAndScrapeWiring:
    """ISSUE 14: the orchestrator's live watch — child cleanup on an
    interrupted watch, and the metrics-port stamping contract."""

    def test_exception_in_watch_kills_the_child(self, tmp_path,
                                                monkeypatch):
        """subprocess.run's kill-on-exception contract, kept across the
        Popen switch: a Ctrl-C (or raising callback) mid-watch must not
        orphan a running training child."""
        import subprocess as sp

        sleeper = tmp_path / "sleeper.py"
        sleeper.write_text("import time\ntime.sleep(600)\n")
        ckpt = tmp_path / "ckpt"
        orch = FleetOrchestrator(
            lambda world, generation, resume: [sys.executable,
                                               str(sleeper)],
            ckpt, global_batch=16, target_step=12, capacity_for=[8],
            max_launches=1, log=lambda _m: None)
        started: list = []
        real_popen = sp.Popen

        def capture_popen(*args, **kwargs):
            proc = real_popen(*args, **kwargs)
            started.append(proc)
            return proc

        monkeypatch.setattr(sp, "Popen", capture_popen)

        def boom(proc, launch, generation):
            raise KeyboardInterrupt

        monkeypatch.setattr(orch, "_watch_child", boom)
        with pytest.raises(KeyboardInterrupt):
            orch.run()
        (proc,) = started
        assert proc.poll() is not None   # killed, not orphaned

    def test_metrics_port_stamp_is_the_base_port(self, tmp_path):
        """The child applies its own rank offset (resolve_metrics_port
        reads DPT_FLEET_RANK), so the orchestrator stamps the BASE port
        — base+rank here would offset twice."""
        from distributed_pytorch_training_tpu.telemetry.metrics_http import (
            METRICS_PORT_ENV, resolve_metrics_port,
        )

        orch, _ = _orchestrator(tmp_path, [{"rc": 0}], [8])
        orch.metrics_port = 9200
        env0 = orch._child_env(8, 0, rank=0)
        env2 = orch._child_env(8, 0, rank=2)
        assert env0[METRICS_PORT_ENV] == "9200"
        assert env2[METRICS_PORT_ENV] == "9200"
        # ... and the child-side resolution lands each rank on its own
        # port from that one stamped value
        assert resolve_metrics_port(None, rank=0) == 0  # env unset here
        import os
        os.environ[METRICS_PORT_ENV] = env2[METRICS_PORT_ENV]
        try:
            assert resolve_metrics_port(None, rank=2) == 9202
        finally:
            del os.environ[METRICS_PORT_ENV]

    def test_no_metrics_port_leaves_env_unstamped(self, tmp_path):
        from distributed_pytorch_training_tpu.telemetry.metrics_http import (
            METRICS_PORT_ENV,
        )

        orch, _ = _orchestrator(tmp_path, [{"rc": 0}], [8])
        assert METRICS_PORT_ENV not in orch._child_env(8, 0)


def test_federation_port_requires_metrics_port():
    """The fan-in proxies the children's per-rank metrics ports — asking
    for it without any child port is a misconfiguration named upfront,
    not a late 'merged page is empty' verdict failure."""
    import pytest

    from distributed_pytorch_training_tpu.resilience.__main__ import main

    with pytest.raises(SystemExit, match="requires --metrics-port"):
        main(["fleet", "--federation-port", "19000"])


def test_fleet_command_registered():
    """`resilience fleet` parses (the console-script surface) and the
    orchestrator module is importable without jax initialized."""
    import distributed_pytorch_training_tpu.resilience.fleet as fleet_mod

    assert callable(fleet_mod.fleet_main)
    from distributed_pytorch_training_tpu.resilience.__main__ import main

    # unknown option after the command must be a usage error, proving the
    # subcommand is wired into the entry point's parser
    with pytest.raises(SystemExit):
        main(["fleet", "--no-such-option"])


@pytest.mark.slow
def test_fleet_cli_e2e_kill_shrink_grow_bitwise(tmp_path, capsys,
                                                monkeypatch):
    """ISSUE-12 acceptance: the real train.py fleet — a zero1 child
    killed at full world, relaunched at half world (cross-world restore
    through train.py's elastic --resume: raw restore + reshard, flat
    moments re-sliced), drained by SIGTERM, relaunched at full world on
    capacity return, completing with the final checkpoint BITWISE equal
    to an uninterrupted control child continuing from the last handoff.
    One attributable flight per abnormal child exit; zero
    CheckpointWorldSizeMismatch escapes.

    Extended for ISSUE 14: the default schedule also injects a
    loader_stall into generation 2, and the run must yield ONE merged
    fleet summary + ONE stitched Perfetto trace covering every
    generation (exactly one pid per (gen, rank)), with the stall rank-
    AND phase-attributed in the straggler table; every child serves
    /metrics (port stamped by the orchestrator) and at least one live
    scrape must have answered with the step counter.

    Extended for ISSUE 15: ONE federated /metrics page (the fan-in
    proxy over the children's ports) must end the run carrying
    gen/rank-labelled step rows for every scraped generation, and the
    gen-2 loader_stall — with the children's watchdog warm-up shortened
    via the env knobs — must auto-arm a capture whose device_profile
    upgrades the straggler verdict to device-attributed."""
    from distributed_pytorch_training_tpu.resilience.__main__ import main

    # watchdog tuning for the children (env-inherited): the gen-2 stall
    # lands on the FIRST post-resume step, where the rolling median has
    # no warm-up — the absolute stall bound is the detector for exactly
    # that; the spike bar stays high so CPU noise cannot arm competing
    # captures
    monkeypatch.setenv("DPT_WATCHDOG_STALL_ABS_S", "1.0")
    monkeypatch.setenv("DPT_WATCHDOG_SPIKE_FACTOR", "1000.0")
    rc = main(["fleet", "--layout", "zero1",
               "--ckpt-dir", str(tmp_path), "--metrics-port", "19377",
               "--federation-port", "19397",
               "--json"])
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert stats["completed"] is True
    assert stats["parity_bitwise"] is True
    assert stats["mismatch_escapes"] == 0
    assert stats["worlds"] == [8, 4, 8]
    assert [l["outcome"] for l in stats["launches"]] == \
        ["crashed", "drained", "completed"]
    assert stats["flights_ok"] is True
    causes = [f["cause"] or "" for f in stats["flights"]]
    assert any("crash@step" in c and "[fleet gen=0" in c for c in causes)
    assert any("preemption" in c and "[fleet gen=1" in c for c in causes)
    # both cross-world restores rode the elastic resume path
    logs = sorted((Path(stats["dir"]) / "ckpt" /
                   "fleet_logs").glob("gen*.log"))
    resumed = [p.read_text(errors="replace") for p in logs[1:]]
    assert all("ELASTIC RESUME" in t for t in resumed)

    # --- the merged fleet view (ISSUE 14 acceptance) ---
    summary = stats["fleet_summary"]
    assert summary is not None and summary["n_streams"] == 3
    assert summary["identities"] == [[0, 0], [1, 0], [2, 0]]  # json lists
    assert Path(stats["fleet_summary_path"]).is_file()
    # the injected loader_stall on gen 2 is rank- AND phase-attributed
    assert stats["straggler_attributed"] is True
    hits = [s for s in stats["stragglers"]
            if s["gen"] == 2 and s["phase"] == "data_wait"]
    assert hits and hits[0]["dur_s"] >= 1.0
    # ONE stitched trace, exactly one pid/tid pair per (gen, rank)
    trace = json.loads(Path(stats["fleet_trace_path"]).read_text())
    names = {e["args"]["name"]: e["pid"]
             for e in trace["traceEvents"] if e["ph"] == "M"}
    assert names == {"gen0/rank0": 1, "gen1/rank0": 2, "gen2/rank0": 3}
    span_keys = {(e["pid"], e["tid"])
                 for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {pid for pid, _ in span_keys} == {1, 2, 3}
    # host spans on tid 1; device_profile windows (ISSUE 15) on tid 2
    assert all(tid in (1, 2) for _, tid in span_keys)
    assert all(e.get("name") == "device_profile"
               for e in trace["traceEvents"]
               if e["ph"] == "X" and e["tid"] == 2)
    # the live /metrics smoke answered during at least one child
    assert stats["metrics_smoke"] is True
    assert any(l["metrics_ok"] for l in stats["launches"])
    # and the tail thread saw live per-generation progress
    assert any(l["live_last_step"] >= 0 for l in stats["launches"])

    # --- the device-time attribution plane (ISSUE 15 acceptance) ---
    # the injected stall auto-armed a capture in the gen-2 child and the
    # straggler verdict carries the device block (span attribution above
    # remains the gate; this is the upgrade)
    assert stats["straggler_device_attributed"] is True
    dev_hits = [s for s in stats["stragglers"] if s.get("device")]
    assert dev_hits and dev_hits[0]["device"]["reason"] \
        == "anomaly:loader_stall"
    # ONE federated page, gen/rank-labelled rows for every generation
    # that provably served /metrics while alive
    assert stats["federation_ok"] is True
    page = Path(stats["federation_page_path"]).read_text()
    scraped = {str(l["generation"]) for l in stats["launches"]
               if l.get("metrics_ok")}
    for gen in scraped:
        assert f'dpt_steps_total{{gen="{gen}",rank="0"}}' in page
    assert "dpt_federation_up{" in page and "dpt_build_info{" in page

"""Cross-process fleet orchestrator (ISSUE 12): relaunch ``train.py``
children at whatever world size the fleet actually has.

The in-process elastic path (supervisor.py + elastic.py) resizes over
surviving LOCAL devices — but a real preemptible fleet loses whole
processes/hosts, and the relaunch comes back with a *different process
count*, not a shrunken in-process mesh. This module is the external half:

* **launch** a training child per *generation* (``argv_for`` builds the
  command; the launch generation + rank ride the env —
  ``DPT_FLEET_GENERATION`` / ``DPT_FLEET_RANK`` — and every flight the
  child flushes carries them in its cause, telemetry/flight.py);
* **watch the exit code**: rc=0 with the target step reached is
  completion; rc=0 short of it is a drained preemption (train.py's
  SIGTERM drain checkpoints and exits clean); anything else is a crash.
  Progress is probed
  from the checkpoint directory's integrity MANIFESTS alone
  (:func:`checkpoint_progress`) — the orchestrator is jax/orbax-free by
  design, it must never initialize a backend;
* **relaunch at the capacity the fleet has**: each generation asks the
  capacity feed (scripted in the harness; a cluster API in production)
  and plans the largest feasible world ``<= available`` dividing the
  fixed global batch (:func:`.elastic.plan_elastic_world`) — the child is
  launched with that many devices and ``--mesh data=<world>``, resuming
  over the SHARED checkpoint directory. Cross-world restores ride
  train.py's elastic ``--resume`` (raw restore + reshard;
  ``CheckpointWorldSizeMismatch`` never escapes a relaunch — the
  orchestrator scans child logs and counts any escape as a hard error).

``resilience fleet`` (:func:`fleet_main`) runs the canonical CPU-mesh
scenario end to end: kill at full world → relaunch at half world →
capacity returns → relaunch at full world, then verifies one flight per
abnormal child exit and (``--verify-parity``) that the final segment is
bitwise-equal to an uninterrupted control child continuing from the last
relaunch point.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

from ..telemetry.aggregate import (
    StreamFollower,
    aggregate_segments,
    last_step_of,
    split_streams,
    stitch_perfetto,
)
from ..telemetry.flight import FLEET_GENERATION_ENV, FLEET_RANK_ENV
from ..telemetry.metrics_http import METRICS_PORT_ENV
from ..telemetry.recorder import stream_filename
from .elastic import plan_elastic_world

# FLEET_GENERATION_ENV / FLEET_RANK_ENV are telemetry/flight.py's (one
# definition: the reader of the stamp owns the names) — re-exported here
# because the orchestrator is the writer.
__all__ = ["FLEET_GENERATION_ENV", "FLEET_RANK_ENV", "FleetOrchestrator",
           "FleetLaunch", "FleetReport", "ReplicaProc", "ServingFleet",
           "checkpoint_progress", "check_fleet_flights", "fleet_main"]

# runtime/dist.py's multi-host rendezvous contract (setup_distributed):
# the orchestrator is the WRITER of these stamps, the child's
# jax.distributed.initialize the reader — one generation spanning
# `hosts` processes rendezvouses through them (ISSUE 20).
DIST_COORD_ENV = "DPT_COORDINATOR_ADDRESS"
DIST_NPROC_ENV = "DPT_NUM_PROCESSES"
DIST_PROC_ID_ENV = "DPT_PROCESS_ID"

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _stderr_log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _xla_flags_for(world: int, base: str = "") -> str:
    """``base`` XLA flags with the host-platform device count replaced by
    ``world`` — the CPU-mesh stand-in for launching a child on a fleet of
    ``world`` chips (any inherited count, e.g. the test harness's 8, must
    not leak into a half-world child)."""
    kept = [f for f in (base or "").split()
            if not f.startswith(_DEVICE_COUNT_FLAG)]
    kept.append(f"{_DEVICE_COUNT_FLAG}={world}")
    return " ".join(kept)


def checkpoint_progress(ckpt_dir) -> Tuple[int, Optional[int]]:
    """``(step, world_size)`` of the newest FINALIZED checkpoint, read
    from the integrity manifests alone (``.manifests/<label>.json``,
    training/checkpoint.py) — no jax, no orbax, no backend. A label whose
    ``.pending`` marker survives without a manifest never finalized and
    does not count. ``(-1, None)`` when nothing is finalized."""
    mdir = Path(ckpt_dir) / ".manifests"
    best_label, best = -1, (-1, None)
    if not mdir.is_dir():
        return best
    for p in mdir.glob("*.json"):
        try:
            label = int(p.stem)
            body = json.loads(p.read_text())
            step = int(body.get("step", -1))
        except (ValueError, OSError):
            continue  # torn/foreign manifest: not progress
        if label > best_label:
            best_label = label
            world = body.get("world_size")
            best = (step, int(world) if world is not None else None)
    return best


@dataclasses.dataclass
class FleetLaunch:
    """One child launch: what ran, how it exited, what progress it left."""

    generation: int
    world: int
    available: int
    resume: bool
    argv: List[str] = dataclasses.field(default_factory=list)
    # multi-host generations (ISSUE 20): exit codes of ranks 1..hosts-1
    # (rank 0's rc stays in `rc` — it is the generation's verdict; any
    # non-zero peer marks the generation crashed)
    peer_rcs: List[int] = dataclasses.field(default_factory=list)
    rc: Optional[int] = None
    seconds: float = 0.0
    outcome: str = "launched"   # completed | drained | crashed
    step_after: int = -1
    log_path: str = ""
    # live observability (ISSUE 14): the largest step seen in the child's
    # telemetry stream WHILE it ran (the tail thread's progress probe),
    # and the /metrics smoke verdict when a metrics port was stamped
    # (None = no port / never scrapeable before exit)
    live_last_step: int = -1
    metrics_scrapes: int = 0
    metrics_ok: Optional[bool] = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class FleetReport:
    """The orchestrator's verdict (the ``resilience fleet`` JSON body)."""

    target_step: int = -1
    completed: bool = False
    relaunches: int = 0
    final_step: int = -1
    final_world: Optional[int] = None
    mismatch_escapes: int = 0   # CheckpointWorldSizeMismatch in child logs
    launches: List[dict] = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class FleetOrchestrator:
    """Launch-watch-relaunch over a shared checkpoint directory.

    ``argv_for(world, generation, resume)`` builds one child's command
    line (the CLI builds a train.py invocation; tests use stub scripts).
    ``capacity_for`` is the capacity feed: a callable ``generation ->
    available replicas``, or a sequence whose last value repeats — the
    scripted stand-in for a cluster's capacity API. ``global_batch`` is
    FIXED across generations (the elastic invariant: per-device batch
    changes, the trajectory doesn't). ``target_step`` decides completion:
    a child exiting rc=0 short of it was drained (preempted), not done.
    ``on_child_exit(generation, launch)`` fires after every child exit —
    the CLI snapshots the checkpoint directory there for the parity
    control. ``set_child_devices=True`` pins each child to a CPU mesh of
    exactly ``world`` virtual devices (JAX_PLATFORMS=cpu + XLA_FLAGS);
    pass False when ``argv_for`` manages the child environment itself.

    Live observability (ISSUE 14): ``telemetry_dir`` names the directory
    the children write their telemetry streams into — when set, the
    orchestrator TAILS the per-rank stream while each child runs and
    logs per-generation progress lines (``gen G live — step S``), so a
    fleet run is watchable without attaching to any child.
    ``metrics_port`` stamps ``DPT_METRICS_PORT`` (+rank offset) into the
    child env so every child serves /metrics + /healthz, and the watch
    loop smoke-scrapes it (``launch.metrics_ok``).

    Federation (ISSUE 15): ``federation_port`` additionally runs ONE
    fan-in proxy (telemetry/metrics_http.FederationServer) over the
    children's per-rank ports for the whole fleet run — a single
    Prometheus scrape target whose every series is gen/rank-labelled
    (identities read from each child's own ``dpt_build_info``), with
    exited generations' last pages kept in the merge marked down. The
    final merged page lands in ``self.federation_page`` after
    :meth:`run`.

    Multi-host generations (ISSUE 20): ``hosts > 1`` makes one
    generation span ``hosts`` processes. The orchestrator stamps the
    ``runtime.setup_distributed`` rendezvous contract into every child's
    env — ``DPT_COORDINATOR_ADDRESS`` (``127.0.0.1:coordinator_port +
    generation``, advancing per generation so a relaunch never races the
    previous coordinator's socket), ``DPT_NUM_PROCESSES=hosts`` and a
    per-child ``DPT_PROCESS_ID`` — launches ranks 1..hosts-1 alongside
    rank 0, and gives each child ``world // hosts`` local devices. Rank
    0 stays the watched child whose rc names the outcome; a non-zero
    peer rc marks the generation ``crashed`` (the collective world was
    torn) and a peer outliving rank 0 is killed after a grace window.
    ``argv_for`` is then called with an extra ``rank`` kwarg, and the
    federation proxy fans in over ``hosts`` per-rank metrics ports.
    """

    def __init__(self, argv_for: Callable[..., List[str]], ckpt_dir,
                 *, global_batch: int, target_step: int,
                 capacity_for: Union[Callable[[int], int], Sequence[int]],
                 max_launches: int = 8,
                 env_extra: Optional[Dict[str, str]] = None,
                 set_child_devices: bool = True,
                 on_child_exit: Optional[Callable[..., None]] = None,
                 log_dir=None,
                 telemetry_dir=None,
                 metrics_port: Optional[int] = None,
                 federation_port: Optional[int] = None,
                 hosts: int = 1,
                 coordinator_port: Optional[int] = None,
                 progress_poll_s: float = 0.5,
                 log: Callable[[str], None] = _stderr_log):
        if max_launches < 1:
            raise ValueError(f"max_launches must be >= 1, "
                             f"got {max_launches}")
        if hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {hosts}")
        if hosts > 1 and coordinator_port is None:
            raise ValueError(
                "multi-host generations need a coordinator_port (the "
                "DPT_COORDINATOR_ADDRESS rendezvous every child of a "
                "generation initializes through)")
        self.argv_for = argv_for
        self.ckpt_dir = Path(ckpt_dir)
        self.global_batch = int(global_batch)
        self.target_step = int(target_step)
        self._capacity = (capacity_for if callable(capacity_for)
                          else self._sequence_feed(capacity_for))
        self.max_launches = int(max_launches)
        self.env_extra = dict(env_extra or {})
        self.set_child_devices = set_child_devices
        self.on_child_exit = on_child_exit
        self.log_dir = Path(log_dir) if log_dir is not None \
            else self.ckpt_dir / "fleet_logs"
        self.telemetry_dir = (Path(telemetry_dir)
                              if telemetry_dir is not None else None)
        self.metrics_port = metrics_port
        self.federation_port = federation_port
        self.federation_page: Optional[str] = None
        # multi-host generations (ISSUE 20): one generation = `hosts`
        # children rendezvousing via runtime.setup_distributed's env
        # contract; argv_for is then called with a `rank` kwarg per child
        self.hosts = int(hosts)
        self.coordinator_port = (int(coordinator_port)
                                 if coordinator_port is not None else None)
        self.progress_poll_s = float(progress_poll_s)
        self.log = log

    @staticmethod
    def _sequence_feed(seq: Sequence[int]) -> Callable[[int], int]:
        values = [int(v) for v in seq]
        if not values:
            raise ValueError("capacity sequence is empty")

        def feed(generation: int) -> int:
            return values[min(generation, len(values) - 1)]

        return feed

    def _child_env(self, world: int, generation: int,
                   rank: int = 0) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(self.env_extra)
        env[FLEET_GENERATION_ENV] = str(generation)
        env[FLEET_RANK_ENV] = str(rank)
        if self.metrics_port:
            # stamp the BASE port: the child applies its own rank offset
            # (resolve_metrics_port reads DPT_FLEET_RANK), so stamping
            # base+rank here would offset twice — co-hosted ranks get
            # base+0, base+1, ... from one stamped value
            env[METRICS_PORT_ENV] = str(int(self.metrics_port))
        local_world = world
        if self.hosts > 1:
            # one generation spans `hosts` processes: each child reads
            # this rendezvous contract in runtime.setup_distributed()
            # (jax.distributed.initialize) and owns world/hosts local
            # devices. The coordinator port advances per generation —
            # a relaunch must not race the previous coordinator's socket
            # in TIME_WAIT.
            env[DIST_COORD_ENV] = (
                f"127.0.0.1:{self.coordinator_port + generation}")
            env[DIST_NPROC_ENV] = str(self.hosts)
            env[DIST_PROC_ID_ENV] = str(rank)
            local_world = max(1, world // self.hosts)
        if self.set_child_devices:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = _xla_flags_for(local_world,
                                              env.get("XLA_FLAGS", ""))
        return env

    def _outcome(self, rc: int, step_after: int) -> str:
        if rc == 0:
            return ("completed" if step_after >= self.target_step
                    else "drained")
        return "crashed"

    def _scrape_metrics(self, port: int) -> Optional[str]:
        """One best-effort /metrics scrape of a running child — the
        shared telemetry helper (a child mid-compile simply has no
        listener yet and that is not an error)."""
        from ..telemetry.metrics_http import scrape_metrics

        return scrape_metrics(port)

    def _watch_child(self, proc: "subprocess.Popen", launch: FleetLaunch,
                     generation: int) -> None:
        """Block until the child exits, tailing its telemetry stream for
        live per-generation progress lines and smoke-scraping /metrics
        when a port was stamped. A child with no stream (stub tests,
        --no-telemetry) just waits — the poll loop costs nothing."""
        follower = None
        if self.telemetry_dir is not None:
            # start at the file's CURRENT end: earlier generations
            # appended to the same stream, and their steps are not this
            # child's progress (events are also gen-filtered below — the
            # seek just avoids re-parsing the whole backlog per child)
            follower = StreamFollower(self.telemetry_dir
                                      / stream_filename(0),
                                      start_at_end=True)
        # the child listens on base + its rank (resolve_metrics_port);
        # today's children are single-process rank 0
        port = (int(self.metrics_port) if self.metrics_port else 0)
        last_logged = -1
        while True:
            try:
                proc.wait(timeout=self.progress_poll_s)
                break
            except subprocess.TimeoutExpired:
                pass
            if follower is not None:
                launch.live_last_step = last_step_of(
                    follower.poll(), launch.live_last_step,
                    gen=generation)
                if launch.live_last_step > last_logged:
                    last_logged = launch.live_last_step
                    self.log(f"fleet: generation {generation} live — "
                             f"step {last_logged + 1}/"
                             f"{self.target_step} (world {launch.world})")
            if port:
                body = self._scrape_metrics(port)
                if body is not None:
                    launch.metrics_scrapes += 1
                    ok = "dpt_steps_total" in body
                    # the smoke holds once ANY successful scrape carried
                    # the step counter — later scrapes can only confirm
                    launch.metrics_ok = bool(launch.metrics_ok) or ok
        # drain whatever the stream gained between the last poll and exit
        if follower is not None:
            launch.live_last_step = last_step_of(
                follower.poll(), launch.live_last_step, gen=generation)

    def _rank_argv(self, world: int, generation: int, resume: bool,
                   rank: int) -> List[str]:
        """One child's command line. Single-host keeps the historical
        ``argv_for(world, generation, resume)`` contract untouched;
        multi-host generations pass the child's rank so the builder can
        address per-rank artifacts (stub tests, per-rank output dirs) —
        topology itself rides the env, not the argv."""
        if self.hosts == 1:
            return list(self.argv_for(world=world, generation=generation,
                                      resume=resume))
        return list(self.argv_for(world=world, generation=generation,
                                  resume=resume, rank=rank))

    def _launch_peers(self, world: int, generation: int,
                      resume: bool) -> List["subprocess.Popen"]:
        peers: List["subprocess.Popen"] = []
        try:
            for rank in range(1, self.hosts):
                p_log = self.log_dir / f"gen{generation}_rank{rank}.log"
                lf = open(p_log, "wb")
                try:
                    peers.append(subprocess.Popen(
                        self._rank_argv(world, generation, resume, rank),
                        env=self._child_env(world, generation, rank=rank),
                        stdout=lf, stderr=subprocess.STDOUT))
                finally:
                    lf.close()  # the child holds its own dup of the fd
        except BaseException:
            for p in peers:
                p.kill()
            for p in peers:
                p.wait()
            raise
        return peers

    def _wait_peers(self, peers: List["subprocess.Popen"],
                    launch: FleetLaunch, report: FleetReport,
                    generation: int, grace_s: float = 60.0) -> None:
        """Collect ranks 1..hosts-1 after rank 0 exited. A peer outliving
        rank 0 by the grace window is wedged (a torn rendezvous blocks in
        a collective forever) — killed and recorded, never waited on
        unboundedly."""
        for rank, p in enumerate(peers, start=1):
            try:
                launch.peer_rcs.append(int(p.wait(timeout=grace_s)))
            except subprocess.TimeoutExpired:
                p.kill()
                launch.peer_rcs.append(int(p.wait()))
                report.errors.append(
                    f"generation {generation}: rank {rank} outlived "
                    f"rank 0 by {grace_s:.0f}s and was killed")

    def run(self) -> FleetReport:
        report = FleetReport(target_step=self.target_step)
        self.log_dir.mkdir(parents=True, exist_ok=True)
        if self.set_child_devices:
            self.log("fleet: CPU harness — every child is pinned to a "
                     "virtual CPU mesh (JAX_PLATFORMS=cpu); no child "
                     "touches an accelerator")
        federation = None
        if self.federation_port and self.metrics_port:
            from ..telemetry.metrics_http import FederationServer

            # background refresh faster than the child watch poll: a
            # short-lived generation must still land in the cache before
            # it exits (the final merged page carries every generation)
            # one target per co-hosted rank: every child of a multi-host
            # generation listens on base + its fleet rank, and the fan-in
            # merges them all into one gen/rank-labelled page
            federation = FederationServer(
                int(self.federation_port),
                targets=[int(self.metrics_port) + r
                         for r in range(self.hosts)],
                refresh_s=min(0.3, self.progress_poll_s))
            try:
                port = federation.start()
                self.log(f"fleet: federated /metrics on :{port} "
                         f"(fan-in over child port {self.metrics_port})")
            except OSError as e:
                self.log(f"fleet: federation port "
                         f"{self.federation_port} could not bind ({e}) — "
                         "continuing without the fan-in")
                federation = None
        try:
            return self._run_generations(report)
        finally:
            if federation is not None:
                # one last fan-out so a child that exited between polls
                # is still merged, then keep the final page for the CLI
                federation.refresh()
                self.federation_page = federation.render()
                federation.stop()

    def _run_generations(self, report: FleetReport) -> FleetReport:
        for generation in range(self.max_launches):
            available = int(self._capacity(generation))
            world = plan_elastic_world(available, self.global_batch)
            step_before, _ = checkpoint_progress(self.ckpt_dir)
            resume = step_before >= 0
            argv = self._rank_argv(world, generation, resume, rank=0)
            launch = FleetLaunch(generation=generation, world=world,
                                 available=available, resume=resume,
                                 argv=list(argv))
            log_path = self.log_dir / f"gen{generation}.log"
            launch.log_path = str(log_path)
            self.log(f"fleet: generation {generation} — launching world "
                     f"{world} ({available} available"
                     + (f", {self.hosts} host(s)" if self.hosts > 1
                        else "")
                     + (", --resume" if resume else ", fresh") + ")")
            t0 = time.perf_counter()
            peers: List["subprocess.Popen"] = []
            with open(log_path, "wb") as lf:
                proc = subprocess.Popen(
                    argv, env=self._child_env(world, generation),
                    stdout=lf, stderr=subprocess.STDOUT)
                try:
                    # peers 1..hosts-1 of a multi-host generation launch
                    # NOW: the whole generation rendezvouses through the
                    # stamped coordinator before any child trains
                    peers = self._launch_peers(world, generation, resume)
                    self._watch_child(proc, launch, generation)
                    self._wait_peers(peers, launch, report, generation)
                except BaseException:
                    # subprocess.run's contract, kept: Ctrl-C (or a
                    # raising watch callback) must not orphan a running
                    # training child — it would keep writing the shared
                    # checkpoint dir and holding the metrics port
                    for p in [proc] + peers:
                        p.kill()
                    for p in [proc] + peers:
                        p.wait()
                    raise
            launch.rc = proc.returncode
            launch.seconds = round(time.perf_counter() - t0, 3)
            step_after, world_after = checkpoint_progress(self.ckpt_dir)
            launch.step_after = step_after
            launch.outcome = self._outcome(launch.rc, step_after)
            if launch.outcome in ("completed", "drained") \
                    and any(rc != 0 for rc in launch.peer_rcs):
                # rank 0 exiting clean does not absolve a dead peer: the
                # generation's collective world was torn
                launch.outcome = "crashed"
            try:
                text = log_path.read_text(errors="replace")
            except OSError:
                text = ""
            if "CheckpointWorldSizeMismatch" in text:
                # the acceptance gate: every cross-world restore must ride
                # the elastic resume path — a named mismatch reaching a
                # child's output means a relaunch DIED on (or even just
                # warned about) the exact failure this orchestrator exists
                # to absorb
                report.mismatch_escapes += 1
                report.errors.append(
                    f"generation {generation}: CheckpointWorldSizeMismatch"
                    " escaped into the child log")
            self.log(f"fleet: generation {generation} exited rc="
                     f"{launch.rc} after {launch.seconds:.1f}s — "
                     f"{launch.outcome} (checkpoint step {step_after}/"
                     f"{self.target_step})")
            report.launches.append(launch.as_dict())
            report.final_step = step_after
            report.final_world = world_after
            if self.on_child_exit is not None:
                self.on_child_exit(generation, launch)
            if launch.outcome == "completed":
                report.completed = True
                break
        report.relaunches = max(0, len(report.launches) - 1)
        if not report.completed:
            report.errors.append(
                f"fleet did not reach step {self.target_step} within "
                f"{self.max_launches} launch(es)")
        return report


@dataclasses.dataclass
class ReplicaProc:
    """One serving replica child under `ServingFleet`: the live process,
    plus the death/relaunch history the report commits."""

    rank: int
    proc: Optional["subprocess.Popen"] = None
    relaunches: int = 0
    rc_history: List[int] = dataclasses.field(default_factory=list)
    log_paths: List[str] = dataclasses.field(default_factory=list)

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class ServingFleet:
    """N LONG-LIVED serving replicas under one supervisor — the serving
    sibling of `FleetOrchestrator` (which runs training children one
    generation at a time; a serving fleet runs its replicas
    CONCURRENTLY, forever).

    * ``argv_for(rank, generation)`` builds each replica's command (the
      CLI passes ``serving serve --port base+rank --metrics-port ...``;
      tests pass stubs — the supervisor is jax-free by the same design
      rule as the training orchestrator and never inspects the argv);
    * a replica that EXITS is relaunched (generation + 1, same rank)
      until its ``max_relaunches`` budget is spent — a router in front
      sees the gap as a failed /healthz and resubmits in the meantime;
    * ``drain()`` is the SIGTERM contract fleet-wide: forward the signal
      to every live child (each drains its own queue), wait, collect rcs;
    * ``federation_port`` serves ONE merged /metrics page over the
      replicas' ports (telemetry FederationServer) — the per-replica
      ``serving_queue_depth`` / slot-occupancy gauges land on a single
      dashboard, each row stamped with its replica's identity.

    Generation + rank ride the child env exactly as training launches do
    (``DPT_FLEET_GENERATION`` / ``DPT_FLEET_RANK``), so a dying replica's
    flight is attributable to its slot in the fleet.
    """

    def __init__(self, argv_for: Callable[..., Sequence[str]],
                 replicas: int,
                 metrics_ports: Optional[Sequence[int]] = None,
                 federation_port: Optional[int] = None,
                 log_dir=None, env_extra: Optional[Dict[str, str]] = None,
                 set_child_devices: bool = True, world: int = 8,
                 max_relaunches: int = 2, poll_s: float = 0.2,
                 log: Callable[[str], None] = _stderr_log):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if metrics_ports is not None and len(metrics_ports) != replicas:
            raise ValueError(
                f"metrics_ports must name one port per replica, got "
                f"{len(metrics_ports)} for {replicas}")
        self.argv_for = argv_for
        self.n_replicas = int(replicas)
        self.metrics_ports = (list(int(p) for p in metrics_ports)
                              if metrics_ports else None)
        self.federation_port = federation_port
        self.log_dir = Path(log_dir) if log_dir is not None \
            else Path(tempfile.mkdtemp(prefix="serving_fleet_"))
        self.env_extra = dict(env_extra or {})
        self.set_child_devices = set_child_devices
        self.world = int(world)
        self.max_relaunches = int(max_relaunches)
        self.poll_s = float(poll_s)
        self.log = log
        self.replicas: List[ReplicaProc] = [
            ReplicaProc(rank=r) for r in range(self.n_replicas)]
        self.federation_page: Optional[str] = None
        self._federation = None

    def _child_env(self, rank: int, generation: int) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(self.env_extra)
        env[FLEET_GENERATION_ENV] = str(generation)
        env[FLEET_RANK_ENV] = str(rank)
        if self.set_child_devices:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = _xla_flags_for(self.world,
                                              env.get("XLA_FLAGS", ""))
        return env

    def _spawn(self, rep: ReplicaProc) -> None:
        generation = rep.relaunches
        argv = list(self.argv_for(rank=rep.rank, generation=generation))
        log_path = self.log_dir / f"replica{rep.rank}_gen{generation}.log"
        rep.log_paths.append(str(log_path))
        lf = open(log_path, "wb")
        try:
            rep.proc = subprocess.Popen(
                argv, env=self._child_env(rep.rank, generation),
                stdout=lf, stderr=subprocess.STDOUT)
        finally:
            # the child holds its own dup of the fd; Popen failure must
            # not leak ours either
            lf.close()
        self.log(f"serving fleet: replica {rep.rank} up "
                 f"(generation {generation}, pid {rep.proc.pid})")

    def start(self) -> None:
        self.log_dir.mkdir(parents=True, exist_ok=True)
        if self.set_child_devices:
            self.log("serving fleet: CPU harness — every replica is pinned "
                     "to a virtual CPU mesh (JAX_PLATFORMS=cpu); one chip "
                     "per replica is not built")
        if self.federation_port and self.metrics_ports:
            from ..telemetry.metrics_http import FederationServer

            self._federation = FederationServer(
                int(self.federation_port),
                targets=self.metrics_ports, refresh_s=self.poll_s)
            try:
                port = self._federation.start()
                self.log(f"serving fleet: federated /metrics on :{port} "
                         f"(fan-in over {self.metrics_ports})")
            except OSError as e:
                self.log(f"serving fleet: federation port "
                         f"{self.federation_port} could not bind ({e}) — "
                         "continuing without the fan-in")
                self._federation = None
        for rep in self.replicas:
            self._spawn(rep)

    def poll(self) -> int:
        """One supervision pass: collect exits, relaunch within budget.
        Returns how many replicas are currently alive."""
        alive = 0
        for rep in self.replicas:
            if rep.alive:
                alive += 1
                continue
            if rep.proc is not None and rep.proc.returncode is not None \
                    and (not rep.rc_history
                         or len(rep.rc_history) <= rep.relaunches):
                rc = rep.proc.returncode
                rep.rc_history.append(rc)
                self.log(f"serving fleet: replica {rep.rank} exited "
                         f"rc={rc} (generation {rep.relaunches})")
                if rep.relaunches < self.max_relaunches:
                    rep.relaunches += 1
                    self._spawn(rep)
                    alive += 1
                else:
                    self.log(f"serving fleet: replica {rep.rank} relaunch "
                             f"budget spent ({self.max_relaunches}) — "
                             "leaving it down")
        return alive

    def run(self, stop, duration_s: Optional[float] = None) -> int:
        """Supervise until ``stop`` is set (or ``duration_s`` elapses),
        then drain. Returns the number of replicas still alive at drain
        time."""
        deadline = (time.perf_counter() + duration_s
                    if duration_s is not None else None)
        try:
            while not stop.is_set():
                self.poll()
                if deadline is not None and \
                        time.perf_counter() >= deadline:
                    break
                stop.wait(self.poll_s)
        finally:
            alive = sum(1 for r in self.replicas if r.alive)
            self.drain()
        return alive

    def kill_replica(self, rank: int) -> None:
        """Chaos hook: hard-kill one replica (the injected death the
        acceptance drill routes around)."""
        rep = self.replicas[rank]
        if rep.alive:
            rep.proc.kill()
            rep.proc.wait()

    def drain(self, grace_s: float = 30.0) -> List[Optional[int]]:
        """SIGTERM every live replica (each drains its own queue), wait
        up to ``grace_s`` each, then collect return codes (kill-on-
        timeout — a wedged replica must not hang the supervisor)."""
        for rep in self.replicas:
            if rep.alive:
                rep.proc.terminate()
        rcs: List[Optional[int]] = []
        for rep in self.replicas:
            if rep.proc is None:
                rcs.append(None)
                continue
            try:
                rcs.append(rep.proc.wait(timeout=grace_s))
            except subprocess.TimeoutExpired:
                self.log(f"serving fleet: replica {rep.rank} ignored "
                         f"SIGTERM for {grace_s:.0f}s — killing")
                rep.proc.kill()
                rcs.append(rep.proc.wait())
        if self._federation is not None:
            self._federation.refresh()
            self.federation_page = self._federation.render()
            self._federation.stop()
            self._federation = None
        return rcs

    def report(self) -> dict:
        return {
            "replicas": self.n_replicas,
            "per_replica": [{
                "rank": r.rank,
                "relaunches": r.relaunches,
                "rc_history": list(r.rc_history),
                "alive": r.alive,
            } for r in self.replicas],
            "federation_page": bool(self.federation_page),
        }


# ---------------------------------------------------------------------------
# the `resilience fleet` CLI scenario: train.py children on the CPU mesh
# ---------------------------------------------------------------------------


def _repo_train_py() -> Path:
    path = Path(__file__).resolve().parents[2] / "train.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"train.py not found at {path} — `resilience fleet` drives "
            "the repo checkout's training entry point")
    return path


def _train_argv(args, world: int, resume: bool, chaos: Optional[str],
                ckpt_dir: str, out_dir: str) -> List[str]:
    """One train.py child: the tiny synthetic-CIFAR ResNet workload
    (augmentation off, fp32 — bitwise parity is the acceptance bar),
    sized so per-device batch = global_batch / world at every world."""
    if args.global_batch % world:
        raise ValueError(f"global batch {args.global_batch} does not "
                         f"divide over world {world}")
    argv = [sys.executable, str(_repo_train_py()),
            "--model", "resnet18",
            "--model-overrides", "num_filters=4",
            "--cifar-stem", "--no-augment",
            "--dataset", "cifar10", "--synthetic",
            "--synthetic-size", str(args.synthetic_size),
            "--epochs", str(args.epochs),
            "--batch-size", str(args.global_batch // world),
            "--mesh", f"data={world}",
            "--seed", str(args.seed),
            "--lr", "0.05",
            "--print-freq", "1000",
            "--checkpoint-dir", ckpt_dir,
            "--checkpoint-every", "1",
            "--output-dir", out_dir]
    if args.layout == "zero1":
        argv.append("--zero1")
    elif args.layout == "fsdp":
        argv.append("--fsdp-explicit")
    if args.wire_dtype != "fp32":
        argv += ["--wire-dtype", args.wire_dtype]
    if resume:
        argv.append("--resume")
    if chaos:
        argv += ["--chaos", chaos]
    return argv


def _parse_gen_chaos(spec: Optional[str], spe: int,
                     target_step: int) -> Dict[int, str]:
    """``"0:crash@step=6;1:sigterm@step=10"`` -> {0: ..., 1: ...}.
    Default: the canonical kill -> drain -> stall schedule — generation 0
    crashes mid-epoch-1 (after one epoch checkpoint exists), generation 1
    drains on SIGTERM two steps short of the end (a mid-epoch preemption
    save the full-world relaunch must resume from), and generation 2 (the
    grown full-world finisher) takes a 1.5s ``loader_stall`` the merged
    fleet summary's straggler detector must rank- AND phase-attribute
    (ISSUE 14's acceptance probe — the stall is non-fatal, the child
    still completes)."""
    if spec is None:
        crash_at = spe + max(1, spe // 2)
        drain_at = max(crash_at + 1, target_step - spe + 1)
        return {0: f"crash@step={crash_at}",
                1: f"sigterm@step={drain_at}",
                2: "loader_stall@step=2:1.5s"}
    out: Dict[int, str] = {}
    for item in filter(None, (s.strip() for s in spec.split(";"))):
        gen_s, _, chaos = item.partition(":")
        if not chaos:
            raise ValueError(f"--gen-chaos item {item!r} is not "
                             "GEN:SPEC")
        out[int(gen_s)] = chaos
    return out


def _compare_final_checkpoints(real_dir: str, control_dir: str,
                               log=_stderr_log) -> Optional[bool]:
    """Bitwise comparison of the newest valid checkpoint in two
    directories, RAW (saved shapes; no template, no mesh — works at any
    world) and over the WHOLE saved state: params, optimizer moments,
    batch stats, EF residuals, step counters. Params alone would let a
    reshard bug that corrupts only the moments or residual rows (which
    never reaches a loss before the final save) score as parity. None
    when either side has nothing to compare."""
    import numpy as np

    from ..training.checkpoint import CheckpointManager

    def load(d):
        mgr = CheckpointManager(d)
        try:
            return mgr.restore_latest_raw()
        finally:
            mgr.close()

    real, control = load(real_dir), load(control_dir)
    if real is None or control is None:
        return None
    real_arrays, real_label, real_world, *_ = real
    ctl_arrays, ctl_label, ctl_world, *_ = control
    if real_label != ctl_label or real_world != ctl_world \
            or sorted(real_arrays) != sorted(ctl_arrays):
        log(f"fleet: parity control diverged structurally — real "
            f"label/world {real_label}/{real_world} vs control "
            f"{ctl_label}/{ctl_world}")
        return False
    import jax.tree_util as jtu

    for key in sorted(real_arrays):
        real_leaves = jtu.tree_leaves(real_arrays[key])
        ctl_leaves = jtu.tree_leaves(ctl_arrays[key])
        if len(real_leaves) != len(ctl_leaves) or not all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(real_leaves, ctl_leaves)):
            log(f"fleet: parity mismatch in checkpoint subtree {key!r}")
            return False
    return True


def check_fleet_flights(flight_dir, launches: List[dict],
                        ignore=None) -> dict:
    """One flight per ABNORMAL child exit, attributable by generation:
    a crashed child must leave exactly one flight stamped
    ``[fleet gen=G ...]`` whose cause matches a crash; a drained child
    exactly one whose cause names the preemption. A completed child must
    leave none. ``ignore`` holds flight paths that existed BEFORE this
    fleet ran: a reused ``--ckpt-dir`` must not let a previous run's
    postmortems satisfy — or fail — THIS run's accounting (the same
    guard the chaos harness applies)."""
    flights = []
    for p in sorted(Path(flight_dir).glob("flight_*.json")):
        if ignore and p in ignore:
            continue
        try:
            body = json.loads(p.read_text())
            flights.append({"path": str(p),
                            "cause": body.get("cause", ""),
                            "generation": body.get("fleet_generation")})
        except ValueError:
            flights.append({"path": str(p), "cause": None,
                            "generation": None})
    problems = []
    for launch in launches:
        gen = str(launch["generation"])
        mine = [f for f in flights if f["generation"] == gen]
        outcome = launch["outcome"]
        if outcome in ("crashed", "drained"):
            if len(mine) != 1:
                problems.append(
                    f"generation {gen} ({outcome}) left {len(mine)} "
                    "flight(s), expected exactly 1")
            elif outcome == "drained" \
                    and "preemption" not in (mine[0]["cause"] or ""):
                problems.append(
                    f"generation {gen} drained but its flight cause "
                    f"is {mine[0]['cause']!r}, not a preemption")
        elif outcome == "completed" and mine:
            problems.append(
                f"generation {gen} completed but left "
                f"{len(mine)} flight(s)")
    ok = not problems and all(f["cause"] is not None for f in flights)
    return {"flights": flights, "flight_problems": problems,
            "flights_ok": ok}


def fleet_main(args) -> int:
    """The ``resilience fleet`` scenario. Exit 0 iff the fleet completed,
    every abnormal child exit left exactly one attributable flight, no
    ``CheckpointWorldSizeMismatch`` escaped, and (unless
    ``--no-verify-parity``) the final checkpoint is bitwise-equal to an
    uninterrupted control child continuing from the last relaunch
    point."""
    if getattr(args, "federation_port", None) \
            and not getattr(args, "metrics_port", None):
        raise SystemExit("--federation-port requires --metrics-port (the "
                         "fan-in proxies the children's per-rank ports)")
    base = Path(args.ckpt_dir or tempfile.mkdtemp(prefix="dpt-fleet-"))
    base.mkdir(parents=True, exist_ok=True)
    ckpt_dir = base / "ckpt"
    out_dir = base / "out"       # children's flights + telemetry
    spe, leftover = divmod(args.synthetic_size, args.global_batch)
    if leftover or spe < 2:
        raise SystemExit(
            f"--synthetic-size {args.synthetic_size} must be a multiple "
            f"of --global-batch {args.global_batch} (>= 2 steps/epoch)")
    if args.epochs < 3:
        raise SystemExit("the fleet scenario needs --epochs >= 3 (one "
                         "epoch per phase: full world, shrunken world, "
                         "grown world)")
    target_step = spe * args.epochs
    gen_chaos = _parse_gen_chaos(args.gen_chaos, spe, target_step)
    capacity = [int(x) for x in args.capacity.split(",") if x.strip()]

    snapshots: Dict[int, Path] = {}

    def snapshot(generation: int, _launch) -> None:
        # the checkpoint directory AS THE NEXT GENERATION WILL SEE IT —
        # the parity control relaunches from exactly this state
        dest = base / f"snap_gen{generation}"
        if dest.exists():
            shutil.rmtree(dest)
        if ckpt_dir.exists():
            shutil.copytree(ckpt_dir, dest)
            snapshots[generation] = dest

    orch = FleetOrchestrator(
        lambda world, generation, resume: _train_argv(
            args, world, resume, gen_chaos.get(generation),
            str(ckpt_dir), str(out_dir)),
        ckpt_dir, global_batch=args.global_batch,
        target_step=target_step, capacity_for=capacity,
        max_launches=args.max_launches, on_child_exit=snapshot,
        telemetry_dir=out_dir,
        metrics_port=getattr(args, "metrics_port", None),
        federation_port=getattr(args, "federation_port", None))
    # flights already present belong to a PREVIOUS fleet run over this
    # --ckpt-dir — excluded from this run's per-generation accounting
    pre_existing_flights = set(Path(out_dir).glob("flight_*.json"))
    # ... and so do telemetry streams: children APPEND to the shared
    # per-rank file, so a reused --ckpt-dir would fold the previous
    # run's segments into THIS run's merged summary, trace, and
    # straggler verdict (a stale loader_stall row could satisfy the
    # acceptance probe). Rotate them aside — same guard as the flights,
    # done by rename because exclusion-by-path cannot split an appended
    # file.
    for stale in sorted(Path(out_dir).glob("telemetry_rank*.jsonl")):
        stale.rename(stale.with_name(
            stale.name + f".prev-{int(time.time())}"))
    report = orch.run()

    flight_stats = check_fleet_flights(out_dir, report.launches,
                                       ignore=pre_existing_flights)

    # The merged fleet view (ISSUE 14): ONE fleet summary + ONE stitched
    # Perfetto trace covering every generation and rank — successive
    # children APPENDED to the shared per-rank stream, so the aggregator
    # splits at meta headers and the trace gets one stable pid per
    # (gen, rank). The straggler table inside the summary is the
    # acceptance probe for the injected loader_stall.
    stream_paths = sorted(Path(out_dir).glob("telemetry_rank*.jsonl"))
    fleet_summary = None
    summary_path = trace_path = None
    if stream_paths:
        unreadable: List[str] = []
        segments = split_streams(stream_paths, missing=unreadable)
        fleet_summary = aggregate_segments(segments, missing=unreadable)
        summary_path = base / "fleet_summary.json"
        summary_path.write_text(
            json.dumps(fleet_summary, sort_keys=True))
        trace_path = base / "fleet_trace.json"
        trace_path.write_text(json.dumps(stitch_perfetto(segments)))

    # a scheduled loader_stall must come back ATTRIBUTED: the stalled
    # child's generation, the data_wait phase — "one rank is slow and
    # here is why" is the observability this plane exists to give
    launched_gens = {launch["generation"] for launch in report.launches}
    stall_gens = sorted(g for g, c in gen_chaos.items()
                        if "loader_stall" in c and g in launched_gens)
    straggler_attributed = None
    if stall_gens:
        hits = [s for s in (fleet_summary or {}).get("stragglers", [])
                if s["phase"] == "data_wait" and s["gen"] in stall_gens]
        straggler_attributed = bool(hits)
        if not straggler_attributed:
            report.errors.append(
                f"loader_stall chaos on generation(s) {stall_gens} was "
                "not rank/phase-attributed by the fleet straggler "
                "detector (expected a data_wait straggler row)")

    metrics_smoke = None
    if getattr(args, "metrics_port", None):
        metrics_smoke = any(launch.get("metrics_ok")
                            for launch in report.launches)
        if not metrics_smoke:
            report.errors.append(
                "--metrics-port was set but no child's /metrics endpoint "
                "ever answered a scrape with the step counter")

    # the gen-2 straggler verdict's device upgrade (ISSUE 15): recorded,
    # never gated — span-based attribution is the contractual fallback
    # when no capture overlapped the flagged step
    straggler_device_attributed = None
    if stall_gens:
        straggler_device_attributed = any(
            s.get("device") for s in (fleet_summary or {})
            .get("stragglers", []) if s["gen"] in stall_gens)

    # federation (ISSUE 15): the run must end with ONE merged page whose
    # per-rank series are gen/rank-labelled — every generation that
    # provably served /metrics while alive must appear in it
    federation_ok = None
    federation_page_path = None
    federated_identities: List[List[str]] = []
    if getattr(args, "federation_port", None):
        page = orch.federation_page or ""
        if page:
            federation_page_path = base / "fleet_metrics.prom"
            federation_page_path.write_text(page)
        import re as _re

        federated_identities = sorted(
            {(m.group(1), m.group(2)) for m in _re.finditer(
                r'dpt_steps_total\{gen="([^"]*)",rank="([^"]*)"\}', page)})
        federated_identities = [list(t) for t in federated_identities]
        scraped_gens = {str(launch["generation"])
                        for launch in report.launches
                        if launch.get("metrics_ok")}
        merged_gens = {g for g, _ in
                       (tuple(t) for t in federated_identities)}
        federation_ok = bool(federated_identities) \
            and scraped_gens <= merged_gens
        if not federation_ok:
            report.errors.append(
                "--federation-port was set but the merged /metrics page "
                f"is missing gen/rank-labelled step rows (merged gens "
                f"{sorted(merged_gens)}, scraped gens "
                f"{sorted(scraped_gens)})")

    parity = None
    if (report.completed and not args.no_verify_parity
            and len(report.launches) > 1):
        final = report.launches[-1]
        snap = snapshots.get(final["generation"] - 1)
        if snap is not None:
            control_ckpt = base / "control_ckpt"
            if control_ckpt.exists():
                shutil.rmtree(control_ckpt)
            shutil.copytree(snap, control_ckpt)
            control_out = base / "control_out"
            argv = _train_argv(args, final["world"], resume=True,
                               chaos=None, ckpt_dir=str(control_ckpt),
                               out_dir=str(control_out))
            orch.log(f"fleet: parity control — uninterrupted relaunch at "
                     f"world {final['world']} from the last handoff")
            env = orch._child_env(final["world"], final["generation"])
            env.pop(FLEET_GENERATION_ENV, None)
            env.pop(FLEET_RANK_ENV, None)
            ctl_log = orch.log_dir / "control.log"
            with open(ctl_log, "wb") as lf:
                rc = subprocess.run(argv, env=env, stdout=lf,
                                    stderr=subprocess.STDOUT).returncode
            if rc != 0:
                report.errors.append(f"parity control child exited {rc}")
                parity = False
            else:
                parity = _compare_final_checkpoints(
                    str(ckpt_dir), str(control_ckpt), log=orch.log)

    # "proved nothing" guards (the chaos CLI's discipline): a scheduled
    # chaos scenario whose run never relaunched exercised none of the
    # machinery this command exists to verify, and a relaunching run
    # whose parity control could not be evaluated proved only half
    if gen_chaos and report.relaunches == 0:
        report.errors.append(
            "chaos was scheduled but the fleet never relaunched — the "
            "kill/shrink/grow machinery was not exercised (chaos step "
            "past the run's end, or a reused directory already at the "
            "target)")
    if (not args.no_verify_parity and report.relaunches > 0
            and parity is None):
        report.errors.append(
            "parity control could not be evaluated (missing handoff "
            "snapshot or un-restorable checkpoints)")

    stats = {"metric": "fleet_chaos", "dir": str(base),
             "worlds": [launch["world"] for launch in report.launches],
             "gen_chaos": {str(k): v for k, v in gen_chaos.items()},
             "parity_bitwise": parity,
             "fleet_summary": fleet_summary,
             "fleet_summary_path": (str(summary_path)
                                    if summary_path else None),
             "fleet_trace_path": str(trace_path) if trace_path else None,
             "stragglers": (fleet_summary or {}).get("stragglers", []),
             "straggler_attributed": straggler_attributed,
             "straggler_device_attributed": straggler_device_attributed,
             "metrics_smoke": metrics_smoke,
             "federation_ok": federation_ok,
             "federated_identities": federated_identities,
             "federation_page_path": (str(federation_page_path)
                                      if federation_page_path else None),
             **flight_stats, **report.as_dict()}
    ok = (report.completed and parity is not False
          and flight_stats["flights_ok"]
          and report.mismatch_escapes == 0
          and not (gen_chaos and report.relaunches == 0)
          and straggler_attributed is not False
          and metrics_smoke is not False
          and federation_ok is not False
          and (args.no_verify_parity or report.relaunches == 0
               or parity is True))
    if args.as_json:
        print(json.dumps(stats, sort_keys=True))
    else:
        for launch in report.launches:
            live = (f", live step {launch['live_last_step'] + 1}"
                    if launch.get("live_last_step", -1) >= 0 else "")
            print(f"generation {launch['generation']}: world "
                  f"{launch['world']} rc={launch['rc']} "
                  f"{launch['outcome']} (step {launch['step_after']}/"
                  f"{target_step}, {launch['seconds']:.1f}s{live})")
        print(f"final step: {report.final_step}/{target_step} at world "
              f"{report.final_world}")
        print(f"flights: {len(flight_stats['flights'])} "
              f"(ok={flight_stats['flights_ok']})")
        for problem in flight_stats["flight_problems"]:
            print(f"flight problem: {problem}")
        if fleet_summary is not None:
            print(f"fleet summary: {summary_path} "
                  f"({fleet_summary['n_streams']} stream segment(s)); "
                  f"merged trace: {trace_path}")
            for s in fleet_summary["stragglers"]:
                print(f"straggler: gen={s['gen']} rank={s['rank']} "
                      f"step={s['step']} {s['phase']} {s['dur_s']:.3f}s "
                      f"({s['factor']}x {s['basis']})")
        if metrics_smoke is not None:
            print(f"metrics_smoke: {metrics_smoke}")
        if federation_ok is not None:
            print(f"federation: ok={federation_ok} identities="
                  f"{federated_identities} page={federation_page_path}")
        if straggler_device_attributed is not None:
            print(f"straggler_device_attributed: "
                  f"{straggler_device_attributed}")
        print(f"parity_bitwise: {parity}")
        for err in report.errors:
            print(f"error: {err}", file=sys.stderr)
        print("fleet: RECOVERED" if ok else "fleet: FAILED")
    return 0 if ok else 1

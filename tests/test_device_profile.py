"""Device-time attribution plane (ISSUE 15): the re-armable StepProfiler
(on-demand windows, busy refusal, session guard), the trace ->
``device_profile`` ingestion (telemetry/device.py over
trace_analysis.device_time_split), the ``POST /profile`` endpoint, the
anomaly-triggered capture path through the REAL instrumented train loop on
the CPU mesh, the straggler detector's device attribution, and the
federated /metrics fan-in.
"""

import gzip
import json
import urllib.error
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_training_tpu import telemetry
from distributed_pytorch_training_tpu.telemetry import device as tele_device
from distributed_pytorch_training_tpu.utils.profiling import (
    StepProfiler, session_owner, trace_session,
)


@pytest.fixture(autouse=True)
def _clean_global_recorder():
    telemetry.reset()
    yield
    telemetry.reset()
    # a leaked jax profiler session would poison every later test
    assert session_owner() is None


@pytest.fixture
def counted_profiler(monkeypatch):
    """jax.profiler start/stop replaced by counters (the
    test_training.py lifecycle-suite convention): session bookkeeping is
    the subject, and an imbalance must fail the test, not poison the
    process's real profiler."""
    calls = {"start": 0, "stop": 0, "dirs": []}

    def _start(log_dir, **kw):
        calls["start"] += 1
        calls["dirs"].append(str(log_dir))

    monkeypatch.setattr(jax.profiler, "start_trace", _start)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop",
                                                  calls["stop"] + 1))
    return calls


def _scrape(port, path="/metrics"):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=2) as resp:
        return resp.status, resp.read().decode("utf-8")


def _post(port, path):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 method="POST")
    with urllib.request.urlopen(req, timeout=2) as resp:
        return resp.status, resp.read().decode("utf-8")


# ---------------------------------------------------------------------------
# device_time_split on hand-built traces
# ---------------------------------------------------------------------------


def _write_trace(tmp_path, events, pid_names=None, tid_names=None):
    """A synthetic *.trace.json.gz in the layout jax.profiler writes."""
    trace = []
    for pid, name in (pid_names or {}).items():
        trace.append({"ph": "M", "pid": pid, "name": "process_name",
                      "args": {"name": name}})
    for (pid, tid), name in (tid_names or {}).items():
        trace.append({"ph": "M", "pid": pid, "tid": tid,
                      "name": "thread_name", "args": {"name": name}})
    for name, pid, tid, ts, dur in events:
        trace.append({"ph": "X", "pid": pid, "tid": tid, "name": name,
                      "ts": ts, "dur": dur})
    d = tmp_path / "plugins" / "profile" / "2026_08_04"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": trace}, f)
    return str(tmp_path)


class TestDeviceTimeSplit:
    def test_four_way_split_sums_to_window(self, tmp_path):
        """compute + hidden + exposed + gap == window, with a collective
        half-hidden under compute and a host gap between ops."""
        from distributed_pytorch_training_tpu.telemetry.trace_analysis \
            import device_time_split

        log = _write_trace(
            tmp_path,
            # compute [0, 100), all-reduce [50, 150) -> 50 hidden /
            # 50 exposed; compute [250, 300) after a 100us host gap
            [("fusion.1", 7, 1, 0.0, 100.0),
             ("all-reduce.2", 7, 1, 50.0, 100.0),
             ("fusion.3", 7, 1, 250.0, 50.0)],
            pid_names={7: "/device:TPU:0 (abc)"},
            tid_names={(7, 1): "XLA Ops"})
        s = device_time_split(log)
        assert s["window_us"] == 300.0
        assert s["comm_hidden_us"] == 50.0
        assert s["comm_exposed_us"] == 50.0
        assert s["compute_us"] == 100.0     # 150 busy-union minus comm
        assert s["host_gap_us"] == 100.0
        assert (s["compute_us"] + s["comm_hidden_us"]
                + s["comm_exposed_us"] + s["host_gap_us"]) \
            == s["window_us"]
        assert s["by_op"] == {"all-reduce": 100.0}
        assert s["exposed_frac_pct"] == 50.0

    def test_cpu_thunk_lanes_and_wrapped_names(self, tmp_path):
        """The CPU test backend's shape: no device pids, wrapped_ thunk
        names, runtime bookkeeping excluded."""
        from distributed_pytorch_training_tpu.telemetry.trace_analysis \
            import device_time_split

        log = _write_trace(
            tmp_path,
            [("wrapped_dot.1", 1, 1, 0.0, 80.0),
             ("wrapped_all-gather.2", 1, 2, 80.0, 20.0),
             ("ThunkExecutor bookkeeping", 1, 3, 0.0, 500.0)])
        s = device_time_split(log)
        assert s["window_us"] == 100.0
        assert s["compute_us"] == 80.0
        assert s["comm_exposed_us"] == 20.0
        assert s["comm_hidden_us"] == 0.0
        assert s["host_gap_us"] == 0.0
        assert s["by_op"] == {"all-gather": 20.0}


# ---------------------------------------------------------------------------
# the re-armable StepProfiler
# ---------------------------------------------------------------------------


class TestStepProfilerRearm:
    def test_armed_window_opens_closes_and_ingests(self, tmp_path,
                                                   counted_profiler):
        captures = []
        prof = StepProfiler(str(tmp_path),
                            on_capture=lambda d, info: captures.append(
                                (d, info)))
        assert prof.request_capture(2, reason="http") is True
        prof(0)   # opens at the next step
        assert counted_profiler["start"] == 1
        prof(1)
        prof(2)   # closes: 2 steps elapsed
        assert counted_profiler == {
            "start": 1, "stop": 1,
            "dirs": counted_profiler["dirs"]}
        assert len(captures) == 1
        d, info = captures[0]
        assert d == counted_profiler["dirs"][0]
        assert info["start_step"] == 0 and info["stop_step"] == 2
        assert info["reason"] == "http"
        # re-armable: a SECOND window in the same run
        assert prof.request_capture(1, reason="again") is True
        prof(3)
        prof(4)
        assert counted_profiler["start"] == 2
        assert counted_profiler["stop"] == 2
        assert len(captures) == 2
        # distinct capture directories — sessions never mix
        assert counted_profiler["dirs"][0] != counted_profiler["dirs"][1]

    def test_busy_refusal_counts_not_clobbers(self, tmp_path,
                                              counted_profiler):
        rec = telemetry.configure(str(tmp_path / "t.jsonl"))
        prof = StepProfiler(str(tmp_path))
        assert prof.request_capture(4) is True
        assert prof.request_capture(2) is False   # already armed
        prof(0)                                   # window opens
        assert prof.request_capture(2) is False   # in flight
        assert prof.busy_refused == 2
        busy = [e for e in rec.tail(50)
                if e["kind"] == "counter" and e["name"] == "profiler_busy"]
        assert len(busy) == 2
        prof.close()
        assert counted_profiler["start"] == counted_profiler["stop"] == 1

    def test_busy_while_static_window_open(self, tmp_path,
                                           counted_profiler):
        prof = StepProfiler(str(tmp_path), 0, 5)
        prof(0)   # static window opens
        assert prof._active
        assert prof.request_capture(2) is False
        prof.close()

    def test_capture_context_and_nested_refusal(self, tmp_path,
                                                counted_profiler):
        captures = []
        prof = StepProfiler(str(tmp_path),
                            on_capture=lambda d, i: captures.append(i))
        with prof.capture(reason="bench") as d:
            assert d is not None
            with prof.capture() as d2:   # nested: refused, still runs
                assert d2 is None
        assert counted_profiler["start"] == counted_profiler["stop"] == 1
        assert [c["reason"] for c in captures] == ["bench"]
        assert session_owner() is None

    def test_trace_session_guard_refuses_second(self, tmp_path,
                                                counted_profiler):
        with trace_session(str(tmp_path / "a")) as started:
            assert started is True
            with trace_session(str(tmp_path / "b")) as second:
                assert second is False
        assert counted_profiler["start"] == counted_profiler["stop"] == 1
        assert session_owner() is None

    def test_close_mid_armed_window_fires_once(self, tmp_path,
                                               counted_profiler):
        captures = []
        prof = StepProfiler(str(tmp_path),
                            on_capture=lambda d, i: captures.append(i))
        prof.request_capture(10)
        prof(0)
        prof.close()
        prof.close()   # idempotent
        assert counted_profiler["start"] == counted_profiler["stop"] == 1
        assert len(captures) == 1
        # honest truncation: the window spanned ONE hook call, not the
        # requested 10 — steps/stop_step report what actually happened
        # (a fabricated K would overstate measured MFU by K/elapsed)
        assert captures[0]["steps"] == 1
        assert captures[0]["stop_step"] == captures[0]["start_step"] + 1

    def test_window_step_labels_survive_label_resets(self, tmp_path,
                                                     counted_profiler):
        """Armed windows stamp start_step from the label passed in and
        derive stop_step from ELAPSED hook calls — a mid-window label
        reset (the epoch boundary: step_hook labels restart) cannot
        produce stop < start or a negative step count."""
        captures = []
        prof = StepProfiler(str(tmp_path),
                            on_capture=lambda d, i: captures.append(i))
        prof.request_capture(2)
        prof(18)    # window opens at global step 18 (end of an epoch)
        prof(19)
        prof(0)     # next epoch: labels reset; window closes here
        assert captures and captures[0]["start_step"] == 18
        assert captures[0]["stop_step"] == 20
        assert captures[0]["steps"] == 2

    def test_nonzero_process_refuses_arming(self, tmp_path, monkeypatch,
                                            counted_profiler):
        """Only process 0 opens windows (__call__ returns early
        elsewhere) — accepting an arm on another rank would wedge its
        profiler on a pending that can never fire (every later POST
        would 409 forever)."""
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        prof = StepProfiler(str(tmp_path))
        assert prof.request_capture(2) is False
        prof(0)
        assert prof._pending is None and prof._window is None
        assert counted_profiler["start"] == 0

    def test_capture_budget_bounds_disk(self, tmp_path, counted_profiler):
        prof = StepProfiler(str(tmp_path), max_captures=1)
        assert prof.request_capture(1) is True
        prof(0)
        prof(1)
        assert prof.request_capture(1) is False   # budget spent
        assert counted_profiler["start"] == counted_profiler["stop"] == 1

    def test_refused_capture_does_not_burn_budget(self, tmp_path,
                                                  counted_profiler):
        """A capture refused because another component holds the jax
        session must not consume a budget slot — N refusals would
        otherwise exhaust max_captures with zero traces written."""
        prof = StepProfiler(str(tmp_path), max_captures=2)
        with trace_session(str(tmp_path / "other")) as started:
            assert started
            for _ in range(5):
                with prof.capture() as d:
                    assert d is None   # refused: session held elsewhere
        with prof.capture() as d:      # budget intact
            assert d is not None
        assert prof._n_captures == 1

    def test_broken_ingestor_never_raises(self, tmp_path,
                                          counted_profiler):
        def boom(d, info):
            raise RuntimeError("ingestor broke")

        prof = StepProfiler(str(tmp_path), on_capture=boom)
        prof.request_capture(1)
        prof(0)
        prof(1)   # on_capture fires here — contained
        assert counted_profiler["stop"] == 1


# ---------------------------------------------------------------------------
# POST /profile + identity + device series on the metrics endpoint
# ---------------------------------------------------------------------------


class TestProfileEndpoint:
    def test_post_profile_arms_busy_and_missing(self, tmp_path):
        rec = telemetry.configure(str(tmp_path / "t.jsonl"), gen=2, rank=1)
        server = telemetry.MetricsServer(0, recorder=rec, backend="cpu")
        port = server.start()
        try:
            # no profiler wired yet -> 404
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(port, "/profile?steps=2")
            assert err.value.code == 404
            got = []
            server.profile_handler = lambda steps: (got.append(steps)
                                                    or True)
            status, body = _post(port, "/profile?steps=3")
            assert status == 202 and json.loads(body)["armed"] is True
            assert got == [3]
            server.profile_handler = lambda steps: False   # busy
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(port, "/profile?steps=2")
            assert err.value.code == 409
            for bad in ("steps=0", "steps=nope"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    _post(port, f"/profile?{bad}")
                assert err.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(port, "/elsewhere")
            assert err.value.code == 404
        finally:
            server.stop()

    def test_build_info_and_healthz_identity(self, tmp_path):
        rec = telemetry.configure(str(tmp_path / "t.jsonl"), gen=4, rank=2)
        server = telemetry.MetricsServer(0, recorder=rec, backend="tpu")
        port = server.start()
        try:
            _, body = _scrape(port)
            assert ('dpt_build_info{gen="4",rank="2",schema_version="2",'
                    'backend="tpu"} 1') in body
            rec.span_event("step_dispatch", 0.004, step=0)
            status, hz = _scrape(port, "/healthz")
            detail = json.loads(hz)
            assert (detail["gen"], detail["rank"]) == (4, 2)
            assert detail["schema_version"] == telemetry.SCHEMA_VERSION
            assert detail["backend"] == "tpu"
        finally:
            server.stop()

    def test_device_profile_events_become_series(self, tmp_path):
        rec = telemetry.configure(str(tmp_path / "t.jsonl"))
        server = telemetry.MetricsServer(0, recorder=rec)
        port = server.start()
        try:
            rec.emit("device_profile", "device_profile",
                     compute_ms=900.0, comm_hidden_ms=50.0,
                     comm_exposed_ms=40.0, host_gap_ms=10.0,
                     window_ms=1000.0, exposed_comm_ratio=0.444)
            _, body = _scrape(port)
            assert "dpt_device_profiles_total 1" in body
            assert 'dpt_device_seconds{phase="compute"} 0.900000' in body
            assert ('dpt_device_seconds{phase="comm_exposed"} 0.040000'
                    in body)
            assert "dpt_exposed_comm_ratio 0.444" in body
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# anomaly-triggered capture: the watchdog's hook
# ---------------------------------------------------------------------------


class TestWatchdogCaptureHook:
    def _watchdog(self, hook, **kw):
        return telemetry.AnomalyWatchdog(
            min_samples=2, stall_factor=3.0, stall_min_s=0.4,
            spike_factor=3.0, capture_hook=hook, **kw)

    def test_stall_and_spike_arm_a_capture(self):
        armed = []
        wd = self._watchdog(lambda name, step: armed.append((name, step)))
        for i in range(4):
            wd.observe_step(i, 0.01, data_wait_s=0.001)
        wd.observe_step(4, 1.0, data_wait_s=0.9)     # loader stall
        for i in range(5, 10):
            wd.observe_step(i, 0.01, data_wait_s=0.001)
        wd.observe_step(10, 0.5, data_wait_s=0.001)  # busy-time spike
        assert armed == [("loader_stall", 4), ("step_time_spike", 10)]

    def test_non_finite_loss_does_not_arm(self):
        armed = []
        wd = self._watchdog(lambda name, step: armed.append(name))
        wd.observe_loss(3, float("nan"))
        assert wd.anomalies and not armed

    def test_hook_fires_before_abort_and_is_contained(self):
        armed = []

        def hook(name, step):
            armed.append(name)
            raise RuntimeError("broken hook")

        wd = self._watchdog(hook, abort=True)
        for i in range(3):
            wd.observe_step(i, 0.01, data_wait_s=0.001)
        with pytest.raises(telemetry.AnomalyAbort):
            wd.observe_step(3, 1.0, data_wait_s=0.9)
        assert armed == ["loader_stall"]   # armed despite abort + raise

    def test_absolute_stall_bound_fires_without_warmup(self):
        """The first post-resume step's stall (the fleet's gen-2 shape):
        the rolling median has nothing to compare against, and only the
        absolute bound can name it. Off by default — PR 8 semantics
        unchanged without the knob."""
        armed = []
        wd = telemetry.AnomalyWatchdog(
            stall_abs_s=1.0,
            capture_hook=lambda name, step: armed.append((name, step)))
        wd.observe_step(0, 1.6, data_wait_s=1.5)   # step 0: zero samples
        assert [a[0] for a in wd.anomalies] == ["loader_stall"]
        assert wd.anomalies[0][1]["absolute_bound_s"] == 1.0
        assert armed == [("loader_stall", 0)]
        # default watchdog: the same first-step stall stays invisible
        # (warm-up), exactly as before
        wd2 = telemetry.AnomalyWatchdog()
        wd2.observe_step(0, 1.6, data_wait_s=1.5)
        assert wd2.anomalies == []

    def test_kwargs_from_env(self, monkeypatch):
        from distributed_pytorch_training_tpu.telemetry.watchdog import (
            kwargs_from_env,
        )

        monkeypatch.setenv("DPT_WATCHDOG_MIN_SAMPLES", "3")
        monkeypatch.setenv("DPT_WATCHDOG_STALL_MIN_S", "0.25")
        monkeypatch.setenv("DPT_WATCHDOG_STALL_ABS_S", "1.5")
        monkeypatch.setenv("DPT_WATCHDOG_SPIKE_FACTOR", "junk")
        kw = kwargs_from_env()
        assert kw == {"min_samples": 3, "stall_min_s": 0.25,
                      "stall_abs_s": 1.5}
        assert telemetry.AnomalyWatchdog(**kw).min_samples == 3


# ---------------------------------------------------------------------------
# the CPU-mesh capture path end to end (ISSUE 15 acceptance)
# ---------------------------------------------------------------------------


class TestAnomalyCaptureEndToEnd:
    def test_stall_triggers_capture_and_device_attribution(self, tmp_path,
                                                           mesh8, capsys):
        """Through the REAL instrumented train loop: an injected
        loader_stall trips the watchdog, the watchdog arms a 2-step
        capture, a real jax.profiler trace is taken WHILE the run
        continues, and ingestion leaves a ``device_profile`` event whose
        split is self-consistent; ``telemetry summary`` renders the
        device block, and the fleet aggregator device-attributes the
        straggler it already names (span fallback intact for the clean
        peer)."""
        from distributed_pytorch_training_tpu.data.loader import (
            ShardedLoader,
        )
        from distributed_pytorch_training_tpu.resilience.__main__ import (
            _build_rig,
        )
        from distributed_pytorch_training_tpu.resilience.faults import (
            FaultInjector, FaultPlan,
        )
        from distributed_pytorch_training_tpu.telemetry.__main__ import (
            main as telemetry_main,
        )
        from distributed_pytorch_training_tpu.telemetry.aggregate import (
            aggregate_streams,
        )

        x = jnp.ones((64, 64), jnp.float32)
        mm = jax.jit(lambda a: (a @ a).sum())
        mm(x).block_until_ready()   # compile OUTSIDE any capture window

        def fake_step(state, batch, key):
            return state, {"loss_sum": mm(x),
                           "correct": jnp.float32(1.0),
                           "weight": jnp.float32(16.0)}

        def run_child(gen, stream_path, fault_hook=None, arm=False):
            trainer, _, loader = _build_rig(
                mesh8, seed=0, dataset_size=320, per_device_batch=2)
            trainer._train_step = fake_step
            if fault_hook is not None:
                loader = ShardedLoader(loader.dataset, trainer.mesh, 2,
                                       shuffle=True, seed=0,
                                       fault_hook=fault_hook)
            telemetry.configure(str(stream_path), gen=gen, rank=0)
            profiler = None
            if arm:
                profiler = StepProfiler(
                    str(tmp_path / f"prof{gen}"),
                    on_capture=tele_device.make_ingestor())
                # spike_factor high: CPU scheduling noise must not arm a
                # second (legitimate) spike capture under test
                trainer.watchdog = telemetry.AnomalyWatchdog(
                    min_samples=2, stall_factor=3.0, stall_min_s=0.4,
                    spike_factor=200.0,
                    capture_hook=lambda name, step:
                        profiler.request_capture(
                            2, reason=f"anomaly:{name}",
                            trigger_step=step))
            spe = len(loader)
            with profiler if profiler is not None else \
                    __import__("contextlib").nullcontext():
                trainer.train_epoch(None, loader.epoch(0), 0, spe,
                                    samples_per_step=[16] * spe,
                                    step_hook=profiler)
            telemetry.reset()

        p0 = tmp_path / "clean.jsonl"
        p1 = tmp_path / "stalled.jsonl"
        run_child(0, p0)
        injector = FaultInjector(
            FaultPlan.parse("loader_stall@step=8:0.6s"))
        run_child(1, p1, fault_hook=injector.on_loader_batch, arm=True)
        assert injector.fired == ["loader_stall@step=8:0.6s"]

        events = [json.loads(line) for line in
                  p1.read_text().splitlines()]
        anomalies = [e for e in events if e["kind"] == "anomaly"]
        assert any(a["name"] == "loader_stall" and a["step"] == 8
                   for a in anomalies)
        profiles = [e for e in events if e["kind"] == "device_profile"]
        stall_profiles = [e for e in profiles
                          if e["reason"] == "anomaly:loader_stall"]
        assert len(stall_profiles) == 1, profiles
        dp = stall_profiles[0]
        assert dp["trigger_step"] == 8
        assert dp["start_step"] == 9 and dp["stop_step"] == 11
        assert (dp["gen"], dp["rank"]) == (1, 0)   # stamped like every event
        # the acceptance self-consistency: the four phases sum to the
        # captured device window
        total = (dp["compute_ms"] + dp["comm_hidden_ms"]
                 + dp["comm_exposed_ms"] + dp["host_gap_ms"])
        assert dp["window_ms"] > 0
        assert total == pytest.approx(dp["window_ms"], rel=1e-3)
        assert tele_device.covers_step(dp, 8)      # trigger association
        assert tele_device.covers_step(dp, 9)      # window containment
        assert not tele_device.covers_step(dp, 20)

        # `telemetry summary` renders the device split beside the wall
        # split — text and --json both
        assert telemetry_main(["summary", str(p1)]) == 0
        out = capsys.readouterr().out
        assert "device-time split" in out and "profiled window(s)" in out
        assert "exposed-comm ratio" in out
        assert telemetry_main(["summary", str(p1), "--json"]) == 0
        s = json.loads(capsys.readouterr().out)
        assert s["device"]["profiles"] == len(profiles)
        assert set(s["device"]["split_ms"]) == set(
            tele_device.DEVICE_PHASES)
        assert any(w.get("trigger_step") == 8
                   for w in s["device"]["windows"])

        # the aggregator's straggler row gains the device block
        agg = aggregate_streams([p0, p1])
        hits = [s for s in agg["stragglers"]
                if s["phase"] == "data_wait" and s["gen"] == 1
                and s["step"] == 8]
        assert hits, agg["stragglers"]
        assert "device" in hits[0]
        assert hits[0]["device"]["reason"] == "anomaly:loader_stall"
        assert hits[0]["device"]["trigger_step"] == 8
        # per-stream device split rides the fleet summary too
        stalled_stream = [st for st in agg["streams"] if st["gen"] == 1][0]
        assert stalled_stream["device"]["profiles"] == len(profiles)
        assert [st for st in agg["streams"]
                if st["gen"] == 0][0]["device"] is None

        # ... and the stitched trace draws the captured window on tid 2
        from distributed_pytorch_training_tpu.telemetry.aggregate import (
            split_streams, stitch_perfetto,
        )
        trace = stitch_perfetto(split_streams([p0, p1]))
        dev = [e for e in trace["traceEvents"]
               if e.get("name") == "device_profile" and e["ph"] == "X"]
        assert len(dev) == len(profiles)
        assert all(e["tid"] == 2 for e in dev)
        assert any(e["dur"] == pytest.approx(dp["window_ms"] * 1e3)
                   for e in dev)


class TestGlobalStepLabels:
    def test_step_hook_receives_global_labels_on_resume(self, mesh8):
        """The loop hands step_hook the SAME global label the spans and
        the watchdog use (start_step + i) — on a mid-epoch resume an
        armed window's step range must line up against the straggler
        table's flagged steps, not restart at 0."""
        from distributed_pytorch_training_tpu.resilience.__main__ import (
            _build_rig,
        )

        trainer, _, loader = _build_rig(mesh8, seed=0, dataset_size=160,
                                        per_device_batch=2)
        metrics = {"loss_sum": jnp.float32(1.0),
                   "correct": jnp.float32(1.0),
                   "weight": jnp.float32(16.0)}
        trainer._train_step = lambda s, b, k: (s, metrics)
        seen = []
        spe = len(loader)
        trainer.train_epoch(None, loader.epoch(0, start_step=4), 0, spe,
                            start_step=4, step_hook=seen.append)
        assert seen == list(range(4, spe))


# ---------------------------------------------------------------------------
# straggler device attribution on synthetic streams (fleet-median factor)
# ---------------------------------------------------------------------------


def _write_synthetic_stream(path, gen, *, stall_at=None, profile=None):
    """Minimal two-phase stream; ``profile`` injects a device_profile."""
    with open(path, "w", encoding="utf-8") as f:
        def emit(kind, name, **fields):
            f.write(json.dumps({"v": 2, "ts": 1000.0, "kind": kind,
                                "name": name, "gen": gen, "rank": 0,
                                **fields}) + "\n")

        emit("meta", "stream", schema=2, run_id=f"g{gen}", pid=100 + gen)
        for step in range(10):
            wait = 1.5 if step == stall_at else 0.004
            emit("span", "data_wait", dur_ms=wait * 1e3, step=step)
            emit("span", "step_dispatch", dur_ms=4.0, step=step)
        if profile is not None:
            emit("device_profile", "device_profile", **profile)
        emit("counter", "epoch_time_s", value=2.0, epoch=0)
    return path


class TestStragglerDeviceAttribution:
    def test_overlapping_profile_attributes_with_fleet_factor(self,
                                                              tmp_path):
        from distributed_pytorch_training_tpu.telemetry.aggregate import (
            aggregate_streams,
        )

        slow = {"start_step": 4, "stop_step": 6, "steps": 2,
                "reason": "anomaly:loader_stall", "trigger_step": 5,
                "window_ms": 100.0, "compute_ms": 20.0,
                "comm_hidden_ms": 5.0, "comm_exposed_ms": 41.0,
                "host_gap_ms": 34.0, "exposed_comm_ratio": 0.89,
                "by_op_ms": {"all-reduce": 46.0}}
        clean = {"start_step": 4, "stop_step": 6, "steps": 2,
                 "reason": "http", "trigger_step": None,
                 "window_ms": 100.0, "compute_ms": 85.0,
                 "comm_hidden_ms": 5.0, "comm_exposed_ms": 10.0,
                 "host_gap_ms": 0.0, "exposed_comm_ratio": 0.66,
                 "by_op_ms": {"all-reduce": 15.0}}
        p0 = _write_synthetic_stream(tmp_path / "r0.jsonl", 0,
                                     profile=clean)
        p1 = _write_synthetic_stream(tmp_path / "r1.jsonl", 1,
                                     stall_at=5, profile=slow)
        agg = aggregate_streams([p0, p1])
        hit = [s for s in agg["stragglers"] if s["gen"] == 1][0]
        d = hit["device"]
        assert d["dominant_op"] == "all-reduce"
        assert d["split_ms"]["comm_exposed"] == 41.0
        # 41 / clean's 10 exposed ms — the "4.1x fleet median" headline
        assert d["exposed_vs_fleet_median"] == 4.1

    def test_no_overlap_keeps_span_fallback(self, tmp_path):
        from distributed_pytorch_training_tpu.telemetry.aggregate import (
            aggregate_streams,
        )

        far = {"start_step": 0, "stop_step": 2, "steps": 2,
               "reason": "http", "trigger_step": None,
               "window_ms": 10.0, "compute_ms": 10.0,
               "comm_hidden_ms": 0.0, "comm_exposed_ms": 0.0,
               "host_gap_ms": 0.0}
        p0 = _write_synthetic_stream(tmp_path / "r0.jsonl", 0)
        p1 = _write_synthetic_stream(tmp_path / "r1.jsonl", 1,
                                     stall_at=5, profile=far)
        agg = aggregate_streams([p0, p1])
        hit = [s for s in agg["stragglers"] if s["gen"] == 1][0]
        assert "device" not in hit   # span-based attribution stands


# ---------------------------------------------------------------------------
# federation: ONE /metrics page over the per-rank ports
# ---------------------------------------------------------------------------


class TestFederation:
    def test_merged_page_is_gen_rank_labelled(self, tmp_path):
        rec_a = telemetry.Recorder(str(tmp_path / "a.jsonl"), gen=0,
                                   rank=0)
        rec_b = telemetry.Recorder(str(tmp_path / "b.jsonl"), gen=1,
                                   rank=0)
        a = telemetry.MetricsServer(0, recorder=rec_a, backend="cpu")
        b = telemetry.MetricsServer(0, recorder=rec_b, backend="cpu")
        pa, pb = a.start(), b.start()
        fed = telemetry.FederationServer(0, targets=[pa, pb])
        fport = fed.start()
        try:
            rec_a.span_event("step_dispatch", 0.004, step=3)
            rec_b.span_event("step_dispatch", 0.004, step=7)
            rec_b.gauge("world_size", 4)
            _, body = _scrape(fport)
            assert "dpt_federation_targets 2" in body
            assert 'dpt_federation_up{gen="0",rank="0"} 1' in body
            assert 'dpt_federation_up{gen="1",rank="0"} 1' in body
            assert 'dpt_steps_total{gen="0",rank="0"} 1' in body
            assert 'dpt_steps_total{gen="1",rank="0"} 1' in body
            assert 'dpt_last_step{gen="1",rank="0"} 7' in body
            assert ('dpt_gauge{gen="1",rank="0",name="world_size"} 4'
                    in body)
            # labelled lines (build_info) pass through un-doubled
            assert body.count('dpt_build_info{gen="0"') == 1
            # one TYPE line per metric family, not per target
            assert body.count("# TYPE dpt_steps_total counter") == 1
            # /healthz names every target
            status, hz = _scrape(fport, "/healthz")
            detail = json.loads(hz)
            assert detail["healthy"] is True
            assert set(detail["targets"]) == {"gen0/rank0", "gen1/rank0"}
        finally:
            fed.stop()
            a.stop()
            b.stop()

    def test_exited_target_stays_cached_marked_down(self, tmp_path):
        rec = telemetry.Recorder(str(tmp_path / "a.jsonl"), gen=2, rank=0)
        server = telemetry.MetricsServer(0, recorder=rec)
        port = server.start()
        fed = telemetry.FederationServer(0, targets=[port])
        fport = fed.start()
        try:
            rec.span_event("step_dispatch", 0.004, step=5)
            _, body = _scrape(fport)
            assert 'dpt_federation_up{gen="2",rank="0"} 1' in body
            server.stop()   # the child "exited"
            _, body = _scrape(fport)
            # last page kept in the merge, marked down — the fleet's
            # final federated page carries every generation
            assert 'dpt_federation_up{gen="2",rank="0"} 0' in body
            assert 'dpt_steps_total{gen="2",rank="0"} 1' in body
            with pytest.raises(urllib.error.HTTPError) as err:
                _scrape(fport, "/healthz")
            assert err.value.code == 503
        finally:
            fed.stop()
            server.stop()

    def test_no_targets_page_is_empty_but_serves(self):
        fed = telemetry.FederationServer(0, targets=[])
        fport = fed.start()
        try:
            _, body = _scrape(fport)
            assert "dpt_federation_targets 0" in body
        finally:
            fed.stop()

"""Test harness: an 8-device virtual CPU mesh.

This is the TPU-world "fake backend" the reference lacks (SURVEY.md §4): real
collectives on 8 XLA CPU devices, no cluster needed. Must run before jax
brings a backend up, hence the env mutation at module import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests are CPU runs, asked for by name

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)


# ---------------------------------------------------------------------------
# Per-file wall budget for the resilience/elastic/fleet chaos suites
# (ISSUE 12 satellite). These files host subprocess + multi-restart
# harnesses whose cost grows a leg at a time; without a stated budget a
# new chaos leg can silently push the fast suite into the 870 s tier-1
# timeout and the failure shows up as a global timeout, not a named
# culprit. Budgets bind only on FAST runs (`-m 'not slow'`, the tier-1
# invocation) and hold ~3x headroom over measured cost; the slow chaos
# legs are budgeted by the marker instead. DPT_TEST_FILE_BUDGET_OFF=1
# disables enforcement (the report still prints).
# ---------------------------------------------------------------------------

_FILE_BUDGETS_S = {
    "test_resilience.py": 300.0,   # measured ~95 s fast
    "test_elastic.py": 240.0,      # measured ~75 s fast
    "test_fleet.py": 60.0,         # stub children: measured ~1 s fast
    # The 2-D TP x FSDP parity suite (ISSUE 13): every leg compiles a
    # fresh shard_map step over the 4-device 2-D mesh — per-leg compile
    # cost is the budget driver, and a new parity leg silently pushing
    # the fast suite into the 870 s tier-1 timeout must name itself here.
    "test_tp.py": 300.0,           # measured ~100 s fast
    # The fleet observability suite (ISSUE 14): synthetic streams + one
    # real mock-step loop leg + HTTP scrapes with sub-second sleeps —
    # cheap today, but endpoint tests accrete timeouts easily.
    "test_telemetry_fleet.py": 90.0,   # measured ~3 s fast
    # The device-time attribution suite (ISSUE 15): real jax.profiler
    # captures through the instrumented loop + HTTP endpoints — trace
    # capture/parse cost accretes per leg, so new windows name
    # themselves here.
    "test_device_profile.py": 120.0,   # measured ~7 s fast
    # The two-tier hier wire suite (ISSUE 16): every parity leg compiles
    # a fresh shard_map step over the (slice=2, data=4) mesh, plus one
    # contract evaluation — per-leg compile cost is the budget driver.
    "test_hier.py": 150.0,             # measured ~39 s fast
    # The continuous-batching suite (ISSUE 17): four SlotEngine warmups
    # (fp32 + int8 on the 8-way mesh, two fleet replicas on 4-device
    # slices), one contract evaluation, and one jitted fixed-pad
    # reference forward for the bitwise pins — compile count is the
    # budget driver, so a new engine config or bucket rung must name
    # itself here. PR 40: a fifth warmup, the SpeculativeEngine whose
    # iteration has to tile like the plain one's.
    "test_continuous.py": 150.0,       # measured ~72 s fast
    # The concurrency-discipline suite (ISSUE 18): AST lint over tmp
    # sources + tiny stub engines + deterministic gated interleavings
    # with sub-second waits — the budget driver is the sum of the small
    # join timeouts, which accrete per interleaving test.
    "test_analysis_concurrency.py": 60.0,   # measured ~7 s fast
    # The speculative-decoding suite (ISSUE 19): one SpeculativeEngine
    # warmup (draft prefill + propose + verify per bucket) plus a plain
    # SlotEngine warmup for the bitwise cross-pins, an oracle-draft
    # engine, and one contract evaluation — warmup compile count is the
    # budget driver, so a new engine or bucket rung names itself here.
    "test_speculative.py": 180.0,      # measured ~48 s fast
    # The control-plane suite (ISSUE 20): the autopilot chaos leg runs a
    # full supervised train with an injected persistent straggler, one
    # boundary shrink, one capacity-return grow, and the bitwise parity
    # continuation — three elastic recompiles plus ~0.9 s x 3 of
    # injected stall dominate; the policy/probe/gate unit legs are
    # milliseconds.
    "test_control.py": 240.0,          # measured ~49 s fast
}
_file_seconds: dict = {}


def pytest_runtest_logreport(report):
    fname = report.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
    if fname in _FILE_BUDGETS_S:
        _file_seconds[fname] = (_file_seconds.get(fname, 0.0)
                                + report.duration)


def _budget_enforced(config) -> bool:
    if os.environ.get("DPT_TEST_FILE_BUDGET_OFF"):
        return False
    return "not slow" in (config.getoption("-m") or "")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _file_seconds:
        return
    terminalreporter.write_sep("-", "chaos-suite wall budget")
    enforced = _budget_enforced(config)
    for fname, secs in sorted(_file_seconds.items()):
        budget = _FILE_BUDGETS_S[fname]
        if enforced:
            verdict = "OVER BUDGET" if secs > budget else "ok"
            terminalreporter.write_line(
                f"{fname}: {secs:.1f}s / {budget:.0f}s budget ({verdict})")
        else:  # slow legs run here — the fast budget does not apply
            terminalreporter.write_line(
                f"{fname}: {secs:.1f}s (fast-suite budget {budget:.0f}s "
                "not enforced on this run)")


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session, exitstatus):
    global _final_exitstatus
    if _budget_enforced(session.config):
        over = {f: s for f, s in _file_seconds.items()
                if s > _FILE_BUDGETS_S[f]}
        if over and session.exitstatus == 0:
            for fname, secs in over.items():
                print(f"BUDGET: {fname} took {secs:.1f}s, over its "
                      f"{_FILE_BUDGETS_S[fname]:.0f}s fast-suite budget "
                      "— a chaos leg grew past the tier-1 allowance; "
                      "mark it slow or shrink it", flush=True)
            session.exitstatus = 1
    _final_exitstatus = int(session.exitstatus)


_final_exitstatus = None


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    """Skip interpreter teardown once the run is reported.

    A full fast-suite run leaves hundreds of compiled XLA executables
    and device buffers behind; their destructors cost ~8-10 s of wall
    AFTER the final summary prints — time that counts against the 870 s
    tier-1 timeout and buys zero coverage. The summary and the final
    exit status are settled by this point (terminal reporting is a
    sessionfinish hookwrapper, unconfigure runs after it), so leave via
    os._exit. DPT_NO_FAST_EXIT=1 restores the normal shutdown (atexit
    consumers, debugging); coverage runs keep it automatically."""
    import sys
    if _final_exitstatus is None or os.environ.get("DPT_NO_FAST_EXIT"):
        return
    if config.pluginmanager.hasplugin("_cov"):
        return
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_final_exitstatus)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=8), devices=devices)

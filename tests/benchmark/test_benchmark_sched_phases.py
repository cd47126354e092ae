"""The readers of the scheduler's phase spans (`benchmark/layer_metrics/
_sched.py` and the sixteen metrics over it): the idle split on a synthetic
trace and synthetic spans (a gap inside one phase, a gap that straddles two,
a gap under none; the six shares sum to the device's idle share; a fence that
seems to end before the program it waited for voids the six), the host-side
medians and the fence's inter-token gap on synthetic spans, every reader on a
run that has nothing for it (the parent's program), and the traced rehearsals
of the two GPT-2 serve cells.

`BENCHMARK.json` does not list the sixteen yet: the benchmark's own tests pin
the LAST thirteen `per_layer` entries to PR 37's, and an entry put before
them reads to the driver as a change to what was there. `ENTRIES` below is
what the `benchmark` issue that loosens the pin puts in; until then the
tests here (and the chip probes, PERF.md section 6) run the harness with
`bench_with_entries` laid over `load_cell`."""

import importlib
import json
import types
from pathlib import Path

import pytest

from benchmark import run as harness
from benchmark import trace_reduce
from benchmark.layer_metrics import _sched

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BATCH, STEADY = "serve_gpt2_124m_batch", "serve_gpt2_124m_chat_steady"
IDLE = {f"batch_idle_in_{p}_pct" for p in
        ("pull", "admit", "dispatch", "fence", "complete")} | {
            "batch_idle_unattributed_pct"}
BATCH_HOST = {"batch_sched_iteration_ms", "batch_admit_page_alloc_ms",
              "batch_admit_table_put_ms", "batch_admit_prefill_dispatch_ms",
              "batch_complete_slot_fetch_ms", "batch_complete_table_put_ms",
              "batch_itl_p99_ms", "batch_kv_live_page_share_pct"}
STEADY_HOST = {"steady_sched_iteration_ms", "steady_itl_p99_ms"}
NEW = IDLE | BATCH_HOST | STEADY_HOST


def entry(name):
    steady = name in STEADY_HOST
    gauge = "kv_live" in name
    return {"name": name,
            "unit": "%" if name.endswith("_pct") else "ms",
            "better": "higher" if gauge else "lower",
            "source": ("device_trace" if name in IDLE else
                       "program_counter" if gauge else "program_span"),
            "layer": "serving host",
            "moves": "tpot_p95_ms" if steady else "serve_out_tokens_per_s",
            "workloads": [STEADY if steady else BATCH]}


ENTRIES = [entry(n) for n in (
    "batch_sched_iteration_ms", "batch_idle_in_pull_pct",
    "batch_idle_in_admit_pct", "batch_idle_in_dispatch_pct",
    "batch_idle_in_fence_pct", "batch_idle_in_complete_pct",
    "batch_idle_unattributed_pct", "batch_admit_page_alloc_ms",
    "batch_admit_table_put_ms", "batch_admit_prefill_dispatch_ms",
    "batch_complete_slot_fetch_ms", "batch_complete_table_put_ms",
    "batch_itl_p99_ms", "batch_kv_live_page_share_pct",
    "steady_sched_iteration_ms", "steady_itl_p99_ms")]
PINNED_TAIL = 13     # test_benchmark_deepseek_v2.py holds these to PR 37's


def bench_with_entries(bench):
    """``bench`` with ENTRIES standing before its last thirteen."""
    at = len(bench["per_layer"]) - PINNED_TAIL
    return dict(bench, per_layer=bench["per_layer"][:at] + ENTRIES
                + bench["per_layer"][at:])

WALL0 = 1_790_000_000.0      # the traced window's start on time.time()
MS = 1e-3
# a span's start is a double near 1.8e9 s: 0.24 us between neighbours, which
# is 0.0024% of the 10 ms window these tests trace
PCT_EPS = 0.01


def read(name, run):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(run)


def span(name, start_ms, end_ms, **attrs):
    return dict(kind="span", name=name, t0=WALL0 + start_ms * MS,
                dur_ms=end_ms - start_ms, **attrs)


def device(busy_ms):
    """One program an interval, one operation long."""
    return trace_reduce.DeviceLanes(
        ops=[trace_reduce.Event(f"%op.{i} = f32[8]{{0}} add(...)", a * 1e6,
                                (b - a) * 1e6)
             for i, (a, b) in enumerate(busy_ms)],
        modules=[trace_reduce.Event(f"jit_step({i})", a * 1e6, (b - a) * 1e6)
                 for i, (a, b) in enumerate(busy_ms)])


def synthetic_run(events, busy_ms=None, trace_start_ns=0.0):
    """A run as the readers see it: a 10 ms traced window that opens at
    WALL0, one chip busy over ``busy_ms`` and a second one that never
    idles (the readers take the idlest)."""
    notes = []
    trace = None
    if busy_ms is not None:
        shift = trace_start_ns / 1e6
        trace = trace_reduce.Trace(
            devices={"/device:TPU:0": device(
                [(a + shift, b + shift) for a, b in busy_ms]),
                     "/device:TPU:1": device([(shift, shift + 10.0)])},
            host_marks=[trace_reduce.Event(harness.WINDOW_MARK,
                                           trace_start_ns, 10e6)])
    return types.SimpleNamespace(
        events=events, window=(WALL0 - 1.0, WALL0 + 1.0), facts={},
        trace_data=trace, notes=notes,
        trace_wall_offset_s=None if trace is None
        else WALL0 - trace_start_ns / 1e9,
        note=lambda **fields: notes.append(fields))


# one chip: operations over 0-1, 2-3, 4-4.5, 5-6 and 9-10 ms of the window,
# so it idles over 1-2, 3-4, 4.5-5 and 6-9: 5.5 of 10 ms
BUSY = [(0, 1), (2, 3), (4, 4.5), (5, 6), (9, 10)]


def two_iterations(fence_end_ms=6.05):
    return [
        span("sched_pull", 0.5, 1.2, iter=7, took=1),
        span("page_alloc", 1.25, 1.3, iter=7, slot=3, request=41, ok=True),
        span("page_table_put", 1.3, 1.7, iter=7, slot=3, request=41,
             at="admit"),
        span("prefill", 1.7, 2.4, iter=7, slot=3, request=41, bucket=128),
        span("sched_admit", 1.2, 2.5, iter=7, admitted=1, pending=0),
        span("sched_dispatch", 2.5, 3.2, iter=7, steps=2, live=64),
        span("sched_fence", 3.2, fence_end_ms, iter=7, steps=2, live=64,
             tokens=129),
        span("slot_fetch", 6.1, 6.6, iter=7, slot=5, request=40),
        span("page_table_put", 6.6, 6.9, iter=7, slot=5, request=40,
             at="complete"),
        span("sched_complete", fence_end_ms, 7.0, iter=7, completed=1),
        # 7.0-7.5: between iterations, under no phase
        span("sched_pull", 7.5, 7.6, iter=8, took=0),
        span("page_alloc", 7.65, 7.75, iter=8, slot=5, request=42,
             ok=False),
        span("sched_admit", 7.6, 9.5, iter=8, admitted=0, pending=1),
        span("sched_dispatch", 9.5, 9.6, iter=8, steps=1, live=63),
        span("sched_fence", 9.6, 10.4, iter=8, steps=1, live=63, tokens=63),
        span("sched_complete", 10.4, 10.5, iter=8, completed=0),
    ]


# -- the idle split -----------------------------------------------------------

@pytest.mark.parametrize("trace_start_ns", [0.0, 7.25e12],
                         ids=["trace_zero", "trace_clock_far_from_zero"])
def test_idle_split_lays_each_gap_under_its_phases(trace_start_ns):
    run = synthetic_run(two_iterations(), BUSY, trace_start_ns)
    want = {
        # 1-2 straddles pull (to 1.2) and admit; 6-9 ends under admit
        "batch_idle_in_pull_pct": 0.2 + 0.1,
        "batch_idle_in_admit_pct": 0.8 + 1.4,
        # 3-4 straddles dispatch (to 3.2) and fence
        "batch_idle_in_dispatch_pct": 0.2,
        # 4.5-5 lies inside the fence; the fence outlasts the device by 50 us
        "batch_idle_in_fence_pct": 0.8 + 0.5 + 0.05,
        "batch_idle_in_complete_pct": 0.95,
        # 7.0-7.5: no phase
        "batch_idle_unattributed_pct": 0.5,
    }
    got = {name: read(name, run) for name in want}
    assert got == pytest.approx({n: 10.0 * ms for n, ms in want.items()},
                                abs=PCT_EPS)
    assert sum(got.values()) == pytest.approx(55.0, abs=PCT_EPS)
    # one line says what was checked, printed once however many ask
    assert len(run.notes) == 1
    note = run.notes[0]
    assert note["device_idle_pct"] == pytest.approx(55.0)
    assert note["residue_pct"] == pytest.approx(0.0, abs=PCT_EPS)
    assert note["holds"] is True
    assert note["fences"] == 1      # the second fence ends after the window
    assert note["fence_lag_p50_us"] == pytest.approx(50.0, abs=0.5)
    assert note["fence_lag_p01_us"] == pytest.approx(50.0, abs=0.5)
    assert note["idle_pct_by_phase"]["unattributed"] == pytest.approx(
        5.0, abs=PCT_EPS)


def test_a_fence_that_ends_before_its_program_voids_the_six():
    """The fence of iteration 7 seems to end at 5.8 ms, 200 us inside the
    program that runs until 6.0: the two clocks disagree by at least that,
    and no idle gap can be laid under a phase."""
    run = synthetic_run(two_iterations(fence_end_ms=5.8), BUSY)
    assert {read(name, run) for name in IDLE} == {None}
    assert len(run.notes) == 1
    note = run.notes[0]
    assert note["holds"] is False and note["fences"] == 1
    assert note["fence_lag_p01_us"] == pytest.approx(-200.0, abs=0.5)
    # what the split would have been is on the line all the same
    assert sum(note["idle_pct_by_phase"].values()) == pytest.approx(
        55.0, abs=PCT_EPS)
    # a fence 40 us inside its program is within what the clocks can say
    near = synthetic_run(two_iterations(fence_end_ms=5.96), BUSY)
    assert read("batch_idle_in_fence_pct", near) is not None
    # the host-side readers do not hang on the join
    assert read("batch_sched_iteration_ms", run) is not None


def test_no_fence_in_the_traced_window_voids_the_six():
    events = [e for e in two_iterations() if e["iter"] == 8]
    run = synthetic_run(events, BUSY)
    assert {read(name, run) for name in IDLE} == {None}
    assert run.notes[0]["fences"] == 0 and run.notes[0]["holds"] is False


def test_phases_that_overlap_show_as_a_residue_and_void_the_six():
    """Two schedulers in one process: the second one's admit lies over the
    first one's fence, the gap under both is counted twice, and the six no
    longer sum to the device's idle share."""
    events = two_iterations() + [
        span("sched_admit", 3.2, 5.0, iter=1, admitted=1, pending=0)]
    run = synthetic_run(events, BUSY)
    assert {read(name, run) for name in IDLE} == {None}
    assert run.notes[0]["residue_pct"] == pytest.approx(-13.0, abs=PCT_EPS)


def test_fence_lags_by_hand():
    programs = [(0.0, 1.0), (2.0, 3.0)]
    # after a program: since it ended
    assert _sched.fence_lags([1.25, 3.5], programs) == [0.25, 0.5]
    # inside a program: short of its end; before the first: nothing to say
    assert _sched.fence_lags([2.75, -0.5], programs) == [-0.25]


# -- the host-side readers ----------------------------------------------------

def ms(value):
    """Milliseconds between two spans' edges, each a double near 1.8e9 s."""
    return pytest.approx(value, abs=1e-3)


def test_host_side_medians_read_the_spans_they_name():
    run = synthetic_run(two_iterations())
    # iteration 7: 0.5 .. 7.0; iteration 8: 7.5 .. 10.5
    assert read("batch_sched_iteration_ms", run) == ms((6.5 + 3.0) / 2)
    assert read("steady_sched_iteration_ms", run) == ms(4.75)
    # the alloc that gave no lease is no admission
    assert read("batch_admit_page_alloc_ms", run) == ms(0.05)
    assert read("batch_admit_table_put_ms", run) == ms(0.4)
    assert read("batch_admit_prefill_dispatch_ms", run) == ms(0.7)
    assert read("batch_complete_slot_fetch_ms", run) == ms(0.5)
    assert read("batch_complete_table_put_ms", run) == ms(0.3)
    # one pair of fences that follow each other: 6.05 -> 10.4 over one step
    assert read("batch_itl_p99_ms", run) == ms(4.35)
    assert read("steady_itl_p99_ms", run) == ms(4.35)


def test_the_fence_gap_is_weighted_by_tokens_and_skips_idle_stretches():
    def fence(it, end_ms, steps, live):
        return span("sched_fence", end_ms - 0.1, end_ms, iter=it,
                    steps=steps, live=live, tokens=steps * live)

    events = [fence(1, 10.0, 1, 2),
              fence(2, 12.0, 1, 2),      # 2.0 ms a token, 2 tokens
              fence(3, 20.0, 4, 50),     # 8.0 / 4 = 2.0 ms, 200 tokens
              fence(4, 29.0, 1, 1),      # 9.0 ms, 1 token: under 1%
              fence(9, 500.0, 1, 1)]     # after an idle stretch: no gap
    run = synthetic_run(events)
    assert read("batch_itl_p99_ms", run) == ms(2.0)
    events.append(fence(10, 505.0, 1, 3))   # 5.0 ms, 3 tokens: over 1%
    assert read("batch_itl_p99_ms",
                synthetic_run(events)) == ms(5.0)
    assert _sched.weighted_percentile([], 99.0) is None


def test_spans_outside_the_window_are_not_read():
    run = synthetic_run(two_iterations())
    run.window = (WALL0 + 7.2 * MS, WALL0 + 1.0)     # iteration 8 alone
    assert read("batch_sched_iteration_ms", run) == ms(3.0)
    assert read("batch_admit_prefill_dispatch_ms", run) is None
    assert read("batch_itl_p99_ms", run) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_returns_none_where_there_is_nothing_to_read(name):
    """The parent's program emits none of the spans; an untraced run has no
    trace. A reader says None and the line leaves its metric out."""
    bare = synthetic_run([], BUSY)
    assert read(name, bare) is None
    others = [dict(kind="span", name="queue_wait", t0=WALL0, dur_ms=1.0,
                   request=1),
              dict(kind="counter", name="serving_decode_steps", ts=WALL0,
                   value=4)]
    assert read(name, synthetic_run(others, BUSY)) is None
    if name in IDLE:     # spans but no trace, a trace but no join
        assert read(name, synthetic_run(two_iterations())) is None
        unjoined = synthetic_run(two_iterations(), BUSY)
        unjoined.trace_wall_offset_s = None
        assert read(name, unjoined) is None


# -- BENCHMARK.json and the cells ---------------------------------------------

def test_the_entries_stand_where_the_benchmarks_pins_allow():
    """Put before PR 37's thirteen, the sixteen change no entry that is
    there, keep the pinned tail, and reach their own cell and no other."""
    assert {m["name"] for m in ENTRIES} == NEW and len(ENTRIES) == 16
    assert not NEW & {m["name"] for m in BENCH["per_layer"]}
    merged = bench_with_entries(BENCH)
    assert [m for m in merged["per_layer"] if m["name"] not in NEW] == \
        BENCH["per_layer"]
    assert merged["per_layer"][-PINNED_TAIL:] == \
        BENCH["per_layer"][-PINNED_TAIL:]
    layers = {m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in ENTRIES:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", m["workloads"]))
        assert (ROOT / f"benchmark/layer_metrics/{m['name']}.py").is_file()
    for cell in BENCH["workloads"]:
        mine = {BATCH: IDLE | BATCH_HOST, STEADY: STEADY_HOST}.get(
            cell["name"], set())
        before, after = ({m["name"] for m in harness.metrics_of_cell(
            b, cell, "per_layer")} for b in (BENCH, merged))
        assert after - before == mine and before <= after


@pytest.mark.parametrize("cell,host_side", [(BATCH, BATCH_HOST),
                                            (STEADY, STEADY_HOST)])
def test_traced_rehearsal_reads_the_scheduler_spans(capsys, monkeypatch,
                                                    cell, host_side):
    real = harness.load_cell

    def load_cell(name, rehearsal):
        bench, *rest = real(name, rehearsal)
        return (bench_with_entries(bench), *rest)

    monkeypatch.setattr(harness, "load_cell", load_cell)
    rc = harness.main(["--workload", cell, "--seed", "3000000017",
                       "--seconds", "2", "--trace", "1", "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True, out[-6:]
    assert line["metrics"] == {} and "breakdown" not in line
    would = set(line["rehearsal"]["would_report"])
    # the host-side readers found the program's spans and its gauge; the
    # six that need a device plane found none and left theirs out
    assert host_side <= would
    assert not would & IDLE

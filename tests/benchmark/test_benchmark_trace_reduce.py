"""The reduction from trace to numbers: the interval arithmetic on a trace
built by hand, and the recorded v5e trace kept beside the benchmark."""

from pathlib import Path

import pytest

from benchmark import trace_reduce
from benchmark.trace_reduce import DeviceLanes, Event, Trace

FIXTURES = Path(__file__).resolve().parents[2] / "benchmark" / "fixtures"


def by_hand() -> Trace:
    # window 0..1000 ns (the host mark). Device 0: a fusion 100..400, an
    # all-reduce 350..600 (50 ns under the fusion), a fusion 700..900.
    # Device 1: one fusion 0..1000.
    d0 = DeviceLanes(
        ops=[Event("fusion.1", 100, 300), Event("all-reduce.1", 350, 250),
             Event("fusion.2", 700, 200)],
        modules=[Event("jit_step(1)", 100, 800)])
    d1 = DeviceLanes(ops=[Event("fusion.1", 0, 1000)],
                     modules=[Event("jit_step(1)", 0, 1000)])
    return Trace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1},
                 host_marks=[Event("benchmark_window", 0, 1000)])


def test_busy_idle_is_the_union_of_intervals():
    busy = by_hand().busy_idle("benchmark_window")
    # device 0 busy: 100..600 and 700..900 = 700 ns of 1000
    assert busy["/device:TPU:0"]["busy_s"] == pytest.approx(700e-9)
    assert busy["/device:TPU:0"]["idle_pct"] == pytest.approx(30.0)
    assert busy["/device:TPU:1"]["idle_pct"] == pytest.approx(0.0)
    assert by_hand().worst_device("benchmark_window") == "/device:TPU:0"


def test_exposed_collective_is_what_no_compute_overlaps():
    exposed = by_hand().exposed_collective("benchmark_window")
    # 250 ns of all-reduce, 50 of them under fusion.1: 200 exposed
    assert exposed["/device:TPU:0"]["collective_s"] == pytest.approx(250e-9)
    assert exposed["/device:TPU:0"]["exposed_s"] == pytest.approx(200e-9)
    assert exposed["/device:TPU:0"]["exposed_pct"] == pytest.approx(20.0)
    assert exposed["/device:TPU:1"]["exposed_s"] == 0.0


def test_top_ops_gaps_and_labels():
    trace = by_hand()
    assert trace.top_ops("benchmark_window", 2) == [
        ("fusion.1", pytest.approx(300e-9)),
        ("all-reduce.1", pytest.approx(250e-9))]
    # gaps of device 0: 0..100, 600..700, 900..1000, longest first (ties by
    # the sort's order); wall = trace + 5 s; a span covers 5.0000006..
    gaps = trace.idle_gaps("benchmark_window", 3)
    assert sorted(gaps) == [(0, 100), (600, 700), (900, 1000)]
    labelled = trace.labelled_gaps(
        "benchmark_window", [(5.0 + 600e-9, 100e-9, "data_wait")], 5.0, 3)
    assert sorted(labelled) == [("data_wait", pytest.approx(100e-9)),
                                ("unattributed", pytest.approx(100e-9)),
                                ("unattributed", pytest.approx(100e-9))]


def test_module_times_and_window_without_a_mark():
    trace = by_hand()
    assert sorted(trace.module_times(r"jit_step")) == [
        pytest.approx(800e-9), pytest.approx(1000e-9)]
    trace.host_marks.clear()
    assert trace.window("benchmark_window") == (0, 1000)


def test_interval_helpers():
    merged = trace_reduce.merge([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert merged == [(1, 4), (5, 8)]
    assert trace_reduce.length(merged) == 6
    assert trace_reduce.intersect_len(merged, [(0, 2), (3, 6)]) == 3
    assert trace_reduce.gaps(merged, (0, 10)) == [(0, 1), (4, 5), (8, 10)]
    assert trace_reduce.clip([(0, 5), (8, 12)], (2, 9)) == [(2, 5), (8, 9)]
    assert trace_reduce.is_collective("all-reduce-start.3")
    assert trace_reduce.is_collective("reduce-scatter.1")
    assert not trace_reduce.is_collective("fusion.12")


# -- the recorded trace -------------------------------------------------------
# benchmark/tools/record_fixture.py on one TPU v5 lite (jax 0.9.0, libtpu
# 0.0.34), 2026-09-26: three steps of a 2-layer, 4-head (D=64), hidden-256
# GPT-2 at S=1024, batch 2, bf16, flash attention, under the window
# annotation. The numbers below were worked out apart from trace_reduce:
# the events read with ProfileData, the busy time by marking every
# nanosecond of the window that an ``XLA Ops`` event covers.

ONE_CHIP = FIXTURES / "v5e_1chip_tiny_gpt2_3steps.xplane.pb.gz"


@pytest.fixture(scope="module")
def one_chip_trace():
    return trace_reduce.load_xplane(ONE_CHIP)


def test_fixture_is_small_enough_to_keep():
    assert ONE_CHIP.stat().st_size < 1_000_000


def test_recorded_trace_planes_lanes_and_window(one_chip_trace):
    assert sorted(one_chip_trace.devices) == ["/device:TPU:0"]
    lanes = one_chip_trace.devices["/device:TPU:0"]
    assert len(lanes.ops) == 1806 and len(lanes.modules) == 3
    assert all(m.name.startswith("jit__train_step_impl(")
               for m in lanes.modules)
    lo, hi = one_chip_trace.window("benchmark_window")
    assert (lo, hi - lo) == (44927119.0, 5629999.0)


def test_recorded_trace_busy_and_idle(one_chip_trace):
    busy = one_chip_trace.busy_idle("benchmark_window")["/device:TPU:0"]
    assert busy["busy_s"] == pytest.approx(1600603e-9, rel=1e-9)
    assert busy["window_s"] == pytest.approx(5629999e-9, rel=1e-9)
    # a model this small leaves the chip idle most of the window
    assert busy["idle_pct"] == pytest.approx(
        100 * (1 - 1600603 / 5629999), rel=1e-9)


def test_recorded_trace_kernels_programs_and_no_collective(one_chip_trace):
    lanes = one_chip_trace.devices["/device:TPU:0"]
    kernels = [e for e in lanes.ops if trace_reduce.is_pallas_call(e.name)]
    # 2 layers x (forward, dkv, dq) x 3 steps, named after the `attn` scope
    assert len(kernels) == 18
    assert all(e.name.startswith("%attn.") for e in kernels)
    assert sum(e.dur_ns for e in kernels) == pytest.approx(791955.0)
    # the first step's program began 265 us before the host's mark (device
    # and host clocks are aligned to about that), so two lie wholly inside
    inside = one_chip_trace.module_times(r"train_step", "benchmark_window")
    assert sorted(inside) == [pytest.approx(625938e-9),
                              pytest.approx(626192e-9)]
    exposed = one_chip_trace.exposed_collective("benchmark_window")
    assert exposed["/device:TPU:0"]["collective_s"] == 0.0
    name, seconds = one_chip_trace.top_ops("benchmark_window", 1)[0]
    assert len(name) <= 96 and seconds > 0


# The same tool on the four-chip host (2026-09-26): the same model, batch 2 a
# chip over data=4, so every step all-reduces its gradients. Worked out apart
# from trace_reduce the same way, collectives being the events whose
# instruction starts ``%all-reduce``; in a program this small nothing overlaps
# them, so all of their time is exposed.

FOUR_CHIP = FIXTURES / "v5e_4chip_tiny_gpt2_3steps.xplane.pb.gz"
BY_HAND_4 = {     # chip: (busy ns, all-reduce ns = exposed ns)
    "/device:TPU:0": (2283904, 326454),
    "/device:TPU:1": (2282017, 325524),
    "/device:TPU:2": (2282750, 326237),
    "/device:TPU:3": (2277962, 321273),
}


def test_four_chip_trace_busy_and_exposed_collectives():
    assert FOUR_CHIP.stat().st_size < 1_000_000
    trace = trace_reduce.load_xplane(FOUR_CHIP)
    assert sorted(trace.devices) == sorted(BY_HAND_4)
    lo, hi = trace.window("benchmark_window")
    assert hi - lo == 8766969.0
    busy = trace.busy_idle("benchmark_window")
    exposed = trace.exposed_collective("benchmark_window")
    for chip, (busy_ns, coll_ns) in BY_HAND_4.items():
        assert busy[chip]["busy_s"] == pytest.approx(busy_ns * 1e-9, rel=1e-9)
        assert exposed[chip]["collective_s"] == pytest.approx(coll_ns * 1e-9,
                                                              rel=1e-9)
        assert exposed[chip]["exposed_s"] == pytest.approx(coll_ns * 1e-9,
                                                           rel=1e-9)
        assert exposed[chip]["exposed_pct"] == pytest.approx(
            100 * coll_ns / 8766969.0, rel=1e-9)
        lanes = trace.devices[chip]
        assert len(lanes.ops) == 1566 and len(lanes.modules) == 3
        # nine combined all-reduces in three steps; on a mesh the flash
        # kernels run in a shard_map and are named `%shard_map.<n>`
        assert sum(trace_reduce.is_collective(e.name)
                   for e in lanes.ops) == 9
        kernels = [e for e in lanes.ops
                   if trace_reduce.is_pallas_call(e.name)]
        assert len(kernels) == 18
        assert all(e.name.startswith("%shard_map.") for e in kernels)
    assert trace.worst_device("benchmark_window") == "/device:TPU:3"

"""Device time by named region (``benchmark/layer_metrics/_regions.py``): the
path arithmetic on traces built by hand, and two traces recorded on a v5e by
``benchmark/tools/record_region_fixture.py`` (a tiny GPT-2's train step; a
tiny token server), whose numbers below were summed by hand from the tool's
``<name>.regions.json`` listing of each execution's operations."""

import gzip
import importlib
import json
import types
from pathlib import Path

import pytest

from benchmark import trace_reduce
from benchmark.layer_metrics import _regions
from benchmark.trace_reduce import DeviceLanes, Event, Trace

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = ROOT / "benchmark" / "fixtures"
TRAIN = FIXTURES / "v5e_1chip_tiny_gpt2_regions_3steps.xplane.pb.gz"
SERVER = FIXTURES / "v5e_1chip_tiny_server_regions_6requests.xplane.pb.gz"
NEW_METRICS = {
    "flash_fwd_ms", "flash_bwd_ms", "train_attention_ms", "train_mlp_ms",
    "train_head_loss_ms", "train_optimizer_ms", "train_unscoped_ms",
    "batch_decode_kv_gather_ms", "batch_decode_kv_scatter_ms",
    "batch_decode_sample_ms", "batch_decode_model_ms",
    "batch_decode_unscoped_ms"}


# -- paths ---------------------------------------------------------------------

@pytest.mark.parametrize("path, regions, want", [
    ("jit(decode)/model/GPT2LMHead/block0/attn/dot_general:",
     ("model", "sample"), "model"),
    # the innermost listed region wins, whatever encloses it
    ("jit(decode)/model/GPT2LMHead/block0/attn/dot_general:",
     ("model", "attn"), "attn"),
    ("jit(f)/jvp(GPT2LMHead)/block0/attn/flash_fwd/flash_fwd/pallas_call:",
     ("attn", "mlp"), "attn"),
    # the transforms jax wraps around a component come off: backward and
    # forward carry the same scope
    ("jit(f)/transpose(jvp(loss))/reduce_sum:", ("loss",), "loss"),
    ("jit(f)/jvp(loss)/jit(take_along_axis)/gather:", ("loss",), "loss"),
    ("jit(f)/transpose(jvp(GPT2LMHead))/head/wte.attend/dot_general:",
     ("wte", "head"), "head"),
    # a whole component, never a substring
    ("jit(f)/jvp(GPT2LMHead)/block0/flash_attention/mul:", ("attn",),
     "unscoped"),
    ("jit(f)/jvp(GPT2LMHead)/block0/ln1/reduce_sum:", ("attn", "mlp"),
     "unscoped"),
    # a fusion across scopes carries several paths: the first in a region
    ("jit(d)/slice;jit(d)/kv_scatter/squeeze:", ("kv_scatter",),
     "kv_scatter"),
    (None, ("attn",), "unscoped"),
    ("", ("attn",), "unscoped"),
])
def test_region_of(path, regions, want):
    assert _regions.region_of(path, regions) == want


def test_components_take_the_transforms_off():
    assert _regions.components(
        "jit(_train_step_impl)/transpose(jvp(GPT2LMHead))/wte/jit(_take)/"
        "scatter-add:") == ["_train_step_impl", "GPT2LMHead", "wte", "_take",
                            "scatter-add"]


# -- a trace built by hand -----------------------------------------------------

GATHER_LOOP = ("%while.2 = (s32[], bf16[4,16]{1,0}) while((s32[], "
               "bf16[4,16]{1,0}) %tuple.1), condition=%cond, body=%body")
BODY = "%fusion.7 = bf16[4,16]{1,0} fusion(bf16[4,16]{1,0} %p), kind=kLoop"
SORT = "%sort.1 = f32[4,512]{1,0} sort(f32[4,512]{1,0} %x), dimensions={1}"
COPY = "%copy.9 = bf16[4,16]{0,1} copy(bf16[4,16]{1,0} %y)"
BY_HAND_PATHS = {"/device:TPU:0": {
    GATHER_LOOP: ["jit(decode)/kv_gather/while:"],
    BODY: ["jit(prefill)/model/add:", "jit(decode)/kv_gather/while/body/add:"],
    SORT: ["jit(decode)/sample/jit(argsort)/sort:"]}}


def by_hand(overlap: float = 0.0) -> Trace:
    # one jit_decode 100..1000: a `while` 100..500 holding two body
    # operations of 150 ns each, a sort 500..800, a copy without a path
    # 800..900 (``overlap`` ns of it under the sort); and a jit_prefill
    # whose operation has the body's very text
    lanes = DeviceLanes(
        ops=[Event(GATHER_LOOP, 100, 400), Event(BODY, 120, 150),
             Event(BODY, 300, 150), Event(SORT, 500, 300),
             Event(COPY, 800 - overlap, 100),
             Event(BODY, 1200, 50)],
        modules=[Event("jit_decode(11)", 100, 900),
                 Event("jit_prefill(12)", 1150, 200)])
    return Trace(devices={"/device:TPU:0": lanes},
                 host_marks=[Event("benchmark_window", 0, 2000)])


def test_containers_are_not_counted_and_no_path_is_unscoped():
    regions = _regions.PAGED_DECODE[1]
    (one,) = _regions.per_execution(by_hand(), BY_HAND_PATHS,
                                    _regions.PAGED_DECODE[0], regions)
    # the loop's 400 ns are its body's 2 x 150, counted once
    assert one["ns"] == {"kv_gather": 300.0, "kv_scatter": 0.0,
                         "sample": 300.0, "model": 0.0, "bookkeeping": 0.0,
                         "unscoped": 100.0, "collective": 0.0}
    assert one["busy_ns"] == 700.0
    notes = []
    got = _regions.split(by_hand(), BY_HAND_PATHS, *_regions.PAGED_DECODE,
                         note=lambda **kw: notes.append(kw))
    assert got == {"kv_gather": pytest.approx(300e-6), "kv_scatter": 0.0,
                   "sample": pytest.approx(300e-6), "model": 0.0,
                   "bookkeeping": 0.0, "unscoped": pytest.approx(100e-6),
                   "collective": 0.0}
    assert notes[0]["groups_sum_ms"] == notes[0]["busy_ms"] == \
        pytest.approx(700e-6)


def test_one_text_in_two_programs_reads_its_own_program_s_path():
    (one,) = _regions.per_execution(by_hand(), BY_HAND_PATHS, r"jit_prefill",
                                    ("model", "kv_gather"))
    assert one["ns"] == {"model": 50.0, "kv_gather": 0.0, "unscoped": 0.0,
                         "collective": 0.0}


def test_a_collective_is_no_gradient_s_region():
    """A combined all-reduce carries the path of ONE of the gradients it
    combines: it is counted as a collective, under no layer."""
    reduce = ("%all-reduce.7 = (bf16[64,16]{1,0}, bf16[16]{0}) all-reduce("
              "bf16[64,16]{1,0} %a, bf16[16]{0} %b), replica_groups={{0,1}}")
    matmul = "%fusion.3 = bf16[64,16]{1,0} fusion(bf16[8,64]{1,0} %x)"
    lanes = DeviceLanes(
        ops=[Event(matmul, 100, 300), Event(reduce, 400, 200)],
        modules=[Event("jit__train_step_impl(5)", 100, 500)])
    trace = Trace(devices={"/device:TPU:0": lanes},
                  host_marks=[Event("benchmark_window", 0, 1000)])
    path = "jit(_train_step_impl)/transpose(jvp(GPT2LMHead))/block0/mlp/fc/"
    paths = {"/device:TPU:0": {matmul: [path + "dot_general:"],
                               reduce: [path + "all-reduce:"]}}
    (one,) = _regions.per_execution(trace, paths, *_regions.TRAIN_STEP)
    assert one["ns"]["mlp"] == 300.0
    assert one["ns"]["collective"] == 200.0
    assert sum(one["ns"].values()) == one["busy_ns"] == 500.0


def test_regions_that_do_not_sum_to_the_busy_time_give_nothing():
    notes = []
    assert _regions.split(by_hand(overlap=50.0), BY_HAND_PATHS,
                          *_regions.PAGED_DECODE,
                          note=lambda **kw: notes.append(kw)) is None
    # 700 ns of leaves on 650 ns of busy time
    assert notes[0]["worst_gap_pct"] == pytest.approx(100 * 50 / 650)
    assert _regions.split(by_hand(), BY_HAND_PATHS, r"jit_nothing",
                          ("model",)) is None


# -- the recorded traces -------------------------------------------------------

@pytest.fixture(scope="module")
def train():
    return trace_reduce.load_xplane(TRAIN), _regions.scope_paths(TRAIN)


@pytest.fixture(scope="module")
def server():
    return trace_reduce.load_xplane(SERVER), _regions.scope_paths(SERVER)


def test_fixtures_are_small_and_named_apart():
    names = sorted(p.name for p in FIXTURES.glob("*.xplane.pb.gz"))
    assert len(set(names)) == len(names) >= 4
    for path in (TRAIN, SERVER):
        assert path.stat().st_size < 500_000


def test_scope_paths_come_from_the_metadata_records(train, server):
    """A path is in no event's name and no event's stats (all ProfileData
    shows); it is the ``tf_op`` stat of the instruction's metadata record."""
    trace, paths = train
    (plane,) = paths
    assert plane == "/device:TPU:0"
    names = {e.name for e in trace.devices[plane].ops}
    assert not any("op_name" in n or "jvp(" in n for n in names)
    with_path = names & set(paths[plane])
    assert 100 <= len(with_path) < len(names)
    kernels = {n: paths[plane][n] for n in names
               if trace_reduce.is_pallas_call(n)}
    # the instruction is named after its innermost scope, the path ends in
    # the kernel's own name (pallas_call's ``name``) under that scope
    assert sorted({n.split(" = ")[0].rsplit(".", 1)[0] for n in kernels}) \
        == ["%flash_bwd_dkv", "%flash_bwd_dq", "%flash_fwd"]
    assert kernels and all(
        p[0].endswith("/pallas_call:") and "/attn/" in p[0]
        for p in kernels.values())
    # copies and slices that run beside the compute carry none
    assert not any(n.startswith(("%copy-done", "%slice-start"))
                   for n in paths[plane])


def test_train_step_split_by_hand(train):
    trace, paths = train
    kernels = _regions.per_execution(trace, paths, *_regions.FLASH_KERNELS)
    # three steps recorded, the first began before the window
    assert [r["start_ns"] for r in kernels] == [49529252.0, 50588692.0]
    # step 1, two layers: 57383 + 57382; 2 x 44463; 30147 + 30146 ns
    assert kernels[0]["ns"] == {
        "flash_fwd": 114765.0, "flash_bwd_dkv": 88926.0,
        "flash_bwd_dq": 60293.0, "unscoped": 357473.0, "collective": 0.0}
    assert kernels[0]["busy_ns"] == 621457.0
    step = _regions.per_execution(trace, paths, *_regions.TRAIN_STEP)[0]
    assert step["ns"] == {
        "attn": 345932.0, "mlp": 101555.0, "wte": 29850.0, "wpe": 17369.0,
        "ln_f": 2076.0, "head": 39085.0, "loss": 42943.0,
        "optimizer": 2803.0, "unscoped": 39844.0, "collective": 0.0}
    assert sum(step["ns"].values()) == step["busy_ns"] == 621457.0
    # the kernels lie inside `attn`; the named kernels are every Pallas call
    lanes = trace.devices["/device:TPU:0"]
    pallas = sum(e.dur_ns for e in lanes.ops
                 if trace_reduce.is_pallas_call(e.name)
                 and 49529252.0 <= e.start_ns < 49529252.0 + 626855.0)
    assert pallas == 114765.0 + 88926.0 + 60293.0 < step["ns"]["attn"]


def test_train_step_medians(train):
    trace, paths = train
    notes = []
    got = _regions.split(trace, paths, *_regions.TRAIN_STEP,
                         note=lambda **kw: notes.append(kw))
    # medians of two steps: (345932 + 345699) / 2 ns, ...
    assert got == pytest.approx({
        "attn": 0.3458155, "mlp": 0.1016085, "wte": 0.029777,
        "wpe": 0.0172355, "ln_f": 0.002076, "head": 0.039085,
        "loss": 0.043052, "optimizer": 0.002806, "unscoped": 0.0398705,
        "collective": 0.0})
    assert notes[0]["executions"] == 2
    assert notes[0]["worst_gap_pct"] == 0.0
    assert notes[0]["groups_sum_ms"] == pytest.approx(0.621326)


def test_decode_step_split_by_hand(server):
    trace, paths = server
    runs = _regions.per_execution(trace, paths, *_regions.PAGED_DECODE)
    assert len(runs) == 10          # the prefills and one-op programs: not
    first = runs[0]
    assert first["start_ns"] == 49778127.0
    # kv_gather: the gathers 313 + 102 + 6 + 4922 + 4862 + 558 + 559 and
    # the per-layer slices 128 + 128 + 425 + 130 + 131 + 431 ns; sample: the
    # sort 6300, the sorted-logits gather 88533, twelve small ones (3059);
    # bookkeeping: 330 + 545 + 18 + 360 (concatenate, select, add, scatter)
    assert first["ns"] == {"kv_gather": 12695.0, "kv_scatter": 4579.0,
                           "sample": 97892.0, "model": 18574.0,
                           "bookkeeping": 1253.0, "unscoped": 5429.0,
                           "collective": 0.0}
    assert sum(first["ns"].values()) == first["busy_ns"] == 140422.0
    got = _regions.split(trace, paths, *_regions.PAGED_DECODE)
    assert got == pytest.approx({
        "kv_gather": 0.012714, "kv_scatter": 0.004769, "sample": 0.097888,
        "model": 0.0185255, "bookkeeping": 0.0012545, "unscoped": 0.0053775,
        "collective": 0.0})


def test_what_xla_leaves_without_a_path_is_unscoped(server):
    """The cumulative sum of the sampler becomes ``reduce-window``s that
    carry no path; `bookkeeping` is a region of its own, which
    `batch_decode_unscoped_ms` adds to what has none."""
    trace, paths = server
    table = paths["/device:TPU:0"]
    windows = {e.name for e in trace.devices["/device:TPU:0"].ops
               if e.name.startswith("%reduce-window")}
    assert windows and not windows & set(table)
    kept = [p for ps in table.values() for p in ps
            if "/bookkeeping/" in p and p.startswith("jit(decode)")]
    assert kept and {_regions.region_of(p, _regions.PAGED_DECODE[1])
                     for p in kept} == {"bookkeeping"}


# -- the readers ---------------------------------------------------------------

def fake_run(tmp_path, fixture):
    """What a reader is given after a traced run, from a fixture."""
    where = tmp_path / "trace" / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(gzip.decompress(
        fixture.read_bytes()))
    notes = []
    return types.SimpleNamespace(
        trace_data=trace_reduce.load_xplane(fixture), out_dir=tmp_path,
        facts={}, note=lambda **kw: notes.append(kw), notes=notes)


def read(metric, run):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(run)


def test_train_readers_sum_to_the_step(tmp_path):
    run = fake_run(tmp_path, TRAIN)
    got = {m: read(m, run) for m in (
        "flash_fwd_ms", "flash_bwd_ms", "train_attention_ms", "train_mlp_ms",
        "train_head_loss_ms", "train_optimizer_ms", "train_unscoped_ms")}
    assert got["flash_fwd_ms"] == pytest.approx(0.1147645)
    assert got["flash_bwd_ms"] == pytest.approx(0.088926 + 0.0602935)
    assert got["train_head_loss_ms"] == pytest.approx(
        0.029777 + 0.0172355 + 0.002076 + 0.039085 + 0.043052)
    assert got["train_optimizer_ms"] == pytest.approx(0.002806)
    assert got["train_unscoped_ms"] == pytest.approx(0.0398705)
    five = sum(got[m] for m in got if m.startswith("train_"))
    assert five == pytest.approx(0.621326, rel=1e-3)
    # one parse of the file and one split per program, however many readers
    assert sum("scope_paths_s" in n for n in run.notes) == 1
    assert sum("region_split" in n for n in run.notes) == 2
    # the decode step is not in this trace: nothing to read, not an error
    assert read("batch_decode_sample_ms", run) is None


def test_decode_readers_sum_to_the_step(tmp_path):
    run = fake_run(tmp_path, SERVER)
    got = {m: read(m, run) for m in sorted(NEW_METRICS)
           if m.startswith("batch_decode_")}
    assert got["batch_decode_sample_ms"] == pytest.approx(0.097888)
    assert got["batch_decode_unscoped_ms"] == pytest.approx(
        0.0053775 + 0.0012545)
    assert sum(got.values()) == pytest.approx(0.1404845, rel=1e-3)


def test_a_program_without_the_scopes_reads_as_nothing(tmp_path):
    """The parent of the PR that added the scopes: the PR 22 fixture has
    flax's module scopes and none of the new ones."""
    run = fake_run(tmp_path, FIXTURES
                   / "v5e_1chip_tiny_gpt2_3steps.xplane.pb.gz")
    assert read("flash_fwd_ms", run) is None
    assert read("flash_bwd_ms", run) is None
    assert read("train_optimizer_ms", run) is None
    assert read("train_attention_ms", run) == pytest.approx(0.3457405)
    run.trace_data = None
    assert read("train_attention_ms", run) is None


def test_benchmark_json_lists_the_twelve_at_its_end():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-12:]
    assert {m["name"] for m in tail} == NEW_METRICS
    for m in tail:
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "device_trace")
    # on one chip XLA fuses AdamW into the gradients' fusions: the optimizer
    # has instructions of its own only behind the all-reduces of four chips
    cells = {m["name"]: m["workloads"] for m in tail}
    assert cells.pop("train_optimizer_ms") == ["train_gpt2_355m_dp4"]
    assert all(len(c) == (1 if n.startswith("batch_") else 2)
               for n, c in cells.items())

"""Experiment tooling (experiments/scaling.py): the HLO collective census
must find the all-reduce XLA inserts for a cross-device reduction."""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_pytorch_training_tpu.analysis.hlo_rules import (
    collective_census,
)


def test_census_finds_allreduce_in_sharded_reduction(mesh8):
    sharding = NamedSharding(mesh8, P("data"))
    x = jax.device_put(np.arange(32, dtype=np.float32), sharding)

    f = jax.jit(lambda v: v.sum(), in_shardings=sharding,
                out_shardings=NamedSharding(mesh8, P()))
    text = f.lower(x).compile().as_text()
    census = collective_census(text)
    assert any(c["op"] == "all-reduce" for c in census), census


def test_census_empty_on_local_computation():
    f = jax.jit(lambda v: v * 2)
    text = f.lower(jnp.ones(4)).compile().as_text()
    assert collective_census(text) == []


@pytest.mark.slow
def test_trace_derived_collective_share(mesh8, tmp_path):
    """The jax.profiler trace parser must find the data-parallel all-reduce
    and report a share in (0, 100] — the README's '~X%' number, measured
    (VERDICT r2 #8: nothing parsed a captured trace)."""
    from distributed_pytorch_training_tpu.experiments.harness import (
        build_image_trainer, synth_image_batch,
    )
    from distributed_pytorch_training_tpu.telemetry.trace_analysis import (
        capture_step_trace, collective_share,
    )

    trainer, state, mesh = build_image_trainer(jax.devices(), False)
    batch, _ = synth_image_batch(mesh, 8)
    key = jax.random.PRNGKey(0)
    state, _ = trainer._train_step(state, batch, key)  # warmup/compile
    td = str(tmp_path / "trace")
    capture_step_trace(trainer._train_step, state, batch, key, td, steps=3)

    share = collective_share(td)
    assert "all-reduce" in share["by_op"], share
    assert 0.0 < share["share_pct"] <= 100.0, share
    assert share["op_us"] > share["collective_us"] > 0.0


def test_trace_parser_raises_without_trace(tmp_path):
    import pytest

    from distributed_pytorch_training_tpu.telemetry.trace_analysis import (
        collective_share,
    )
    with pytest.raises(FileNotFoundError):
        collective_share(str(tmp_path))


# ---- smoke-run every experiment driver (VERDICT r2 #9) -------------------

def _run_experiment(argv):
    from distributed_pytorch_training_tpu.experiments import scaling
    scaling.main(argv)


_SMOKE = ["--batch-size", "8", "--steps", "1", "--repeats", "1",
          "--min-window-s", "0.01"]


@pytest.mark.slow
def test_experiment_scaling_smoke(capsys):
    _run_experiment(["scaling"] + _SMOKE)
    out = capsys.readouterr().out
    assert "scaling_efficiency_pct" in out


@pytest.mark.slow
def test_experiment_batch_smoke(capsys):
    _run_experiment(["batch"] + _SMOKE + ["--batch-list", "8,16"])
    out = capsys.readouterr().out
    assert "per_device_batch" in out


@pytest.mark.slow
def test_experiment_amp_smoke(capsys):
    _run_experiment(["amp"] + _SMOKE)
    out = capsys.readouterr().out
    assert "bf16_speedup" in out


@pytest.mark.slow
def test_experiment_gradsync_smoke(capsys, tmp_path):
    _run_experiment(["gradsync"] + _SMOKE
                    + ["--csv", str(tmp_path / "gs.csv")])
    out = capsys.readouterr().out
    assert "grad_sync_share_1vsN_pct" in out
    assert "grad_sync_share_trace_pct" in out
    assert "all-reduce" in out  # census + trace breakdown both present
    assert (tmp_path / "gs.csv").exists()


@pytest.mark.slow
def test_experiment_grad_sync_smoke(capsys):
    """The explicit-reducer arm: every mode row carries the census columns
    (engagement proof) and the bucketed rows show the compressed wire."""
    _run_experiment(["grad_sync", "--model", "gpt2_124m", "--lm-tiny",
                     "--seq-len", "32", "--bucket-cap-mb", "25"] + _SMOKE)
    out = capsys.readouterr().out
    assert "grad_collectives" in out
    assert "bucketed_bf16" in out and "bucketed_int8" in out
    assert "bucketed_int8_multihop" in out
    assert "wire_bytes_per_replica" in out
    assert "exposed_comm_pct" in out


@pytest.mark.slow
def test_experiment_fsdp_smoke(capsys):
    """The explicit-FSDP arm (ISSUE 7): replicated-vs-fsdp rows with the
    per-layer collective census, at-rest residency division and the
    fsdp_gather_bytes wire term."""
    _run_experiment(["fsdp", "--model", "gpt2_124m", "--lm-tiny",
                     "--seq-len", "32"] + _SMOKE)
    out = capsys.readouterr().out
    assert "fsdp_fp32" in out and "fsdp_int8_multihop" in out
    assert "param_bytes_at_rest_per_replica" in out
    assert "fsdp_gather_bytes" in out
    assert "all_gathers" in out


def test_comm_overlap_split_math(tmp_path):
    """Interval arithmetic of the exposed-vs-hidden split on a synthetic
    trace: one collective fully covered by compute, one half covered, one
    fully exposed."""
    import gzip
    import json

    from distributed_pytorch_training_tpu.telemetry.trace_analysis import (
        comm_overlap_split,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        # compute lane: [0, 100) and [200, 250)
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 0,
         "dur": 100},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.2", "ts": 200,
         "dur": 50},
        # hidden: all-reduce [10, 60) inside compute
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce.1", "ts": 10,
         "dur": 50},
        # half hidden: [80, 120) overlaps compute only until 100
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-gather.1", "ts": 80,
         "dur": 40},
        # fully exposed: [130, 160)
        {"ph": "X", "pid": 1, "tid": 2, "name": "reduce-scatter.1",
         "ts": 130, "dur": 30},
        # completion markers must not count
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce-done.1",
         "ts": 160, "dur": 500},
    ]
    d = tmp_path / "plugins"
    d.mkdir()
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    split = comm_overlap_split(str(tmp_path))
    assert split["collective_us"] == 120.0
    assert split["hidden_us"] == 70.0   # 50 + 20
    assert split["exposed_us"] == 50.0  # 20 + 30
    assert split["exposed_frac_pct"] == round(100.0 * 50 / 120, 2)


def test_comm_overlap_split_cross_pid_and_async_start(tmp_path):
    """ISSUE-6 satellite: the two split properties only exercised
    implicitly before. (a) Per-pid isolation — compute on ANOTHER device
    never hides a collective (overlap is same-device concurrency, not
    wall-clock coincidence). (b) Async ``-start`` events span the transfer
    and are the measured interval; their ``-done`` completion markers (a
    wait, not work) must add nothing."""
    import gzip
    import json

    from distributed_pytorch_training_tpu.telemetry.trace_analysis import (
        comm_overlap_split,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 2, "tid": 1,
         "args": {"name": "XLA Ops"}},
        # device 0 compute: [0, 100)
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 0,
         "dur": 100},
        # (a) device 1 collective [10, 60) — device 0's compute must NOT
        # hide it: device 1 runs nothing else, so it is fully exposed
        {"ph": "X", "pid": 2, "tid": 1, "name": "all-gather.7", "ts": 10,
         "dur": 50},
        # (b) async start on device 0: [20, 70) spans the transfer, fully
        # inside device 0's compute -> fully hidden
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce-start.3",
         "ts": 20, "dur": 50},
        # its completion marker: wait-not-work, counts nothing
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce-done.3",
         "ts": 70, "dur": 400},
    ]
    d = tmp_path / "plugins"
    d.mkdir()
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    split = comm_overlap_split(str(tmp_path))
    assert split["collective_us"] == 100.0  # 50 (dev1) + 50 (async start)
    assert split["hidden_us"] == 50.0       # only the same-device overlap
    assert split["exposed_us"] == 50.0      # the cross-device one
    assert split["exposed_frac_pct"] == 50.0


def test_trace_census_ragged_all_to_all_and_async_pairing(tmp_path):
    """The widened trace regex (ISSUE 3 satellite): `ragged-all-to-all`
    (MoE dispatch) counts as communication, and an async `-start`/`-done`
    pair counts ONCE — the `-done` completion marker's duration is
    wait-not-work, so adding it would double the collective share."""
    import gzip
    import json

    from distributed_pytorch_training_tpu.telemetry.trace_analysis import (
        collective_share,
    )

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 0,
         "dur": 100},
        # async pair: the -start span covers the transfer (40us of work);
        # the -done marker is a 500us wait that must NOT count
        {"ph": "X", "pid": 1, "tid": 1, "name": "all-reduce-start.3",
         "ts": 100, "dur": 40},
        {"ph": "X", "pid": 1, "tid": 1, "name": "all-reduce-done.3",
         "ts": 140, "dur": 500},
        # MoE dispatch op the old alternation missed entirely
        {"ph": "X", "pid": 1, "tid": 1, "name": "ragged-all-to-all.7",
         "ts": 700, "dur": 25},
    ]
    d = tmp_path / "plugins"
    d.mkdir()
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    share = collective_share(str(tmp_path))
    assert share["by_op"] == {"all-reduce": 40.0, "ragged-all-to-all": 25.0}
    assert share["collective_us"] == 65.0  # -done's 500us excluded
    assert share["op_us"] == 665.0


@pytest.mark.slow
def test_experiment_pipeline_smoke(capsys):
    _run_experiment(["pipeline"] + _SMOKE)
    out = capsys.readouterr().out
    assert "bubble_predicted_pct" in out
    assert "dp=8 (baseline)" in out
    assert "pipe=2,data=4" in out


def test_plot_generation_all_kinds(tmp_path):
    """plots.py renders a PNG for every experiment CSV shape (the README's
    'Tables + plots' promise — plots regenerate from the CSVs)."""
    import csv as csv_mod

    from distributed_pytorch_training_tpu.experiments import plots

    fixtures = {
        "scaling": [
            {"chips": 1, "global_samples_per_s": 100.0,
             "per_chip_samples_per_s": 100.0, "scaling_efficiency_pct": 100.0},
            {"chips": 8, "global_samples_per_s": 730.0,
             "per_chip_samples_per_s": 91.2, "scaling_efficiency_pct": 91.2},
        ],
        "batch": [
            {"per_device_batch": 32, "global_samples_per_s": 50.0},
            {"per_device_batch": 256, "global_samples_per_s": 300.0},
        ],
        "amp": [
            {"precision": "fp32", "global_samples_per_s": 100.0},
            {"precision": "bf16", "global_samples_per_s": 420.0},
            {"precision": "bf16_speedup", "global_samples_per_s": 4.2},
        ],
        "gradsync": [
            {"measurement": "step_time_1chip_ms", "value": 10.0},
            {"measurement": "grad_sync_share_1vsN_pct", "value": 12.0},
            {"measurement": "grad_sync_share_trace_pct", "value": 10.5},
        ],
        "pipeline": [
            {"config": "dp=8 (baseline)", "microbatches": "-",
             "samples_per_s": 100.0, "bubble_predicted_pct": 0.0,
             "vs_dp_pct": 100.0},
            {"config": "pipe=2,data=4", "microbatches": 4,
             "samples_per_s": 80.0, "bubble_predicted_pct": 20.0,
             "vs_dp_pct": 80.0},
        ],
    }
    for kind, rows in fixtures.items():
        path = tmp_path / f"{kind}.csv"
        with open(path, "w", newline="") as f:
            w = csv_mod.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
        out = tmp_path / f"{kind}.png"
        plots.main([str(path), "--out", str(out)])  # kind auto-detected
        assert out.exists() and out.stat().st_size > 5000, kind


def test_plot_appended_csv_uses_latest_run(tmp_path):
    """The documented flow APPENDS rows across runs; plots must render the
    latest sweep, not a zigzag across all of them."""
    import csv as csv_mod

    from distributed_pytorch_training_tpu.experiments import plots

    run1 = [{"per_device_batch": b, "global_samples_per_s": v}
            for b, v in ((32, 10.0), (64, 20.0))]
    run2 = [{"per_device_batch": b, "global_samples_per_s": v}
            for b, v in ((32, 11.0), (64, 22.0))]
    path = tmp_path / "batch.csv"
    with open(path, "w", newline="") as f:
        w = csv_mod.DictWriter(f, fieldnames=["per_device_batch",
                                              "global_samples_per_s"])
        w.writeheader()
        w.writerows(run1 + run2)

    rows = plots._latest(plots._read(str(path)), "batch")
    assert [r["global_samples_per_s"] for r in rows] == ["11.0", "22.0"]
    out = tmp_path / "b.png"
    plots.main([str(path), "--out", str(out)])
    assert out.exists() and out.stat().st_size > 5000


@pytest.mark.slow
def test_experiment_gradsync_bert_smoke(capsys):
    """The BASELINE matrix's config 4 is 'BERT-base MLM seq-len 512
    (grad-sync profiling run)' — the gradsync driver must serve LM models,
    not only the image configs (tiny shapes here; real seq on hardware)."""
    from distributed_pytorch_training_tpu.experiments import scaling
    scaling.main(["gradsync", "--model", "bert_base", "--seq-len", "64",
                  "--batch-size", "2", "--steps", "1", "--repeats", "1",
                  "--min-window-s", "0.01", "--lm-tiny"])
    out = capsys.readouterr().out
    assert "grad_sync_share_trace_pct" in out
    assert "all-reduce" in out


def test_flash_causal_flops_use_kernel_cost_estimate():
    """The analytic FLOPs instrument must use the kernel's own CostEstimate
    (causal-aware: only live diagonal blocks), not one tile x the full grid
    — the r3 advisor found causal attention MFU ~2x overcounted (ADVICE r3)."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_training_tpu.experiments.flops import (
        jaxpr_matmul_flops,
    )
    from distributed_pytorch_training_tpu.ops import flash_attention
    from distributed_pytorch_training_tpu.ops.flash_attention import tile_census

    b, s, h, d, blk = 1, 1024, 2, 64, 512
    q = jnp.zeros((b, s, h, d), jnp.float32)

    def fwd(q):
        return flash_attention(q, q, q, True, None, blk, blk)

    got = jaxpr_matmul_flops(fwd, q)
    census = tile_census(s, s, blk, blk, True)
    assert census[:3] == (1, 2, 1)  # 3 of 4 blocks; the 2 on the diagonal
    # are taken whole by the forward kernel (its walk's rows are the block)
    scores = 3 * blk * blk
    assert census.scores(blk) == scores
    expect = b * h * scores * 4 * d
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    # and the non-causal kernel counts the full rectangle
    got_full = jaxpr_matmul_flops(
        lambda q: flash_attention(q, q, q, False, None, blk, blk), q)
    np.testing.assert_allclose(got_full, b * h * 4 * 4 * blk * blk * d,
                               rtol=1e-6)


def test_shard_map_flops_count_every_shard(mesh8):
    """The flash adapter runs per shard inside a shard_map on a multi-device
    mesh; the analytic count must still be the whole call's (the body is
    one shard's program, so it counts once per shard)."""
    from distributed_pytorch_training_tpu.experiments.flops import (
        jaxpr_matmul_flops,
    )
    from distributed_pytorch_training_tpu.ops import make_flash_attention_fn

    q = jnp.ones((8, 128, 2, 16), jnp.float32)
    plain = jaxpr_matmul_flops(
        lambda q: make_flash_attention_fn(True)(q, q, q), q)
    sharded = jaxpr_matmul_flops(
        lambda q: make_flash_attention_fn(True, mesh=mesh8)(q, q, q), q)
    assert plain > 0 and sharded == plain


def test_chip_peak_unknown_tpu_kind_raises():
    """A TPU the peaks table does not know is an error to fix in the table,
    never an MFU silently left out; None is for non-TPU devices only."""
    import types

    import pytest

    from distributed_pytorch_training_tpu.experiments.flops import (
        chip_peak_tflops,
    )

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert chip_peak_tflops(dev("tpu", "TPU v5 lite")) == 197.0
    assert chip_peak_tflops(dev("cpu", "cpu")) is None
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        chip_peak_tflops(dev("tpu", "TPU v9 imaginary"))

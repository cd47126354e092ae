"""Record the two small traces that pin ``layer_metrics/_regions.py``: a few
train steps of the small fixture model (as ``record_fixture``: real head
size, S=1024, the real flash kernels) on ONE chip, and a few requests
through a small token server (``SlotEngine`` behind ``Router``), so a few
executions of ``jit_decode`` and ``jit_prefill`` beside the eager one-op
programs of admission and completion. Both under the profiler options and
the window annotation the harness uses.

    python -m benchmark.tools.record_region_fixture --out chiprun_out/regions

Writes ``<out>/train.xplane.pb.gz``, ``<out>/decode.xplane.pb.gz``, for each
``<name>.describe.json`` (``trace_reduce.describe``) and
``<name>.regions.json``: per execution of the program, every leaf operation
with its start, duration, scope path and region, from which the numbers in
``tests/benchmark/test_benchmark_regions.py`` were worked out by hand. Chip
only.
"""

from __future__ import annotations

import argparse
import gzip
import json
import re
import shutil
import sys
from pathlib import Path


def _traced(out: Path, name: str, body) -> Path:
    """Run ``body()`` as `benchmark.run.Run.profile` does and keep the
    trace, packed, as ``<out>/<name>.xplane.pb.gz``."""
    import jax

    from benchmark import trace_reduce
    from benchmark.run import WINDOW_MARK

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    raw = out / f"{name}_raw"
    jax.profiler.start_trace(str(raw), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            body()
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(raw)
    packed = out / f"{name}.xplane.pb.gz"
    packed.write_bytes(gzip.compress(path.read_bytes(), 9))
    (out / f"{name}.describe.json").write_text(
        json.dumps(trace_reduce.describe(path), indent=1, default=str))
    shutil.rmtree(raw, ignore_errors=True)
    return packed


def _by_hand(packed: Path, programs) -> dict:
    """What the test's numbers are worked out from: per program, per
    execution, the leaf operations with their scope paths and regions, and
    `_regions.split`'s own answer beside them."""
    from benchmark import trace_reduce
    from benchmark.layer_metrics import _regions
    from benchmark.run import WINDOW_MARK

    trace = trace_reduce.load_xplane(packed)
    paths = _regions.scope_paths(packed)
    window = trace.window(WINDOW_MARK)
    out = {"gz_bytes": packed.stat().st_size, "window_ns": window,
           "modules": sorted({m.name for lanes in trace.devices.values()
                              for m in lanes.modules}),
           "programs": {}}
    for label, (pattern, regions) in programs.items():
        notes = []
        got = _regions.split(trace, paths, pattern, regions,
                             note=lambda **kw: notes.append(kw))
        executions = []
        for plane, lanes in trace.devices.items():
            for m in lanes.modules:
                if not re.search(pattern, m.name) or \
                        m.start_ns < window[0] or m.end_ns > window[1]:
                    continue
                ops = []
                for e in lanes.ops:
                    if e.start_ns < m.start_ns or e.end_ns > m.end_ns:
                        continue
                    path = _regions.path_of(paths.get(plane, {}), e.name,
                                             m.name)
                    ops.append({
                        "op": trace_reduce.short_name(e.name),
                        "start_ns": e.start_ns, "dur_ns": e.dur_ns,
                        "container": trace_reduce.is_container(e.name),
                        "collective": trace_reduce.is_collective(e.name),
                        "path": path,
                        "region": _regions.region_of(path, regions)})
                executions.append({"plane": plane, "module": m.name,
                                   "start_ns": m.start_ns,
                                   "dur_ns": m.dur_ns, "ops": ops})
        out["programs"][label] = {"pattern": pattern,
                                  "regions": list(regions), "split_ms": got,
                                  "notes": notes, "executions": executions}
    return out


def record_train(out: Path, steps: int) -> Path:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.ops import make_flash_attention_fn
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.parallel.sharding import shard_batch
    from distributed_pytorch_training_tpu.training.loop import (
        TrainConfig, Trainer,
    )
    from distributed_pytorch_training_tpu.training.optim import (
        make_optimizer, make_schedule,
    )
    from distributed_pytorch_training_tpu.training.tasks import (
        LanguageModelingTask,
    )

    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    model = get_model("gpt2_124m", hidden_dim=256, depth=2, num_heads=4,
                      vocab_size=2048, dtype=jnp.bfloat16,
                      attention_fn=make_flash_attention_fn(causal=True,
                                                           mesh=mesh))
    trainer = Trainer(LanguageModelingTask(compute_dtype=jnp.bfloat16), mesh,
                      TrainConfig(per_device_batch=2, bf16=True),
                      rules=type(model).partition_rules())
    tx = make_optimizer("adamw", make_schedule("constant", 3e-4))
    state = trainer.init_state(model, np.zeros((1, 1024), np.int32), tx,
                               jax.random.PRNGKey(0))
    batch = shard_batch({
        "input_ids": np.random.default_rng(0).integers(
            0, 2048, (2, 1024)).astype(np.int32),
        "weight": np.ones(2, np.float32)}, mesh)
    key = jax.random.PRNGKey(0)
    box = {"state": state}

    def run_steps(n):
        for _ in range(n):
            box["state"], metrics = trainer._train_step(box["state"], batch,
                                                        key)
        float(metrics["weight"])

    run_steps(2)   # compile, settle
    return _traced(out, "train", lambda: run_steps(steps))


def record_decode(out: Path, requests: int, new_tokens: int) -> Path:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.serving.continuous import SlotEngine
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )
    from distributed_pytorch_training_tpu.serving.router import (
        InProcessReplica, Router,
    )

    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    model = get_model("gpt2_124m", hidden_dim=256, depth=2, num_heads=4,
                      vocab_size=2048, max_position=128, dtype=jnp.bfloat16)
    params = jax.jit(lambda k: model.init(
        k, np.zeros((1, 32), np.int32), train=False)["params"])(
        jax.random.PRNGKey(0))
    cfg = PagedServeConfig(buckets=(32, 64), rows=4, max_new_tokens=32,
                           serve_dtype="bf16", page_size=16, kv_dtype="fp32",
                           prefix_skip=True)
    engine = SlotEngine(model, mesh, cfg, params)
    for kind, bucket in (("paged_decode", 0), ("paged_prefill", 32),
                         ("paged_prefill", 64)):
        engine._executable(kind, bucket)
    router = Router([InProcessReplica("replica0", engine)])
    rng = np.random.default_rng(0)

    def serve(n):
        handles = [router.submit(
            rng.integers(0, 2048, size=int(size)).astype(np.int32),
            max_new_tokens=new_tokens, seed=i)
            for i, size in enumerate(rng.integers(8, 64, size=n))]
        for h in handles:
            h.result(timeout=120.0)

    try:
        serve(4)   # every program and eager op once, outside the trace
        return _traced(out, "decode", lambda: serve(requests))
    finally:
        router.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=6)
    args = ap.parse_args(argv)

    import jax

    from benchmark.layer_metrics import _regions

    if jax.devices()[0].platform != "tpu":
        print("record_region_fixture: chip only", file=sys.stderr)
        return 1
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    summary = {}
    for name, packed, programs in (
            ("train", record_train(out, args.steps),
             {"train_step": _regions.TRAIN_STEP,
              "flash_kernels": _regions.FLASH_KERNELS}),
            ("decode", record_decode(out, args.requests, args.new_tokens),
             {"paged_decode": _regions.PAGED_DECODE})):
        by_hand = _by_hand(packed, programs)
        (out / f"{name}.regions.json").write_text(
            json.dumps(by_hand, indent=1, default=str))
        summary[name] = {
            "gz_bytes": by_hand["gz_bytes"], "modules": by_hand["modules"],
            "split_ms": {k: v["split_ms"]
                         for k, v in by_hand["programs"].items()},
            "notes": {k: v["notes"] for k, v in by_hand["programs"].items()}}
    print(json.dumps(summary, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())

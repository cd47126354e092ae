"""Record the small trace kept under ``benchmark/fixtures/``: a few steps of a
small GPT-2 (real head size, S=1024, so the flash kernels are the real ones)
through the real ``Trainer`` on every chip present, under the profiler, with
the window annotation the harness uses.

    python -m benchmark.tools.record_fixture --out chiprun_out/fixture

Writes ``<out>/trace.xplane.pb.gz`` and ``<out>/describe.json`` (what the
trace holds, by ``trace_reduce.describe``). Chip only. The test beside the
fixture pins the numbers ``trace_reduce`` gets from it against numbers worked
out by hand from ``describe.json``.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce
    from benchmark.run import WINDOW_MARK
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

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_fixture: chip only", file=sys.stderr)
        return 1
    mesh = build_mesh(MeshSpec(data=len(devices)), devices=devices)
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
    gb = 2 * len(devices)
    batch = shard_batch({
        "input_ids": np.random.default_rng(0).integers(
            0, 2048, (gb, 1024)).astype(np.int32),
        "weight": np.ones(gb, np.float32)}, mesh)
    key = jax.random.PRNGKey(0)
    for _ in range(2):   # compile, settle
        state, metrics = trainer._train_step(state, batch, key)
    float(metrics["weight"])

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(out / "raw"), profiler_options=options)
    with jax.profiler.TraceAnnotation(WINDOW_MARK):
        for _ in range(args.steps):
            state, metrics = trainer._train_step(state, batch, key)
        float(metrics["weight"])
    jax.profiler.stop_trace()

    path = trace_reduce.newest_xplane(out / "raw")
    packed = out / "trace.xplane.pb.gz"
    packed.write_bytes(gzip.compress(path.read_bytes(), 9))
    (out / "describe.json").write_text(
        json.dumps(trace_reduce.describe(path), indent=1, default=str))
    trace = trace_reduce.load_xplane(packed)
    print(json.dumps({
        "raw_bytes": path.stat().st_size, "gz_bytes": packed.stat().st_size,
        "devices": sorted(trace.devices), "steps": args.steps,
        "busy_idle": trace.busy_idle(WINDOW_MARK),
        "exposed_collective": trace.exposed_collective(WINDOW_MARK),
        "top_ops": trace.top_ops(WINDOW_MARK, 10)}, indent=1))
    import shutil

    shutil.rmtree(out / "raw", ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""``fit_check`` for the cells of ``drivers/train_lm.py``: compile a cell's
train step at real widths for a v5e that is described, not attached, with the
configuration's ``model_overrides``, and print ``memory_analysis()`` for each
(sequences per chip, remat) asked for. This is how a cell's ``per_chip_batch``
and ``remat`` are chosen without chip time; its rows are in PERF.md.

    JAX_PLATFORMS=cpu python -m benchmark.tools.fit_check_lm \
        --workload train_qwen3_next_s8192_1chip [--batches 1,2] [--remat 0,1]
        [--seq-len 8192]

Nothing runs and nothing is timed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

from benchmark.tools.fit_check import _mem, topology


def train_step_memory(topo, config: dict, traffic: dict, chips: int,
                      per_chip_batch: int, remat: bool, seq_len: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.ops import make_flash_attention_fn
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.parallel.sharding import batch_spec
    from distributed_pytorch_training_tpu.training.loop import (
        TrainConfig, Trainer,
    )
    from distributed_pytorch_training_tpu.training.optim import (
        make_optimizer, make_schedule,
    )
    from distributed_pytorch_training_tpu.training.tasks import (
        LanguageModelingTask,
    )

    fa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")
    fa._interpret = lambda: False      # the described chip runs Mosaic

    job = config["job"]
    mesh = build_mesh(MeshSpec(data=chips), devices=list(topo.devices)[:chips])
    model = get_model(
        config["registry_model"], dtype=jnp.bfloat16, remat=remat,
        attention_fn=make_flash_attention_fn(causal=True, mesh=mesh),
        **config.get("model_overrides", {}))
    tx = make_optimizer(job["optimizer"],
                        make_schedule(job["schedule"], job["lr"]),
                        weight_decay=job["weight_decay"])
    trainer = Trainer(LanguageModelingTask(compute_dtype=jnp.bfloat16), mesh,
                      TrainConfig(per_device_batch=per_chip_batch, bf16=True),
                      rules=type(model).partition_rules())
    sample = np.zeros((1, seq_len), np.int32)
    rep = NamedSharding(mesh, P())
    state = jax.eval_shape(
        lambda key: trainer.init_state(model, sample, tx, key),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state)
    gb = per_chip_batch * chips
    batch = {
        "input_ids": jax.ShapeDtypeStruct(
            (gb, seq_len), jnp.int32,
            sharding=NamedSharding(mesh, batch_spec(2))),
        "weight": jax.ShapeDtypeStruct(
            (gb,), jnp.float32, sharding=NamedSharding(mesh, batch_spec(1))),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    t0 = time.perf_counter()
    compiled = trainer._train_step.lower(state, batch, key).compile()
    text = compiled.as_text()
    return {"program": "train_step", "chips": chips, "seq_len": seq_len,
            "per_chip_batch": per_chip_batch, "remat": remat,
            "compile_s": round(time.perf_counter() - t0, 1),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            **_mem(compiled)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", default="1,2")
    ap.add_argument("--remat", default="0,1")
    ap.add_argument("--seq-len", type=int, default=None)
    args = ap.parse_args(argv)

    from benchmark.run import load_cell

    _, cell, config, traffic = load_cell(args.workload, rehearsal=False)
    seq_len = args.seq_len or int(traffic["seq_len"])
    topo = topology()
    for remat in (bool(int(x)) for x in args.remat.split(",")):
        for b in (int(x) for x in args.batches.split(",")):
            try:
                row = train_step_memory(topo, config, traffic, cell["chips"],
                                        b, remat, seq_len)
            except Exception as e:  # noqa: BLE001 — "does not fit" is a row
                row = {"program": "train_step", "per_chip_batch": b,
                       "remat": remat, "seq_len": seq_len,
                       "refused": str(e).splitlines()[0][:300]}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

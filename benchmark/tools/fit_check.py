"""Compile each cell's programs at real widths for a v5e that is described,
not attached (the on-chip-measurement guide's third rehearsal), and print
``memory_analysis()``. This is how the train cells' batch and the serve
cells' ``rows`` are chosen without chip time; its output is in PERF.md.

    JAX_PLATFORMS=cpu python -m benchmark.tools.fit_check [--only train|serve]
        [--batches 6,8,10] [--rows 64,96,128]

Nothing runs and nothing is timed. The sizes come from the configuration and
traffic files; the program's own builders make the programs (``Trainer``'s
jitted step, ``SlotEngine.lower_paged_*``), handed the described devices.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

GB = 1e9


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: getattr(m, k) / GB for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    # what the program holds at its peak: arguments + temporaries, plus
    # the outputs that are not written over donated arguments
    out["peak_gb"] = (out["argument_size_in_bytes"] + out["temp_size_in_bytes"]
                      + out["output_size_in_bytes"]
                      - out["alias_size_in_bytes"])
    return {k.replace("_size_in_bytes", "_gb"): round(v, 3)
            for k, v in out.items()}


def topology():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def train_step_memory(topo, config: dict, traffic: dict, chips: int,
                      per_chip_batch: int) -> dict:
    """The default train step of the cell, compiled for ``chips`` described
    devices at ``per_chip_batch``."""
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
    import importlib

    fa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")
    fa._interpret = lambda: False      # the described chip runs Mosaic

    job = config["job"]
    mesh = build_mesh(MeshSpec(data=chips), devices=list(topo.devices)[:chips])
    seq_len = int(traffic["seq_len"])
    model = get_model(config["registry_model"], dtype=jnp.bfloat16,
                      attention_fn=make_flash_attention_fn(causal=True,
                                                           mesh=mesh))
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
    return {"program": "train_step", "chips": chips,
            "per_chip_batch": per_chip_batch,
            "compile_s": round(time.perf_counter() - t0, 1),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "all_reduces": text.count(" all-reduce("),
            "all_reduce_starts": text.count(" all-reduce-start("),
            **_mem(compiled)}


def serve_program_memory(topo, config: dict, rows: int,
                         programs=("paged_decode", "paged_prefill")) -> list:
    """``paged_decode`` and the largest bucket's ``paged_prefill`` at
    ``rows`` slots. The pool is an argument of both, so ``argument_gb`` is
    weights + pool and ``temp_gb`` the step's temporaries."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.serving import continuous
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )

    job = config["job"]
    mesh = build_mesh(MeshSpec(data=1), devices=list(topo.devices)[:1])
    dtype = jnp.bfloat16 if job["serve_dtype"] == "bf16" else jnp.float32
    model = get_model(config["registry_model"], dtype=dtype)
    cfg = PagedServeConfig(
        buckets=tuple(job["buckets"]), rows=rows,
        max_new_tokens=int(job["max_new_tokens"]),
        serve_dtype=job["serve_dtype"], page_size=int(job["page_size"]),
        kv_dtype=job["kv_dtype"], prefix_skip=bool(job["prefix_skip"]))
    sample = np.zeros((1, min(cfg.buckets)), np.int32)
    params = jax.eval_shape(
        lambda key: model.init(key, sample, train=False)["params"],
        jax.ShapeDtypeStruct((2,), jnp.uint32))

    class Described(continuous.SlotEngine):
        """The engine with shapes for state: a described device holds no
        array, and lowering needs only the avals."""

        def reset_state(self):
            c = self.config
            pool = jax.eval_shape(lambda: self.model.init_paged_pool(
                c.total_pages, c.page_size, quantized=c.kv_dtype == "int8"))
            self._pool = pool
            self._control = jax.eval_shape(self._init_control)
            self._page_table = np.zeros((c.rows, c.pages_per_slot), np.int32)

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    # InferenceEngine.__init__ converts and device_puts the weights; while
    # it is built, let shapes pass through both calls untouched
    real_put, real_asarray = jax.device_put, jnp.asarray
    jax.device_put = lambda x, *a, **k: x
    jnp.asarray = lambda x, *a, **k: x if isinstance(
        x, jax.ShapeDtypeStruct) else real_asarray(x, *a, **k)
    try:
        engine = Described(model, mesh, cfg, jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            params))
    finally:
        jax.device_put, jnp.asarray = real_put, real_asarray
    lower = {"paged_decode": engine.lower_paged_decode,
             "paged_prefill": lambda: engine.lower_paged_prefill(
                 max(cfg.buckets))}
    out = []
    for name in programs:
        t0 = time.perf_counter()
        compiled = lower[name]().compile()
        out.append({"program": name, "rows": rows,
                    "pool_gb": round(continuous.paged_kv_bytes(
                        engine._pool) / GB, 3),
                    "compile_s": round(time.perf_counter() - t0, 1),
                    **_mem(compiled)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", choices=("train", "serve"))
    ap.add_argument("--batches", default="6,8,10")
    ap.add_argument("--rows", default="64,96,128")
    ap.add_argument("--chips", default="1,4")
    args = ap.parse_args(argv)

    from benchmark.run import BENCH_DIR

    topo = topology()
    load = lambda p: json.loads((BENCH_DIR / p).read_text())  # noqa: E731
    if args.only != "serve":
        cfg = load("configs/gpt2_355m.json")
        mix = load("traffic/pretrain_s1024_1chip.json")
        for chips in (int(c) for c in args.chips.split(",")):
            for b in (int(x) for x in args.batches.split(",")):
                try:
                    row = train_step_memory(topo, cfg, mix, chips, b)
                except Exception as e:  # noqa: BLE001 — "does not fit" is a row
                    row = {"program": "train_step", "chips": chips,
                           "per_chip_batch": b,
                           "refused": str(e).splitlines()[0][:300]}
                print(json.dumps(row), flush=True)
    if args.only != "train":
        cfg = load("configs/gpt2_124m.json")
        for rows in (int(x) for x in args.rows.split(",")):
            try:
                for row in serve_program_memory(topo, cfg, rows):
                    print(json.dumps(row), flush=True)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"program": "serve", "rows": rows,
                                  "refused": str(e).splitlines()[0][:300]}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

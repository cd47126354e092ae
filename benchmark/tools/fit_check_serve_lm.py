"""`fit_check`'s serve half for a family configuration with
``model_overrides`` (``fit_check.serve_program_memory`` builds the model from
its registry name alone): compile ``paged_decode`` and the largest bucket's
``paged_prefill`` of a serve cell for a described v5e, at each ``--rows``,
and print arguments / temporaries / peak. This is how the cell's ``rows`` and
page size are sized without chip time; its rows are in PERF.md section 4.

    JAX_PLATFORMS=cpu python -m benchmark.tools.fit_check_serve_lm \
        --workload serve_deepseek_v2_long_prompt_batch --rows 96,112,128 \
        [--page-size 16]

Nothing runs. The tool's process sees the CPU, so the choices the program
makes by observing its backend are steered to what it makes on the chip: the
kernel read of the decode step, the flash kernel for the prefill's expanded
attention, Mosaic instead of the interpreter.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time


def serve_program_memory(topo, config: dict, rows: int, page_size=None,
                         programs=("paged_decode", "paged_prefill")) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.tools.fit_check import GB, _mem
    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.models.layers import paged_kv_bytes
    from distributed_pytorch_training_tpu.ops import make_flash_attention_fn
    from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
    from distributed_pytorch_training_tpu.serving import continuous
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )

    for name in ("flash_attention", "mla_paged_attention", "paged_attention"):
        importlib.import_module(
            f"distributed_pytorch_training_tpu.ops.{name}"
        )._interpret = lambda: False     # the described chip runs Mosaic
    continuous.paged_attention_backend_supported = lambda: True

    job = config["job"]
    mesh = build_mesh(MeshSpec(data=1), devices=list(topo.devices)[:1])
    dtype = jnp.bfloat16 if job["serve_dtype"] == "bf16" else jnp.float32
    model = get_model(config["registry_model"], dtype=dtype,
                      attention_fn=make_flash_attention_fn(causal=True),
                      **config.get("model_overrides", {}))
    cfg = PagedServeConfig(
        buckets=tuple(job["buckets"]), rows=rows,
        max_new_tokens=int(job["max_new_tokens"]),
        serve_dtype=job["serve_dtype"],
        page_size=int(page_size or job["page_size"]),
        kv_dtype=job["kv_dtype"], prefix_skip=bool(job["prefix_skip"]))
    params = jax.eval_shape(
        lambda key: model.init(key, np.zeros((1, 8), np.int32),
                               train=False)["params"],
        jax.ShapeDtypeStruct((2,), jnp.uint32))

    class Described(continuous.SlotEngine):
        """The engine with shapes for state (`fit_check`'s)."""

        def reset_state(self):
            c = self.config
            self._pool = jax.eval_shape(lambda: self.model.init_paged_pool(
                c.total_pages, c.page_size, quantized=c.kv_dtype == "int8"))
            self._control = jax.eval_shape(self._init_control)
            self._page_table = np.zeros((c.rows, c.pages_per_slot), np.int32)

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
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
                    "page_size": cfg.page_size, "kv_path": engine.kv_path,
                    "pool_gb": round(paged_kv_bytes(engine._pool) / GB, 3),
                    "compile_s": round(time.perf_counter() - t0, 1),
                    "tpu_custom_calls": compiled.as_text().count(
                        "tpu_custom_call"),
                    **_mem(compiled)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", default="96,112,128")
    ap.add_argument("--page-size", type=int, default=None)
    args = ap.parse_args(argv)

    from benchmark.run import load_cell
    from benchmark.tools.fit_check import topology

    _, _, config, _ = load_cell(args.workload, rehearsal=False)
    topo = topology()
    for rows in (int(x) for x in args.rows.split(",")):
        try:
            for row in serve_program_memory(topo, config, rows,
                                            args.page_size):
                print(json.dumps(row), flush=True)
        except Exception as e:  # noqa: BLE001 — "does not fit" is a row
            print(json.dumps({"program": "serve", "rows": rows,
                              "refused": str(e).splitlines()[0][:300]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

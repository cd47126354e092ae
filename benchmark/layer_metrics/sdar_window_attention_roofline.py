"""The window read's share of its roofline: the least time the chip could
take for the `paged_attention` calls of one block step (per call the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, by
benchmark/flops/<family> from the published sizes and the committed positions
a step's rows really held: the scheduler's ``serving_live_cache_tokens`` over
its ``serving_block_steps``; every committed row is counted once a slot row,
not once a query position, and the products at their useful size, not the
block-diagonal tile's) over the device time of those calls, read by the
kernel's NAME (a step holds the grouped products' custom calls too), median
over the steps traced. Bytes bound it: 4 query positions of 8 query heads a
key head are 32 FLOPs a byte against the chip's 240."""

import importlib

from benchmark.layer_metrics import _regions, _sdar_regions
from benchmark.layer_metrics._sdar_regions import counted


def read(run):
    shape = run.facts.get("serve_shape")
    steps = counted(run, "serving_block_steps")
    live = counted(run, "serving_live_cache_tokens")
    if run.peaks is None or not shape or not steps or not live:
        return None
    kernel_ms = _regions.read(run, _sdar_regions.SDAR_BLOCK_STEP,
                              (_sdar_regions.WINDOW_KERNEL,))
    if kernel_ms is None:
        return None
    flops = importlib.import_module(f"benchmark.flops.{shape['family']}")
    cost = flops.window_attention_call_cost(
        run.config["published"], shape["rows"], shape["window"], live / steps)
    least = max(cost["flops"] / (run.peaks["bf16_tflops"] * 1e12),
                cost["bytes"] / (run.peaks["hbm_gb_per_s"] * 1e9))
    return 100.0 * least * shape["layers"] / (kernel_ms / 1e3)

"""The absorbed decode read's share of its roofline: the least time the chip
could take for the `mla_paged_attention` calls of one decode step (per call
the larger of FLOPs over the bf16 peak and bytes over the HBM peak, by
benchmark/flops/<family> from the published sizes and the cached positions a
step's rows really held: the scheduler's ``serving_live_cache_tokens`` over
its ``serving_decode_steps``) over the device time of those calls, read by the
kernel's NAME (not every custom call: a decode step holds the grouped
products' too), median over the steps traced. At 128 heads the read has 242
FLOPs a byte against the chip's 240: both bounds at once."""

import importlib

from benchmark.layer_metrics import _dsv2_regions, _regions


def _count(run, name: str) -> float:
    return sum(float(e["value"]) for e in run.events
               if e.get("kind") == "counter" and e.get("name") == name)


def read(run):
    shape = run.facts.get("serve_shape")
    steps = _count(run, "serving_decode_steps")
    live = _count(run, "serving_live_cache_tokens")
    if run.peaks is None or not shape or not steps or not live:
        return None
    kernel_ms = _regions.read(run, _dsv2_regions.DSV2_DECODE,
                              (_dsv2_regions.MLA_KERNEL,))
    if kernel_ms is None:
        return None
    flops = importlib.import_module(f"benchmark.flops.{shape['family']}")
    cost = flops.mla_decode_call_cost(run.config["published"], shape["rows"],
                                      live / steps)
    least = max(cost["flops"] / (run.peaks["bf16_tflops"] * 1e12),
                cost["bytes"] / (run.peaks["hbm_gb_per_s"] * 1e9))
    return 100.0 * least * shape["layers"] / (kernel_ms / 1e3)

"""Median `slot_fetch` span of a completion (`engine.fetch_slot`: two slices
and the fetch of the tokens and the kept logits), saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.span_median_ms(run, "slot_fetch")

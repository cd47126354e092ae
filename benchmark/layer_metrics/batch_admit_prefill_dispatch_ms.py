"""Median `prefill` span of an admission (`engine.admit`: the key, seven
puts and the dispatch), saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.span_median_ms(run, "prefill")

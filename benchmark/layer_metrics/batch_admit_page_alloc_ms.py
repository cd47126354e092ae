"""Median `page_alloc` span of an admission (`PagePool.alloc` that gave a
lease), saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.span_median_ms(run, "page_alloc", ok=True)

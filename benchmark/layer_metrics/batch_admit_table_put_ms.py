"""Median `page_table_put` span of an admission (`set_page_row`: the whole
page table put on the device), saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.span_median_ms(run, "page_table_put", at="admit")

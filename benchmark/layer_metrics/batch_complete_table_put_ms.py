"""Median `page_table_put` span of a completion (`set_page_row` with the
freed slot's row zeroed: the whole page table put on the device again),
saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.span_median_ms(run, "page_table_put", at="complete")

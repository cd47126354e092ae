"""Share of the window the train loop spent blocked on the loader: the sum
of its ``data_wait`` spans over the window."""

from benchmark.layer_metrics._shared import span_share_pct


def read(run):
    return span_share_pct(run, "data_wait")

"""Time-weighted mean of the ``serving_kv_live_page_share`` gauge, saturated
cell: the share of the page table that is leased, which is what the kernel
read of the decode step touches (the work count beside
``batch_decode_kv_gather_ms``)."""

from benchmark.layer_metrics._shared import gauge_mean_pct


def read(run):
    return gauge_mean_pct(run, "serving_kv_live_page_share")

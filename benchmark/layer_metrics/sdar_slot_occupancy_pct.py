"""Time-weighted mean of the ``serving_slot_occupancy`` gauge,
block-diffusion cell."""

from benchmark.layer_metrics._shared import gauge_mean_pct


def read(run):
    return gauge_mean_pct(run, "serving_slot_occupancy")

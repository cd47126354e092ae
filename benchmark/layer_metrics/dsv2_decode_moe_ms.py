"""Device time of one decode step in the feed-forward half: router, dispatch,
the grouped products over the held experts (XLA's ``ragged-dot`` calls by
name), the shared experts and the leading dense MLP."""

from benchmark.layer_metrics import _dsv2_regions, _regions


def read(run):
    return _regions.read(run, _dsv2_regions.DSV2_DECODE, _dsv2_regions.MOE)

"""Device time of one block step in the expert layers: the router and the
top-8, the sort and the gathers, the grouped products over all 128 experts."""

from benchmark.layer_metrics import _regions, _sdar_regions


def read(run):
    return _regions.read(run, _sdar_regions.SDAR_BLOCK_STEP,
                         _sdar_regions.MOE)

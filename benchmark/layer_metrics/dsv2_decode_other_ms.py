"""Device time of one decode step outside attention and the feed-forward
half: the pool's scatter, embedding, head, sampler, bookkeeping, what the
model leaves under no scope of its own, and operations under no path."""

from benchmark.layer_metrics import _dsv2_regions, _regions


def read(run):
    return _regions.read(run, _dsv2_regions.DSV2_DECODE, _dsv2_regions.OTHER)

"""Device time of one block step outside attention, the experts and the
head: the committed blocks' scatter, the embedding, bookkeeping, what the
model leaves under no scope of its own, and operations under no path."""

from benchmark.layer_metrics import _regions, _sdar_regions


def read(run):
    return _regions.read(run, _sdar_regions.SDAR_BLOCK_STEP,
                         _sdar_regions.OTHER)

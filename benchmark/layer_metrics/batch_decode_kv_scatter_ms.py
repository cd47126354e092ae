"""Device time of one paged decode step under `kv_scatter`: the fresh rows
taken from the model's new cache and written back to the pool."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.PAGED_DECODE, ("kv_scatter",))

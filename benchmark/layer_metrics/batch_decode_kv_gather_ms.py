"""Device time of one paged decode step under `kv_gather`: the dense view
built from the pages, and its per-layer slices."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.PAGED_DECODE, ("kv_gather",))

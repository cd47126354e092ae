"""Device time of one paged decode step under `model`: the model's `apply`."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.PAGED_DECODE, ("model",))

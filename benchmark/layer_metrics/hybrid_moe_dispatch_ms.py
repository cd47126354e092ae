"""Device time of one train step under `moe_dispatch`: the held assignments'
rows gathered in and the weighted rows added back."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP, ("moe_dispatch",))

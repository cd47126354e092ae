"""Device time of one train step under `moe_route`: the router's product,
softmax, top-k, the sort of the assignments and the group sizes."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP, ("moe_route",))

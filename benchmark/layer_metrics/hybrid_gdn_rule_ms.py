"""Device time of one train step under `gdn_rule`, forward and backward: the
chunked gated delta rule alone (the in-chunk triangular solve, the scores,
the scan over chunks that carries the state)."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP, ("gdn_rule",))

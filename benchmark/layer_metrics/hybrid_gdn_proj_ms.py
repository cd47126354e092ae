"""Device time of one train step under `gdn` outside `gdn_rule`: the Gated
DeltaNet mixers' projections, convolution, normalisations and gates, with
the fused AdamW epilogue of their own gradients."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP, ("gdn",))

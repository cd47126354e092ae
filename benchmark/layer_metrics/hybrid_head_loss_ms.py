"""Device time of one train step under `embed`, `final_norm`, `head` and
`loss`: the vocabulary-wide work, with the fused AdamW epilogue of the two
tables' gradients."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP, ("embed", "final_norm", "head", "loss"))

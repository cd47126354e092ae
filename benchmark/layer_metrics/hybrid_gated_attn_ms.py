"""Device time of one train step under `gated_attn`: the full-attention
layer's projections, QK-norm, rotary, the flash kernels and the output gate."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP, ("gated_attn",))

"""Device time of one train step in the grouped products over the held
experts (XLA's ``ragged-dot`` calls, forward and transposes, and what else
lies under `moe_experts`) and under `shared_expert`."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import (
    GROUPED_PRODUCT, HYBRID_TRAIN_STEP,
)


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP,
                         ("moe_experts", "shared_expert", *GROUPED_PRODUCT))

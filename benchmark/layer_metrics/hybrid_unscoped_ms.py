"""Device time of one train step under no region (the layers' norms and
residuals, what XLA left without a scope path) and in collectives. With the
other `hybrid_*_ms` and `train_optimizer_ms` (scope `optimizer`: the experts'
AdamW has instructions of its own here) it sums to the step's busy time."""

from benchmark.layer_metrics import _regions
from benchmark.layer_metrics._hybrid_regions import HYBRID_TRAIN_STEP


def read(run):
    return _regions.read(run, HYBRID_TRAIN_STEP,
                         (_regions.UNSCOPED, _regions.COLLECTIVE))

"""Device time of one train step under none of the train step's regions:
the collectives (the all-reduces on four chips, whatever path they carry),
the blocks' LayerNorms and residuals, and what XLA left without a scope
path. With `train_attention_ms`, `train_mlp_ms`, `train_head_loss_ms` and
the `optimizer` region it sums to the step's busy time."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.TRAIN_STEP,
                         (_regions.UNSCOPED, _regions.COLLECTIVE))

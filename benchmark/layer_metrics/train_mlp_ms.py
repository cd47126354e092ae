"""Device time of one train step under the blocks' `mlp` scope, forward and
backward; the fused AdamW epilogue of its weight gradients included, and no
collective (see `train_attention_ms`)."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.TRAIN_STEP, ("mlp",))

"""Device time of one train step under the blocks' `attn` scope, forward and
backward: the projections and the flash kernels. A fused instruction has one
path: where XLA fuses a parameter's AdamW update into the matmul that makes
its gradient (one chip), that update is counted here, not under `optimizer`.
Collectives are not (`train_unscoped_ms`)."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.TRAIN_STEP, ("attn",))

"""Device time of one train step under `optimizer` (`tx.update` and
`apply_updates`): the optimizer's instructions of their OWN. Registered for
the four-chip cell, where an all-reduce stands between a gradient and its
update; on one chip XLA fuses each update into the fusion that produces the
gradient, this reads ~0.02 ms and the update's time is in the gradient's
region."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.TRAIN_STEP, ("optimizer",))

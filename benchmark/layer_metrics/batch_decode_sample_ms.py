"""Device time of one paged decode step under `sample`: the sampler over the
(rows, vocabulary) logits."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.PAGED_DECODE, ("sample",))

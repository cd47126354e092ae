"""Device time of one paged decode step under `bookkeeping` (the `out_buf`
and `control` updates) or under none of the decode step's regions. With the
other four `batch_decode_*_ms` it sums to the step's busy time."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.PAGED_DECODE,
                         (_regions.UNSCOPED, "bookkeeping",
                          _regions.COLLECTIVE))

"""Device time of one block step in attention: the four projections, the
q/k norms, the rotary and the window read (the `paged_attention` kernel
calls, or the gather read's views and softmax)."""

from benchmark.layer_metrics import _regions, _sdar_regions


def read(run):
    return _regions.read(run, _sdar_regions.SDAR_BLOCK_STEP,
                         _sdar_regions.ATTN)

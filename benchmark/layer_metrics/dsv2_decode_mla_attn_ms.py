"""Device time of one decode step in the absorbed attention read: the
`mla_paged_attention` kernel calls (or the gather read's views and masked
softmax) under `mla_attn`."""

from benchmark.layer_metrics import _dsv2_regions, _regions


def read(run):
    return _regions.read(run, _dsv2_regions.DSV2_DECODE, _dsv2_regions.ATTN)

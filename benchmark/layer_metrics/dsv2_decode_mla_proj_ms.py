"""Device time of one decode step under `mla_proj`: the down- and
up-projections, norms, rotary, the absorption of W_uk and W_uv, W_o."""

from benchmark.layer_metrics import _dsv2_regions, _regions


def read(run):
    return _regions.read(run, _dsv2_regions.DSV2_DECODE, _dsv2_regions.PROJ)

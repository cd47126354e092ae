"""Device time of the flash forward kernel calls (`flash_fwd`, keys of 192 and
values of 128) of one prefill program, median over the prefills traced."""

from benchmark.layer_metrics import _dsv2_regions, _regions


def read(run):
    return _regions.read(run, _dsv2_regions.DSV2_PREFILL, ("flash_fwd",))

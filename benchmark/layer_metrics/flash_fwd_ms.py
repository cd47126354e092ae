"""Device time of the forward flash kernel calls (`flash_fwd`) of one train
step."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.FLASH_KERNELS, ("flash_fwd",))

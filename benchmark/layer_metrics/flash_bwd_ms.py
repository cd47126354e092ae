"""Device time of the two backward flash kernels' calls (`flash_bwd_dkv`,
`flash_bwd_dq`) of one train step."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.FLASH_KERNELS, ("flash_bwd_dkv", "flash_bwd_dq"))

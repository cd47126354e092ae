"""Device time of one block step from the last layer's output to the choice:
the final norm, the head over B positions a row, the confidences and which
masked positions take their argmax."""

from benchmark.layer_metrics import _regions, _sdar_regions


def read(run):
    return _regions.read(run, _sdar_regions.SDAR_BLOCK_STEP,
                         _sdar_regions.HEAD_UNMASK)

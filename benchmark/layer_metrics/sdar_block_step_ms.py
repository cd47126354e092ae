"""Median device time of one block step (`serving/block_diffusion.py`: every
live row forwards its window; committing rows write their block)."""

from benchmark import stats
from benchmark.run import WINDOW_MARK


def read(run):
    trace = run.trace_data
    if trace is None or not trace.devices:
        return None
    med = stats.median(trace.module_times(r"jit_block_step\b", WINDOW_MARK))
    return None if med is None else med * 1e3

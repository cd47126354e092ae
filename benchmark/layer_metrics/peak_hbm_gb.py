"""Peak bytes on the fullest chip after the window, in GB (1e9): the
harness's ``memory_peak_bytes`` (held arrays plus reserved program
temporaries, both as the runtime reports them)."""

from benchmark.run import memory_peak_bytes


def read(run):
    peak = memory_peak_bytes(run)
    return peak / 1e9 if peak else None

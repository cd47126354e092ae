"""Share of the traced window in which the chip ran no operation and the
scheduler was inside no phase span (between iterations, the lock's hand-off,
the interpreter lost to another thread), saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.idle_share_pct(run, _sched.UNATTRIBUTED)

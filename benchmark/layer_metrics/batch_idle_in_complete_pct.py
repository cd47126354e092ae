"""Share of the traced window in which the chip ran no operation while the
scheduler was inside `sched_complete`, saturated cell."""

from benchmark.layer_metrics import _sched


def read(run):
    return _sched.idle_share_pct(run, "sched_complete")

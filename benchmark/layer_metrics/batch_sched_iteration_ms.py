"""Median wall time of one scheduler iteration that advanced (`sched_pull`'s
start to `sched_complete`'s end), saturated cell: the host's twin of
``batch_decode_step_ms``."""

from benchmark.layer_metrics._sched import iteration_ms as read  # noqa: F401

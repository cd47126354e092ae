"""Median wall time of one scheduler iteration that advanced (`sched_pull`'s
start to `sched_complete`'s end), steady cell: its gap to ``decode_step_ms``
is the host's part of a token."""

from benchmark.layer_metrics._sched import iteration_ms as read  # noqa: F401

"""Idle share of the idlest chip over the traced train steps."""

from benchmark.layer_metrics._shared import device_idle_pct as read  # noqa: F401

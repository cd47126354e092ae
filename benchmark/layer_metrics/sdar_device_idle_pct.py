"""Idle share of the chip over the traced part of the block-diffusion serve
window."""

from benchmark.layer_metrics._shared import device_idle_pct as read  # noqa: F401

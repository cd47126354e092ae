"""Median device time of one paged decode step, latent-attention cell."""

from benchmark.layer_metrics._shared import decode_step_ms as read  # noqa: F401

"""99th percentile of the inter-token gap as the scheduler's fence sees it
(`_sched.itl_p99_ms`), steady cell."""

from benchmark.layer_metrics._sched import itl_p99_ms as read  # noqa: F401

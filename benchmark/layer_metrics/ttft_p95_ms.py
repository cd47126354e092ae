"""95th percentile, over the requests due in the window, of first-token fence
minus the time the request was DUE. Per-layer and not end-to-end for now: at
2 requests/s a 30 s window holds ~60 requests, and over runs of the same code
the p95 spread 5.3% (PERF.md section 6), wider than half of any bound a
benchmark may set. It hangs on the same decode step and fence cadence as
``tpot_p95_ms``, the metric it is filed under."""


def read(run):
    return (run.facts.get("summary") or {}).get("ttft_ms", {}).get("p95")

"""Share of the window the scheduler's thread spent dispatching prefills
(its ``prefill`` spans), block-diffusion cell."""

from benchmark.layer_metrics._shared import span_share_pct


def read(run):
    return span_share_pct(run, "prefill")

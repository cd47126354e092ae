"""Device time of the prefill programs in the traced window over the
thousands of bucket tokens they ran: the sum of the executions' times on the
``XLA Modules`` lane, over their count times the mean bucket of the window's
``prefill`` spans (a span says which bucket an admission took; the trace
does not)."""

from benchmark.run import WINDOW_MARK


def read(run):
    trace = run.trace_data
    if trace is None or not trace.devices:
        return None
    times = trace.module_times(r"jit_prefill\b", WINDOW_MARK)
    buckets = [e["bucket"] for e in run.events
               if e.get("kind") == "span" and e.get("name") == "prefill"
               and "bucket" in e]
    if not times or not buckets:
        return None
    ktokens = len(times) * (sum(buckets) / len(buckets)) / 1e3
    return sum(times) * 1e3 / ktokens

"""Collective wall time on which no compute operation overlaps on that chip,
over the traced window, on the worst chip. On one chip there is no
collective and the reader returns nothing."""

from benchmark.run import WINDOW_MARK


def read(run):
    trace = run.trace_data
    if trace is None or not trace.devices or run.cell["chips"] < 2:
        return None
    return max(d["exposed_pct"]
               for d in trace.exposed_collective(WINDOW_MARK).values())

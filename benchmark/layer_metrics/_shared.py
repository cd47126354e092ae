"""What several readers share. A reader is ``read(run) -> float | None``:
``run`` is the harness's `benchmark.run.Run` after the traced run (telemetry
events, the reduced trace, the driver's facts, the compile meter, the peaks
row). None means there was nothing to read, and the metric is left out."""

from __future__ import annotations

from benchmark import stats
from benchmark.run import WINDOW_MARK


def device_idle_pct(run):
    """100 x (1 - union of operation intervals / traced window), on the
    idlest device."""
    trace = run.trace_data
    if trace is None or not trace.devices:
        return None
    return max(b["idle_pct"] for b in trace.busy_idle(WINDOW_MARK).values())


def span_share_pct(run, name: str):
    """Seconds of the window covered by the program's ``name`` spans, over
    the window, in percent."""
    t0, t1 = run.window
    spans = [(e["t0"], e["dur_ms"] / 1e3) for e in run.events
             if e.get("kind") == "span" and e.get("name") == name]
    if not spans or t1 <= t0:
        return None
    return 100.0 * stats.spans_in_window(spans, t0, t1) / (t1 - t0)


def gauge_mean_pct(run, name: str):
    """Time-weighted mean over the window of a 0..1 gauge, in percent."""
    t0, t1 = run.window
    samples = [(e["ts"], float(e["value"])) for e in run.events
               if e.get("kind") == "gauge" and e.get("name") == name]
    mean = stats.time_weighted_mean(samples, t0, t1)
    return None if mean is None else 100.0 * mean


def decode_step_ms(run):
    """Median device time of one execution of the paged decode program."""
    trace = run.trace_data
    if trace is None or not trace.devices:
        return None
    times = trace.module_times(r"jit_decode\b", WINDOW_MARK)
    med = stats.median(times)
    return None if med is None else med * 1e3

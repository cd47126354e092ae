"""The arithmetic behind every reported number: percentiles, medians,
time-weighted means, open-loop timing from the due time."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default rule), q in [0, 100].
    None for an empty sample: a metric with nothing to read is left out."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def time_weighted_mean(samples: Iterable[Tuple[float, float]],
                       t_start: float, t_end: float) -> Optional[float]:
    """Mean over [t_start, t_end] of a gauge given as (time, value) samples,
    each value holding until the next sample. The last sample at or before
    t_start sets the value the window opens with."""
    pts = sorted(samples)
    if not pts or t_end <= t_start:
        return None
    current = None
    area = 0.0
    covered = 0.0
    t = t_start
    for ts, value in pts:
        if ts <= t_start:
            current = value
            continue
        if ts >= t_end:
            break
        if current is not None:
            area += current * (ts - t)
            covered += ts - t
        t, current = ts, value
    if current is not None:
        area += current * (t_end - t)
        covered += t_end - t
    return area / covered if covered > 0 else None


def open_loop_latency(due: float, sent: float, wait_after_sent: float
                      ) -> Tuple[float, float]:
    """(latency counted from when the request was DUE, generator lateness).
    A request the generator sent late still waited from its due time, so the
    lateness is part of what its user saw."""
    late = max(0.0, sent - due)
    return late + wait_after_sent, late


def spans_in_window(spans: Iterable[Tuple[float, float]], t_start: float,
                    t_end: float) -> float:
    """Seconds of [t_start, t_end] covered by the (start, duration) spans,
    each clipped to the window. Spans of one thread do not overlap, so the
    sum is the covered time."""
    total = 0.0
    for t0, dur in spans:
        a, b = max(t0, t_start), min(t0 + dur, t_end)
        if b > a:
            total += b - a
    return total


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's measure
    of how much a metric varies between runs."""
    med = median(values)
    if med is None or med == 0:
        return None
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(med)


def summarize(values: List[float]) -> dict:
    return {"n": len(values), "p50": percentile(values, 50.0),
            "p95": percentile(values, 95.0), "p99": percentile(values, 99.0),
            "max": max(values) if values else None}

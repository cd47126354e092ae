"""The token server's scheduler iteration, as the program's spans say it,
and the device's idle time laid under it.

`ContinuousScheduler.step` (``serving/continuous.py``) emits five phase spans
that tile one iteration under its lock, each starting where the one before it
ends: ``sched_pull``, ``sched_admit``, ``sched_dispatch``, ``sched_fence``,
``sched_complete``, all carrying the iteration's serial number ``iter`` (an
iteration with nothing running has the first two only; an idle poll none).
Inside ``sched_admit`` lie one ``page_alloc``, one ``page_table_put``
(``at="admit"``) and one ``prefill`` (or ``prefill_skip``) an admission;
inside ``sched_complete`` one ``slot_fetch`` and one ``page_table_put``
(``at="complete"``) a completion. The host-side readers take spans that START
inside ``run.window``; the idle readers take the traced part of it.

The idle split joins two clocks: spans are on ``time.time()``, the trace has
its own zero, and `benchmark.run.Run.profile` joins them at the window's
annotation (``run.trace_wall_offset_s``). That join is CHECKED in every traced
run, not repaired: a ``sched_fence`` blocks on the output of the last program
its iteration dispatched, so it cannot end before that program does. For every
fence that ends inside the traced window `fence_lags` gives its end minus the
end of the program it falls into (negative: it seems to return while the device
still works, so the clocks disagree) or of the last program before it (the
runtime's wake-up latency after a fence, as this join reads it). A 1st
percentile under `-FENCE_EARLY_S`, no fence in the traced window, or six
shares that do not sum to the device's idle share within `RESIDUE_LIMIT_PCT`
of the window (each is intersected on its own, as `_regions.split` holds
regions to the busy time: phases that overlap, two schedulers in one process,
would count a gap twice): the six idle readers return None instead of a wrong
number. The ``note`` line gives the lags' median, 1st percentile and count,
the six shares and the residue either way. (Measured, PERF.md section 6,
PR 40: the harness's join puts the spans 1.5-2.3 ms late in five of nine
traced batch runs; a join that passes is still only known to the least fence
lag plus the least launch latency, 0.7-1.0 ms.)

On a program without the spans (the parent of the PR that added them) every
reader here finds nothing and returns None.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import stats, trace_reduce
from benchmark.run import WINDOW_MARK

PHASES = ("sched_pull", "sched_admit", "sched_dispatch", "sched_fence",
          "sched_complete")
UNATTRIBUTED = "unattributed"
FENCE_EARLY_S = 50e-6  # a fence this far inside its program: the join is off
RESIDUE_LIMIT_PCT = 1.0

Interval = Tuple[float, float]          # (start s, end s)


def spans(run, name: str, **where) -> List[dict]:
    """The program's ``name`` spans that start inside the window and carry
    the attributes ``where``, in the order they were emitted."""
    t0, t1 = run.window
    return [e for e in run.events
            if e.get("kind") == "span" and e.get("name") == name
            and "t0" in e and t0 <= e["t0"] < t1
            and all(e.get(k) == v for k, v in where.items())]


def end_of(span: dict) -> float:
    return span["t0"] + span["dur_ms"] / 1e3


def span_median_ms(run, name: str, **where) -> Optional[float]:
    """Median duration of the window's ``name`` spans, in milliseconds."""
    return stats.median([e["dur_ms"] for e in spans(run, name, **where)])


def iteration_ms(run) -> Optional[float]:
    """Median, over the iterations of the window that advanced (they
    have the last three phases), of ``sched_pull``'s start to
    ``sched_complete``'s end: the host's twin of the decode step's device
    time."""
    started = {e["iter"]: e["t0"] for e in spans(run, "sched_pull")}
    return stats.median([
        (end_of(e) - started[e["iter"]]) * 1e3
        for e in spans(run, "sched_complete") if e["iter"] in started])


def weighted_percentile(pairs: Sequence[Tuple[float, float]],
                        q: float) -> Optional[float]:
    """The least value with at least ``q`` percent of the weight at or
    under it; ``pairs`` are (value, weight)."""
    pairs = sorted(p for p in pairs if p[1] > 0)
    total = sum(w for _, w in pairs)
    seen = 0.0
    for value, weight in pairs:
        seen += weight
        if seen >= total * q / 100.0:
            return value
    return None


def itl_p99_ms(run) -> Optional[float]:
    """Inter-token gap as the fence sees it: for two fences of iterations
    that follow each other, the time between their ends over the steps of
    the later one, weighted by the tokens those steps emit (``live x
    steps``); the 99th percentile over the window, in milliseconds. An
    iteration after an idle stretch has no fence before it and no gap."""
    fences = spans(run, "sched_fence")
    gaps = [((end_of(b) - end_of(a)) * 1e3 / b["steps"],
             b["live"] * b["steps"])
            for a, b in zip(fences, fences[1:])
            if b["iter"] == a["iter"] + 1 and b["steps"] > 0]
    return weighted_percentile(gaps, 99.0)


# -- the device's idle time under the phases ----------------------------------

def fence_lags(fence_ends: Sequence[float],
               programs: List[Interval]) -> List[float]:
    """Each fence's end minus the end of the program it falls into
    (negative: it seems to return while the device still works) or of the
    last program before it (``programs`` sorted, disjoint). A fence with no
    program of the window before it has none."""
    starts = [a for a, _ in programs]
    out = []
    for end in fence_ends:
        i = bisect.bisect_right(starts, end) - 1
        if i >= 0:
            out.append(end - programs[i][1])
    return out


def idle_split(idle: List[Interval], phases: Dict[str, List[Interval]],
               window: Interval) -> Dict[str, float]:
    """Seconds of ``idle`` (sorted, disjoint, inside ``window``) under each
    phase's intervals, and under none of them as UNATTRIBUTED, each
    intersected on its own."""
    out = {}
    covered: List[Interval] = []
    for name, ivs in phases.items():
        ivs = trace_reduce.merge(trace_reduce.clip(ivs, window))
        out[name] = trace_reduce.intersect_len(idle, ivs)
        covered += ivs
    out[UNATTRIBUTED] = trace_reduce.intersect_len(
        idle, trace_reduce.gaps(trace_reduce.merge(covered), window))
    return out


def idle_shares(run) -> Optional[Dict[str, float]]:
    """Percent of the traced window in which the idlest device ran no
    operation AND the scheduler was inside each phase (UNATTRIBUTED: inside
    none). Computed once a run and kept under ``facts["sched_idle"]``; None
    without a device trace, without the clocks' join, without phase spans,
    or where one of the two checks fails."""
    if "sched_idle" in run.facts:
        return run.facts["sched_idle"]
    run.facts["sched_idle"] = None
    trace, offset = run.trace_data, run.trace_wall_offset_s
    if trace is None or not trace.devices or offset is None:
        return None
    events = [e for e in run.events if e.get("kind") == "span"
              and e.get("name") in PHASES and "t0" in e]
    if not events:
        return None
    # seconds from the traced window's start: a wall-clock second of the
    # 2020s is a double with 0.24 us between neighbours, the trace counts ns
    a, b = trace.window(WINDOW_MARK)
    origin = a / 1e9 + offset
    window = (0.0, (b - a) / 1e9)
    idle = sorted(((g0 - a) / 1e9, (g1 - a) / 1e9)
                  for g0, g1 in trace.idle_gaps(WINDOW_MARK, 1 << 62))
    worst = trace.worst_device(WINDOW_MARK)
    programs = trace_reduce.merge(
        ((m.start_ns - a) / 1e9, (m.end_ns - a) / 1e9)
        for m in trace.devices[worst].modules)
    phases = {name: [(e["t0"] - origin, end_of(e) - origin) for e in events
                     if e["name"] == name] for name in PHASES}
    lags = fence_lags([end for _, end in phases["sched_fence"]
                       if window[0] <= end <= window[1]], programs)
    early = stats.percentile(lags, 1.0)
    device = trace.busy_idle(WINDOW_MARK)[worst]["idle_pct"]
    shares = {name: 100.0 * s / window[1]
              for name, s in idle_split(idle, phases, window).items()}
    residue = device - sum(shares.values())
    good = bool(lags) and early >= -FENCE_EARLY_S \
        and abs(residue) <= RESIDUE_LIMIT_PCT
    run.note(sched_idle_check="a fence ends after the program it waited for",
             holds=good, fences=len(lags),
             fence_lag_p50_us=_us(stats.median(lags)),
             fence_lag_p01_us=_us(early), idle_gaps=len(idle),
             device_idle_pct=device, idle_pct_by_phase=shares,
             residue_pct=residue)
    if good:
        run.facts["sched_idle"] = shares
    return run.facts["sched_idle"]


def _us(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1e6


def idle_share_pct(run, name: str) -> Optional[float]:
    shares = idle_shares(run)
    return None if shares is None else shares[name]

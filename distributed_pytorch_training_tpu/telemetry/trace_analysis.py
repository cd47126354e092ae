"""Gradient-sync share from jax.profiler traces.

The reference README promises "At 4 GPUs, gradient synchronization accounts
for ~X% of step time" but never measures it (/root/reference/README.md:35) —
on GPU one would read an nsys/profiler timeline. The TPU equivalent: capture
a `jax.profiler` trace of the compiled train step and sum the durations of
collective ops (the DDP all-reduce equivalents XLA scheduled) against the
total XLA-op busy time. This module parses the Chrome-trace JSON the profiler
writes (`plugins/profile/<ts>/<host>.trace.json.gz`) — no tensorboard plugin
needed.

Two readers: experiments/scaling.py `gradsync`, which cross-checks three
ways — (a) measured 1-vs-N step-time delta, (b) the static HLO collective
census (`analysis/hlo_rules.py`), (c) the trace-derived share here (the
profiler-timeline read-off the README placeholder calls for) — and the
telemetry plane's `device_profile` event (`telemetry/device.py`, over
`device_time_split`).
"""

from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Tuple

# Collective op names as they appear on XLA timelines (sync form, async
# `-start` form, and CPU thunk form). `-done` events are completion markers
# whose duration is wait-not-work; skip them like the HLO census does —
# an async collective's `-start` span covers the transfer, so counting
# both halves of a pair would double its time. `ragged-all-to-all` (MoE
# dispatch at uneven expert loads) precedes `all-to-all` so the longer
# name keys the by_op breakdown.
_COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|ragged-all-to-all|all-to-all)"
    r"(?!.*-done)")

# Host-side runtime bookkeeping seen on CPU traces (no device lanes exist
# there); everything matching these is neither compute nor communication.
_INFRA_PREFIXES = (
    "ThreadpoolListener", "ThunkExecutor", "Wait", "Rendezvous", "PjRt",
    "CommonPjRt", "Handle inputs", "end:", "CreateOutputs", "Allocate",
    "Deallocate", "BufferAlloc", "BufferFree", "MarkDonated", "python",
    "HostCallback", "TransferTo", "TransferFrom", "CopyTo", "CopyFrom",
    "ExecuteHelper", "Execute (", "call_location",
)


def _norm(name: str) -> str:
    """'wrapped_all-reduce.3' -> 'all-reduce.3' (CPU thunks wrap op names)."""
    return name[8:] if name.startswith("wrapped_") else name


def load_trace(log_dir: str) -> Tuple[List[dict], Dict[int, str],
                                      Dict[tuple, str]]:
    """(complete events, pid -> process name, (pid, tid) -> thread name)
    from every trace.json.gz under `log_dir` (one per host). Raises
    FileNotFoundError if no trace exists."""
    paths = sorted(glob.glob(
        str(Path(log_dir) / "**" / "*.trace.json.gz"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {log_dir}")
    events: List[dict] = []
    pids: Dict[int, str] = {}
    tids: Dict[tuple, str] = {}
    for p in paths:
        data = json.loads(gzip.open(p).read())
        for e in data.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pids[e.get("pid")] = e.get("args", {}).get("name", "")
            elif e.get("ph") == "M" and e.get("name") == "thread_name":
                tids[(e.get("pid"), e.get("tid"))] = (
                    e.get("args", {}).get("name", ""))
            elif e.get("ph") == "X" and e.get("dur", 0) > 0:
                events.append(e)
    return events, pids, tids


def xla_op_events(events: List[dict], pids: Dict[int, str],
                  tids: Dict[tuple, str]) -> List[dict]:
    """The events that represent on-device XLA op execution, counted ONCE.

    TPU/GPU traces put ops on `/device:...` process lanes, but a device pid
    carries several overlapping lanes ("XLA Modules" spans the same wall
    time as the sum of its "XLA Ops") — summing all of them double-counts
    busy time and halves the reported collective share, so restrict to the
    per-op lanes when thread names identify them. CPU traces (the test
    backend) run thunks on host threadpool lanes with no device pids; fall
    back to name-based filtering of runtime bookkeeping.
    """
    device_pids = {pid for pid, name in pids.items() if "/device:" in name}
    if device_pids:
        dev = [e for e in events if e.get("pid") in device_pids]
        op_lanes = {key for key, name in tids.items()
                    if key[0] in device_pids and "xla ops" in name.lower()}
        if op_lanes:
            return [e for e in dev
                    if (e.get("pid"), e.get("tid")) in op_lanes]
        return dev
    return [e for e in events
            if not _norm(e["name"]).startswith(_INFRA_PREFIXES)]


def collective_share(log_dir: str) -> dict:
    """Trace-derived gradient-sync share: collective time / XLA-op busy time.

    Returns {collective_us, op_us, share_pct, by_op: {name: us}} aggregated
    over every device lane in the capture window. `share_pct` is the
    fraction of device busy time spent in communication — the number the
    reference's README placeholder wants (README.md:35).
    """
    events, pids, tids = load_trace(log_dir)
    ops = xla_op_events(events, pids, tids)
    coll_us = 0.0
    op_us = 0.0
    by_op: Dict[str, float] = {}
    for e in ops:
        name = _norm(e["name"])
        dur = float(e["dur"])
        op_us += dur
        m = _COLLECTIVE_RE.match(name)
        if m:
            coll_us += dur
            key = m.group(1)
            by_op[key] = by_op.get(key, 0.0) + dur
    return {
        "collective_us": round(coll_us, 1),
        "op_us": round(op_us, 1),
        "share_pct": round(100.0 * coll_us / op_us, 2) if op_us else 0.0,
        "by_op": {k: round(v, 1) for k, v in sorted(by_op.items())},
    }


def comm_overlap_split(log_dir: str) -> dict:
    """Exposed-vs-hidden communication time from a jax.profiler trace —
    the overlap instrument of the bucketed reducer (DDP's hooks hide comm
    behind backward compute; here the scan-body collectives have no data
    dependency on the next microbatch, and this measures how much of their
    wall time XLA actually hid).

    A collective event's duration is HIDDEN where it overlaps (same pid,
    any lane) with non-collective op execution, EXPOSED elsewhere. On TPU
    timelines async ``-start`` events span the transfer, so the split is
    honest; on the CPU test backend thunks serialize on the threadpool, so
    exposed ~= 100% — the number is only meaningful with device lanes.

    Returns {collective_us, hidden_us, exposed_us, exposed_frac_pct}.
    """
    events, pids, tids = load_trace(log_dir)
    ops = xla_op_events(events, pids, tids)
    comp_by_pid: Dict[int, List[Tuple[float, float]]] = {}
    coll = []
    for e in ops:
        iv = (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
        if _COLLECTIVE_RE.match(_norm(e["name"])):
            coll.append((e.get("pid"), iv))
        else:
            comp_by_pid.setdefault(e.get("pid"), []).append(iv)
    merged: Dict[int, List[Tuple[float, float]]] = {}
    for pid, ivs in comp_by_pid.items():
        ivs.sort()
        out: List[Tuple[float, float]] = []
        for a, b in ivs:
            if out and a <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], b))
            else:
                out.append((a, b))
        merged[pid] = out
    total = hidden = 0.0
    for pid, (a, b) in coll:
        total += b - a
        for ca, cb in merged.get(pid, ()):
            if cb <= a:
                continue
            if ca >= b:
                break
            hidden += min(b, cb) - max(a, ca)
    exposed = max(0.0, total - hidden)
    return {
        "collective_us": round(total, 1),
        "hidden_us": round(hidden, 1),
        "exposed_us": round(exposed, 1),
        "exposed_frac_pct": round(100.0 * exposed / total, 2) if total
        else 0.0,
    }


def device_time_split(log_dir: str) -> dict:
    """The four-way device-time attribution of one captured window
    (ISSUE 15 — the number set telemetry/device.py turns into a typed
    ``device_profile`` event):

    * ``compute_us`` — op busy time that is neither communication nor
      hidden under it,
    * ``comm_hidden_us`` — collective time overlapping compute on the
      same pid (XLA hid it),
    * ``comm_exposed_us`` — collective time nothing overlapped (the
      number that decides whether compressed sync paid off),
    * ``host_gap_us`` — wall extent of the capture minus device busy
      time (dispatch stalls, loader waits, host work).

    The four numbers are UNION wall measures per pid (compute-only wall,
    collective wall coinciding with compute, collective-only wall, idle
    wall), so ``compute + hidden + exposed + gap == window`` holds
    EXACTLY on any trace — including the CPU thunk pool, where 8 virtual
    replicas' all-reduce events overlap each other on one pid and a
    per-event sum (``comm_overlap_split``'s accounting, kept unchanged
    for `experiments.scaling`) can exceed the wall. ``by_op`` stays
    per-event op time (the collective rollup is op work, not wall share).
    On the CPU backend the hidden/exposed numbers measure thunk
    concurrency, not ICI overlap — the ``comm_overlap_split`` caveat
    applies unchanged.
    """
    events, pids, tids = load_trace(log_dir)
    ops = xla_op_events(events, pids, tids)
    coll_by_pid: Dict[int, List[Tuple[float, float]]] = {}
    comp_by_pid: Dict[int, List[Tuple[float, float]]] = {}
    by_op: Dict[str, float] = {}
    for e in ops:
        iv = (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
        pid = e.get("pid")
        name = _norm(e["name"])
        m = _COLLECTIVE_RE.match(name)
        if m:
            coll_by_pid.setdefault(pid, []).append(iv)
            by_op[m.group(1)] = by_op.get(m.group(1), 0.0) + (iv[1] - iv[0])
        else:
            comp_by_pid.setdefault(pid, []).append(iv)

    def _merge(ivs: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
        ivs = sorted(ivs)
        out: List[Tuple[float, float]] = []
        for a, b in ivs:
            if out and a <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], b))
            else:
                out.append((a, b))
        return out

    def _length(ivs: List[Tuple[float, float]]) -> float:
        return sum(b - a for a, b in ivs)

    def _intersect_len(xs: List[Tuple[float, float]],
                       ys: List[Tuple[float, float]]) -> float:
        total = 0.0
        i = j = 0
        while i < len(xs) and j < len(ys):
            a = max(xs[i][0], ys[j][0])
            b = min(xs[i][1], ys[j][1])
            if b > a:
                total += b - a
            if xs[i][1] <= ys[j][1]:
                i += 1
            else:
                j += 1
        return total

    window = compute = hidden = exposed = gap = coll_total = 0.0
    for pid in set(coll_by_pid) | set(comp_by_pid):
        comp = _merge(comp_by_pid.get(pid, []))
        coll = _merge(coll_by_pid.get(pid, []))
        every = _merge(comp + coll)
        if not every:
            continue
        extent = every[-1][1] - every[0][0]
        busy = _length(every)
        c_len, k_len = _length(comp), _length(coll)
        overlap = _intersect_len(comp, coll)
        window += extent
        compute += c_len - overlap
        hidden += overlap
        exposed += k_len - overlap
        gap += extent - busy
        coll_total += k_len
    return {
        "window_us": round(window, 1),
        "compute_us": round(compute, 1),
        "comm_hidden_us": round(hidden, 1),
        "comm_exposed_us": round(exposed, 1),
        "host_gap_us": round(gap, 1),
        "collective_us": round(coll_total, 1),
        "exposed_frac_pct": round(100.0 * exposed / coll_total, 2)
        if coll_total else 0.0,
        "by_op": {k: round(v, 1) for k, v in sorted(by_op.items())},
        "n_device_lanes": len(set(coll_by_pid) | set(comp_by_pid)),
    }


def capture_step_trace(step_fn, state, batch, key, log_dir: str,
                       steps: int = 3):
    """Run `steps` executions of a compiled/jitted train step under a
    jax.profiler trace (call AFTER warmup so compile time stays out of the
    window). Returns the final state. Rides utils/profiling's session
    guard: a concurrently-open session refuses loudly instead of raising
    from deep inside jax."""
    import jax

    from ..utils.profiling import trace_session

    with trace_session(log_dir, owner="capture_step_trace") as started:
        if not started:
            raise RuntimeError(
                "capture_step_trace: a jax profiler session is already "
                "open in this process — stop it (StepProfiler window / "
                "on-demand capture) before capturing a step trace")
        metrics = None
        for _ in range(steps):
            state, metrics = step_fn(state, batch, key)
        if metrics is not None:
            jax.block_until_ready(metrics)
            float(jax.device_get(metrics["weight"]))  # true completion sync
    return state

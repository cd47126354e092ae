"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle, the
operations that took most time, the longest idle gaps, per-program and
per-kernel device time, and collective time that no compute hides.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. What a
TPU trace from jax 0.9.0 / libtpu 0.0.34 holds is written down in PERF.md
(section 7) and pinned by ``benchmark/fixtures/`` and its test: one plane per
chip named ``/device:TPU:<n>``, on it a lane ``XLA Ops`` (one event per
executed HLO operation, serial on the core) and a lane ``XLA Modules`` (one
event per executed program); host threads under ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` events land.

The interval arithmetic (union, intersection, subtraction of sorted interval
lists) is a copy of ``experiments/trace_analysis.py``'s, which is exact; its
trace reader (``*.trace.json.gz``, lanes found by pid) is not used.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gzip
import re
import tempfile
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LANE = "XLA Ops"
MODULES_LANE = "XLA Modules"
# An event on the ``XLA Ops`` lane is named by the whole HLO instruction:
# ``%all-reduce.3 = f32[1024]{0:T(1024)} all-reduce(...), replica_groups=...``
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DeviceLanes:
    ops: List[Event]
    modules: List[Event]


# -- interval arithmetic (copied from experiments/trace_analysis.py) ---------

def merge(ivs: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def intersect_len(xs: List[Interval], ys: List[Interval]) -> float:
    """Length of the intersection of two MERGED interval lists."""
    total = 0.0
    i = j = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(ivs: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in ivs
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that the MERGED list ``busy`` leaves open."""
    out = []
    t = window[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


# -- the trace ---------------------------------------------------------------

def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def is_pallas_call(name: str) -> bool:
    """A Pallas (Mosaic) kernel: an HLO custom-call whose target is
    ``tpu_custom_call``; the flash kernels are named ``%attn.<n>`` after the
    model's attention scope."""
    return 'custom_call_target="tpu_custom_call"' in name


def is_container(name: str) -> bool:
    """``while``, ``conditional`` and ``call`` events span the operations of
    their bodies, which are events of their own: a container is neither
    compute nor a collective."""
    if " = " not in name:
        return False
    found = _OPCODE.search(" " + _LAYOUT.sub("", name.split(" = ", 1)[1]))
    return bool(found) and found.group(1) in ("while", "conditional", "call")


def short_name(name: str, limit: int = 96) -> str:
    """``%fusion.27 = f32[64,50257]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.27 fusion f32[64,50257]``: instruction, opcode, result shape
    without layouts, cut to ``limit`` characters."""
    if " = " not in name:
        return name[:limit]
    lhs, rhs = name.split(" = ", 1)
    rhs = _LAYOUT.sub("", rhs)
    found = _OPCODE.search(" " + rhs)
    if not found:
        return lhs.lstrip("%")[:limit]
    shape = rhs[:max(found.start() - 1, 0)].strip()
    return f"{lhs.lstrip('%')} {found.group(1)} {shape}"[:limit]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceLanes]
    host_marks: List[Event]      # TraceAnnotation events on host threads

    def mark(self, name: str) -> Optional[Interval]:
        """The interval of the host annotation ``name`` (the longest, if it
        was entered more than once)."""
        found = [e for e in self.host_marks if e.name == name]
        if not found:
            return None
        e = max(found, key=lambda e: e.dur_ns)
        return (e.start_ns, e.end_ns)

    def window(self, mark: Optional[str]) -> Interval:
        """The traced window: the host annotation when it is there, else
        first operation start to last operation end over all devices."""
        iv = self.mark(mark) if mark else None
        if iv is not None:
            return iv
        starts = [e.start_ns for d in self.devices.values() for e in d.ops]
        ends = [e.end_ns for d in self.devices.values() for e in d.ops]
        if not starts:
            raise ValueError("the trace holds no device operation")
        return (min(starts), max(ends))

    def _busy(self, lanes: DeviceLanes, window: Interval,
              keep: Optional[Callable[[Event], bool]] = None
              ) -> List[Interval]:
        return merge(clip(((e.start_ns, e.end_ns) for e in lanes.ops
                           if keep is None or keep(e)), window))

    def busy_idle(self, mark: Optional[str] = None) -> Dict[str, dict]:
        """Per device: seconds in which an operation ran (the union of the
        ``XLA Ops`` intervals inside the window), the window, the idle
        share."""
        window = self.window(mark)
        out = {}
        for name, lanes in self.devices.items():
            busy = length(self._busy(lanes, window))
            span = window[1] - window[0]
            out[name] = {"busy_s": busy / 1e9, "window_s": span / 1e9,
                         "idle_pct": 100.0 * (1.0 - busy / span)}
        return out

    def worst_device(self, mark: Optional[str] = None) -> str:
        busy = self.busy_idle(mark)
        return max(busy, key=lambda d: busy[d]["idle_pct"])

    def top_ops(self, mark: Optional[str], n: int) -> List[Tuple[str, float]]:
        """The operations with most device time inside the window, summed
        by (shortened) name over the idlest device: [(name, seconds)]. A
        ``while`` or ``conditional`` holds its body's operations as nested
        events, so a parent and its children both appear."""
        window = self.window(mark)
        lanes = self.devices[self.worst_device(mark)]
        total: Dict[str, float] = {}
        for e in lanes.ops:
            a, b = max(e.start_ns, window[0]), min(e.end_ns, window[1])
            if b > a:
                key = short_name(e.name)
                total[key] = total.get(key, 0.0) + (b - a) / 1e9
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, mark: Optional[str], n: int) -> List[Interval]:
        """The ``n`` longest idle gaps of the idlest device."""
        window = self.window(mark)
        lanes = self.devices[self.worst_device(mark)]
        found = gaps(self._busy(lanes, window), window)
        return sorted(found, key=lambda g: g[0] - g[1])[:n]

    def labelled_gaps(self, mark: Optional[str],
                      spans: List[Tuple[float, float, str]],
                      wall_offset_s: Optional[float],
                      n: int) -> List[Tuple[str, float]]:
        """The longest idle gaps, each named by the program's telemetry span
        (``(wall start s, duration s, name)``) that covers most of it, else
        ``unattributed``: [(label, seconds)]. ``wall_offset_s`` is wall
        clock minus trace clock."""
        out = []
        for a, b in self.idle_gaps(mark, n):
            label, best = "unattributed", 0.0
            if wall_offset_s is not None:
                wa, wb = a / 1e9 + wall_offset_s, b / 1e9 + wall_offset_s
                for t0, dur, name in spans:
                    cover = min(wb, t0 + dur) - max(wa, t0)
                    if cover > best:
                        label, best = name, cover
            out.append((label, (b - a) / 1e9))
        return out

    def module_times(self, pattern: str,
                     mark: Optional[str] = None) -> List[float]:
        """Device seconds of each execution, inside the window, of the
        programs whose name matches ``pattern`` (``XLA Modules`` lane, all
        devices)."""
        window = self.window(mark)
        rx = re.compile(pattern)
        return [e.dur_ns / 1e9 for lanes in self.devices.values()
                for e in lanes.modules
                if rx.search(e.name) and e.start_ns >= window[0]
                and e.end_ns <= window[1]]

    def exposed_collective(self, mark: Optional[str] = None
                           ) -> Dict[str, dict]:
        """Per device: collective wall time, and the part of it during
        which no other operation ran on that device (``exposed``)."""
        window = self.window(mark)
        out = {}
        for name, lanes in self.devices.items():
            coll = self._busy(lanes, window,
                              lambda e: is_collective(e.name))
            compute = self._busy(
                lanes, window, lambda e: not is_collective(e.name)
                and not is_container(e.name))
            total = length(coll)
            exposed = total - intersect_len(coll, compute)
            span = window[1] - window[0]
            out[name] = {"collective_s": total / 1e9,
                         "exposed_s": exposed / 1e9,
                         "exposed_pct": 100.0 * exposed / span}
        return out


# -- reading ------------------------------------------------------------------

def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@contextlib.contextmanager
def _profile(path):
    """``ProfileData`` of an ``.xplane.pb`` or, as the fixtures are kept, an
    ``.xplane.pb.gz`` (unpacked to a temporary file for the reader)."""
    import jax

    path = Path(path)
    if path.suffix != ".gz":
        yield jax.profiler.ProfileData.from_file(str(path))
        return
    with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as tmp:
        tmp.write(gzip.decompress(path.read_bytes()))
        tmp.flush()
        yield jax.profiler.ProfileData.from_file(tmp.name)


def load_xplane(path) -> Trace:
    """Read a trace file into a `Trace`."""
    with _profile(path) as data:
        return _reduce(data)


def _reduce(data) -> Trace:
    devices: Dict[str, DeviceLanes] = {}
    marks: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lanes = DeviceLanes(ops=[], modules=[])
            for line in plane.lines:
                if line.name == OPS_LANE:
                    target = lanes.ops
                elif line.name == MODULES_LANE:
                    target = lanes.modules
                else:
                    continue
                for e in line.events:
                    target.append(Event(e.name, float(e.start_ns),
                                        float(e.duration_ns)))
            lanes.ops.sort(key=lambda e: e.start_ns)
            lanes.modules.sort(key=lambda e: e.start_ns)
            if lanes.ops:
                devices[plane.name] = lanes
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("benchmark_"):
                        marks.append(Event(e.name, float(e.start_ns),
                                           float(e.duration_ns)))
    return Trace(devices=devices, host_marks=marks)


def describe(path) -> dict:
    """What a trace file holds, for a human: planes, lanes, event counts,
    the commonest event names with a sample of their stats. This is how
    PERF.md's description of a TPU trace was taken."""
    with _profile(path) as data:
        planes = []
        for plane in data.planes:
            lines = []
            for line in plane.lines:
                events = list(line.events)
                by_name: Dict[str, list] = {}
                for e in events:
                    by_name.setdefault(e.name, []).append(e)
                common = sorted(by_name.items(),
                                key=lambda kv: -sum(x.duration_ns
                                                    for x in kv[1]))[:12]
                lines.append({
                    "line": line.name, "events": len(events),
                    "first_start_ns": min((e.start_ns for e in events),
                                          default=None),
                    "last_end_ns": max((e.start_ns + e.duration_ns
                                        for e in events), default=None),
                    "top": [{"name": n, "count": len(es),
                             "total_ns": sum(x.duration_ns for x in es),
                             "stats": {k: (v if not isinstance(v, str)
                                           else v[:160])
                                       for k, v in list(es[0].stats)[:14]}}
                            for n, es in common]})
            planes.append({"plane": plane.name, "lines": lines})
    return {"file": str(path), "bytes": Path(path).stat().st_size,
            "planes": planes}

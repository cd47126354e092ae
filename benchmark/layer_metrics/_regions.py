"""Device time of one executed program, split by the named regions of its
source: `jax.named_scope` paths (flax opens one per module; the program adds
``head``, ``loss``, ``optimizer``, ``kv_gather``, ``kv_scatter``, ``sample``,
``model``, ``bookkeeping`` and the three flash kernels' names). The names
are listed once, here (`TRAIN_STEP`, `FLASH_KERNELS`, `PAGED_DECODE`); where
the program opens a scope it writes the literal, and
``tests/benchmark/test_benchmark_region_names.py`` holds every name below
against the lowered text of the program it is read from.

Where a scope path lands in a TPU trace from jax 0.9.0 / libtpu 0.0.34
(PERF.md section 7): not in an ``XLA Ops`` event's name (the instruction's
text, printed without ``metadata=``) and not in the event's stats, which is
all ``jax.profiler.ProfileData`` shows, but in the stat ``tf_op`` of the
event's METADATA record (``XEventMetadata.stats``), one per distinct
instruction, as ``jit(decode)/model/GPT2LMHead/block0/attn/dot_general:``.
``trace_reduce`` keeps names only, so `scope_paths` opens the run's
``.xplane.pb`` a second time and walks the protobuf's wire format itself, as
far as the metadata tables and no further (the lanes, most of the file, are
skipped by their length): instruction text -> scope paths, per device plane.
Why by hand: the only generated ``xplane_pb2`` this installation has is
TensorFlow's (``tensorflow.tsl.profiler.protobuf``), and importing it runs
``tensorflow/__init__``: 11 s here, and a second runtime loaded into the
process that holds the chip, to parse the lanes as well (0.2-1.1 s this way).

A reader is three lines over `read`. On a program without the scopes (the
parent of the PR that added them) a named region matches nothing and its
reader returns None.
"""

from __future__ import annotations

import bisect
import gzip
import re
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark import stats, trace_reduce
from benchmark.run import WINDOW_MARK

# every split has these two beside its regions: operations under no region,
# and collectives, whose path is that of ONE of the gradients they combine
UNSCOPED = "unscoped"
COLLECTIVE = "collective"

# (pattern of the program's name on the ``XLA Modules`` lane, the regions its
# readers split it by)
TRAIN_STEP = (r"train_step", ("attn", "mlp", "wte", "wpe", "ln_f", "head",
                              "loss", "optimizer"))
FLASH_KERNELS = (r"train_step", ("flash_fwd", "flash_bwd_dkv",
                                 "flash_bwd_dq"))
PAGED_DECODE = (r"jit_decode\b", ("kv_gather", "kv_scatter", "sample",
                                  "model", "bookkeeping"))

_WRAPPED = re.compile(r"^[A-Za-z_][\w.\-]*\((.*)\)$")


# -- the metadata tables of an .xplane.pb --------------------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, a view (no copy: a lane is most of the file) for a
    length-delimited field; fixed-width fields are stepped over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane file")
        yield field, wire, value


def _map_value(entry: memoryview) -> memoryview:
    """The value of one ``map<int64, Message>`` entry."""
    return next((v for f, w, v in _fields(entry) if f == 2 and w == 2),
                memoryview(b""))


def _text(view: memoryview) -> str:
    return str(view, "utf-8", "replace")


def scope_paths(path) -> Dict[str, Dict[str, List[str]]]:
    """``{device plane: {instruction text: [scope path, ...]}}`` from the
    ``tf_op`` stat of the event metadata of an ``.xplane.pb`` (or ``.gz``).
    Two programs on one plane can hold instructions of the same text, hence
    the list; `path_of` picks by program."""
    raw = Path(path).read_bytes()
    if Path(path).suffix == ".gz":
        raw = gzip.decompress(raw)
    out: Dict[str, Dict[str, List[str]]] = {}
    for field, wire, plane in _fields(memoryview(raw)):   # XSpace.planes = 1
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for f, w, v in _fields(plane):
            if f == 2 and w == 2:                     # XPlane.name
                name = _text(v)
            elif f == 4 and w == 2:                   # .event_metadata
                events.append(v)
            elif f == 5 and w == 2:                   # .stat_metadata
                meta = dict((f2, v2) for f2, _, v2 in _fields(_map_value(v)))
                stat_names[meta.get(1)] = _text(meta.get(2, b""))
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        table: Dict[str, List[str]] = {}
        for entry in events:
            text, found = "", None
            for f, w, v in _fields(_map_value(entry)):
                if f == 2 and w == 2:                 # XEventMetadata.name
                    text = _text(v)
                elif f == 5 and w == 2:               # .stats (XStat)
                    stat = dict((f2, v2) for f2, _, v2 in _fields(v))
                    if stat.get(1) == tf_op and 5 in stat:   # str_value
                        found = _text(stat[5])
            if found and found not in table.setdefault(text, []):
                table[text].append(found)
        out[name] = table
    return out


# -- from a path to a region ---------------------------------------------------

def components(scope_path: str) -> List[str]:
    """``jit(f)/transpose(jvp(loss))/reduce_sum:`` -> ``[f, loss,
    reduce_sum]``: the transforms jax wraps around a component are taken
    off, so forward and backward carry the same scope."""
    out = []
    for part in scope_path.rsplit(":", 1)[0].split("/"):
        while True:
            inner = _WRAPPED.match(part)
            if not inner:
                break
            part = inner.group(1)
        out.append(part)
    return out


def region_of(scope_path: Optional[str], regions: Sequence[str]) -> str:
    """The innermost of ``regions`` that is a whole component of the path,
    else UNSCOPED. A fusion of operations from several scopes carries their
    paths joined by ``;``: the first that lies in a region decides."""
    for one in (scope_path or "").split(";"):
        for part in reversed(components(one)):
            if part in regions:
                return part
    return UNSCOPED


def path_of(table: Dict[str, List[str]], text: str,
             program: str) -> Optional[str]:
    found = table.get(text)
    if not found:
        return None
    # ``jit_decode(<id>)`` on the modules lane is ``jit(decode)/...`` here
    prefix = "jit(" + program.split("(", 1)[0][len("jit_"):] + ")"
    return next((p for p in found if p.startswith(prefix)), found[0])


# -- the split -----------------------------------------------------------------

def per_execution(trace: trace_reduce.Trace,
                  paths: Dict[str, Dict[str, List[str]]],
                  program_pattern: str, regions: Sequence[str],
                  mark: Optional[str] = WINDOW_MARK) -> List[dict]:
    """One entry per execution, wholly inside the window, of the programs
    matching ``program_pattern``, all chips: ``{"plane", "start_ns", "ns":
    {region: device ns of its LEAF operations}, "busy_ns": the union of the
    leaves' intervals}``. A ``while``, ``conditional`` or ``call`` holds its
    body's operations as events of their own and is skipped; a collective
    is COLLECTIVE whatever its path says."""
    window = trace.window(mark)
    rx = re.compile(program_pattern)
    out = []
    for plane, lanes in trace.devices.items():
        table = paths.get(plane, {})
        starts = [e.start_ns for e in lanes.ops]
        where: Dict[Tuple[str, str], str] = {}
        for m in lanes.modules:
            if not rx.search(m.name) or m.start_ns < window[0] \
                    or m.end_ns > window[1]:
                continue
            got = dict.fromkeys((*regions, UNSCOPED, COLLECTIVE), 0.0)
            leaves = []
            for e in lanes.ops[bisect.bisect_left(starts, m.start_ns):
                               bisect.bisect_right(starts, m.end_ns)]:
                if e.end_ns > m.end_ns or trace_reduce.is_container(e.name):
                    continue
                key = (m.name, e.name)
                if key not in where:
                    where[key] = COLLECTIVE \
                        if trace_reduce.is_collective(e.name) else region_of(
                            path_of(table, e.name, m.name), regions)
                got[where[key]] += e.dur_ns
                leaves.append((e.start_ns, e.end_ns))
            if leaves:
                out.append({"plane": plane, "start_ns": m.start_ns,
                            "ns": got, "busy_ns": trace_reduce.length(
                                trace_reduce.merge(leaves))})
    return out


def split(trace: trace_reduce.Trace, paths: Dict[str, Dict[str, List[str]]],
          program_pattern: str, regions: Sequence[str],
          mark: Optional[str] = WINDOW_MARK, note=None
          ) -> Optional[Dict[str, float]]:
    """Median over `per_execution`, all chips pooled, of the milliseconds in
    each region (every name of ``regions``, UNSCOPED, COLLECTIVE). In every
    execution the regions have to sum to its busy time within 1%: if they do not,
    leaves overlap in a way this reader does not know, and it returns None
    rather than a wrong split. ``note(**fields)`` is told both sums."""
    runs = per_execution(trace, paths, program_pattern, regions, mark)
    if not runs:
        return None
    sums = [sum(r["ns"].values()) / 1e6 for r in runs]
    busies = [r["busy_ns"] / 1e6 for r in runs]
    worst = max(abs(s - b) / b for s, b in zip(sums, busies))
    if note is not None:
        note(region_split=program_pattern, regions=list(regions),
             executions=len(runs), groups_sum_ms=stats.median(sums),
             busy_ms=stats.median(busies), worst_gap_pct=100.0 * worst)
    if worst > 0.01:
        return None
    return {r: stats.median([run["ns"][r] / 1e6 for run in runs])
            for r in (*regions, UNSCOPED, COLLECTIVE)}


def region_ms(run, program_pattern: str,
              regions: Sequence[str]) -> Optional[Dict[str, float]]:
    """`split` of the traced run's own trace. The file is parsed once and
    each split computed once per run, however many readers ask: both are
    kept on the run, under ``facts["region_splits"]``."""
    trace = run.trace_data
    if trace is None or not trace.devices:
        return None
    kept = run.facts.setdefault("region_splits", {})
    if "paths" not in kept:
        t0 = time.perf_counter()
        kept["paths"] = scope_paths(
            trace_reduce.newest_xplane(Path(run.out_dir) / "trace"))
        run.note(scope_paths_s=time.perf_counter() - t0,
                 instructions_with_a_path={p: len(t) for p, t
                                           in kept["paths"].items()})
    key = (program_pattern, tuple(regions))
    if key not in kept:
        kept[key] = split(trace, kept["paths"], program_pattern, regions,
                          note=run.note)
    return kept[key]


def read(run, program: Tuple[str, Sequence[str]],
         pick: Sequence[str]) -> Optional[float]:
    """Milliseconds of one execution of ``program`` in the regions ``pick``
    (names of ``program``'s regions, UNSCOPED, COLLECTIVE). None where the
    program has none of them: the scopes are not in it."""
    got = region_ms(run, *program)
    if got is None:
        return None
    total = sum(got[r] for r in pick)
    return total if total > 0 else None

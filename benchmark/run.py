"""One run of one cell: ``python -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

The harness is driven by data. ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; the configuration is
``benchmark/configs/<config>.json``, the mix ``benchmark/traffic/<mix>.json``,
the mix's ``driver`` one module under ``benchmark/drivers/``, and each
per-layer metric one reader ``benchmark/layer_metrics/<metric>.py``. A new
cell, model or metric is new files plus an entry; no file here names one.

The last line of stdout is the one JSON object the contract asks for;
everything else (medians, counts, lateness, XLA's FLOP count, the S0 timing
check) goes on earlier lines and into ``benchmark_out/<cell>/``.

No accelerator, fewer chips than the cell asks for, or a device kind that
``peaks.json`` does not list: exit code 1 and no result line. With
``--rehearsal`` AND ``JAX_PLATFORMS=cpu``, both by name, the run is a rehearsal
at the tiny sizes the configuration and the mix give under ``rehearsal``; it
checks control flow and correctness and prints no device metric.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # process start, as near as Python can say

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent
WINDOW_MARK = "benchmark_window"


class CompileMeter:
    """Seconds in XLA's backend compile (a persistent-cache hit counts its
    retrieval there), the number of such compiles, and the cache's hit and
    miss census, from jax's own monitoring events: the counting
    ``chip_smoke.py`` does."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Run:
    """What a driver is given, and what the readers read afterwards."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    devices: list
    peaks: Optional[dict]
    compile: CompileMeter
    out_dir: Path
    t_start: float = _T_START
    # filled by the driver
    window: tuple = (0.0, 0.0)          # (start, end) on time.time()'s clock
    events: List[dict] = dataclasses.field(default_factory=list)
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace_data: Any = None              # benchmark.trace_reduce.Trace
    trace_wall_offset_s: Optional[float] = None

    def window_opens(self, t_perf: float) -> None:
        """Set-up ends here: what the compile meter read so far is the
        set-up's, and a compile from here to `window_closes` is a fault."""
        self.facts.update(t_window_start=t_perf,
                          compile_s_setup=self.compile.seconds,
                          cache_misses_setup=self.compile.misses,
                          compiles_setup=self.compile.compiles)

    def window_closes(self) -> None:
        self.facts["compiles_in_window"] = \
            self.compile.compiles - self.facts["compiles_setup"]

    def note(self, **fields) -> None:
        """One earlier line of stdout: a fact that is not a metric."""
        print("note " + json.dumps(fields, default=str), flush=True)

    def record_events(self):
        """Collect the program's telemetry (spans, counters, gauges) in
        memory. Only a traced run does: end-to-end numbers are taken with
        the instrumentation off."""
        from distributed_pytorch_training_tpu import telemetry

        recorder = telemetry.configure(None, ring_size=16)
        recorder.add_observer(self.events.append)

    def profile(self, body) -> None:
        """Run ``body()`` under the profiler, inside one host annotation
        named WINDOW_MARK, and reduce the trace that comes back."""
        import jax

        from benchmark import trace_reduce

        trace_dir = self.out_dir / "trace"
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            wall0 = time.time()
            with jax.profiler.TraceAnnotation(WINDOW_MARK):
                body()
        finally:
            jax.profiler.stop_trace()
        path = trace_reduce.newest_xplane(trace_dir)
        self.trace_data = trace_reduce.load_xplane(path)
        mark = self.trace_data.mark(WINDOW_MARK)
        if mark is not None:
            # telemetry is on time.time(); the trace has its own zero
            self.trace_wall_offset_s = wall0 - mark[0] / 1e9
        self.note(trace_file=str(path), bytes=path.stat().st_size,
                  device_planes=sorted(self.trace_data.devices))


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearsal: bool):
    """(bench, cell, config, traffic) for a workload name, rehearsal
    overrides folded in."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    if rehearsal:
        config = _merge(config, config.get("rehearsal", {}))
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    return bench, cell, config, traffic


def metrics_of_cell(bench: dict, cell: dict, group: str) -> List[dict]:
    """The metrics of ``group`` this cell reports: no ``workloads`` key
    means every cell; a per-layer metric only where the metric it moves is."""
    def applies(m):
        return "workloads" not in m or cell["name"] in m["workloads"]

    picked = [m for m in bench[group] if applies(m)]
    if group == "per_layer":
        e2e = {m["name"] for m in bench["end_to_end"] if applies(m)}
        picked = [m for m in picked if m["moves"] in e2e]
    return picked


def memory_peak_bytes(run: Run) -> int:
    """The peak on the fullest chip. On this runtime (jax 0.9.0, libtpu
    0.0.34) ``peak_bytes_in_use`` counts the arrays the process holds, and a
    running program's temporaries are reserved apart, under
    ``peak_bytes_reserved`` (PERF.md section 7): the peak is their sum, since
    the programs of a window run while its state is held."""
    peak = 0
    for d in run.devices[:run.cell["chips"]]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def device_block(run: Run) -> dict:
    used = run.devices[:run.cell["chips"]]
    block = {"platform": used[0].platform, "kind": used[0].device_kind,
             "count": len(used), "memory_peak_bytes": memory_peak_bytes(run)}
    if run.trace_data is not None and run.trace_data.devices:
        busy = run.trace_data.busy_idle(WINDOW_MARK)
        block["busy_s"] = sum(b["busy_s"] for b in busy.values()) / len(busy)
        block["window_s"] = next(iter(busy.values()))["window_s"]
    return block


def breakdown_block(run: Run) -> Optional[dict]:
    if run.trace_data is None or not run.trace_data.devices:
        return None
    spans = [(e["t0"], e["dur_ms"] / 1e3, e["name"]) for e in run.events
             if e.get("kind") == "span" and "t0" in e]
    return {
        "device_ops": [[n, s] for n, s in
                       run.trace_data.top_ops(WINDOW_MARK, 10)],
        "idle_gaps": [[n, s] for n, s in run.trace_data.labelled_gaps(
            WINDOW_MARK, spans, run.trace_wall_offset_s, 10)],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU run at tiny sizes (needs JAX_PLATFORMS=cpu)")
    args = ap.parse_args(argv)

    rehearsal = args.rehearsal
    if rehearsal and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        print("benchmark: --rehearsal is a CPU run; set JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 1
    bench, cell, config, traffic = load_cell(args.workload, rehearsal)
    seconds = args.seconds if args.seconds is not None else \
        float(bench["run_seconds"])
    if rehearsal and "seconds" in traffic.get("rehearsal", {}):
        seconds = min(seconds, float(traffic["rehearsal"]["seconds"]))

    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import jax

    if rehearsal:
        try:  # a rehearsal alone in its process asks for the cell's devices
            if jax.config.jax_num_cpu_devices < cell["chips"]:
                jax.config.update("jax_num_cpu_devices", cell["chips"])
        except RuntimeError:
            pass  # the backend is already up (a test process): use it
    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        print(f"benchmark: JAX found no accelerator (platform "
              f"{devices[0].platform!r}); a measurement needs the chip. "
              "--rehearsal under JAX_PLATFORMS=cpu runs the rehearsal.",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} chips, JAX "
              f"reports {len(devices)}", file=sys.stderr)
        return 1
    peaks = None
    if not rehearsal:
        table = json.loads((BENCH_DIR / "peaks.json").read_text())
        if devices[0].device_kind not in table:
            print(f"benchmark: no peaks on record for device kind "
                  f"{devices[0].device_kind!r} (benchmark/peaks.json has "
                  f"{sorted(table)}); add it with its source",
                  file=sys.stderr)
            return 1
        peaks = table[devices[0].device_kind]

    from distributed_pytorch_training_tpu.runtime import (
        compile_cache_dir, enable_persistent_compile_cache,
    )

    meter = CompileMeter()
    if enable_persistent_compile_cache():
        # every program, however quick to compile, is found again by the
        # next run of this cell in this checkout
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    out_dir = ROOT / "benchmark_out" / cell["name"]
    out_dir.mkdir(parents=True, exist_ok=True)
    run = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=seconds, trace=bool(args.trace), rehearsal=rehearsal,
              devices=devices, peaks=peaks, compile=meter, out_dir=out_dir)
    run.note(cell=cell["name"], seed=args.seed, seconds=seconds,
             trace=args.trace, rehearsal=rehearsal,
             device_kind=devices[0].device_kind, devices=len(devices),
             compile_cache=str(compile_cache_dir()))
    if run.trace:
        run.record_events()

    driver = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}")
    try:
        result = driver.run(run)   # correct, attempted, failed, values
    finally:
        if run.trace:
            from distributed_pytorch_training_tpu import telemetry

            telemetry.reset()

    values = dict(result["values"])
    values["setup_s"] = run.facts["t_window_start"] - run.t_start
    if run.trace:
        wanted = metrics_of_cell(bench, cell, "per_layer")
        values = {}
        for m in wanted:
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}")
            got = reader.read(run)
            if got is not None:   # nothing to read: left out of the line
                values[m["name"]] = float(got)
    else:
        wanted = metrics_of_cell(bench, cell, "end_to_end")
    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {n: {"value": values[n], "unit": units[n]}
               for n in units if values.get(n) is not None}

    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device_block(run)}
    if run.trace:
        breakdown = breakdown_block(run)
        if breakdown is not None:
            line["breakdown"] = breakdown
    run.note(memory_stats=devices[0].memory_stats())
    run.note(compile_s=meter.seconds, compiles=meter.compiles,
             cache_hits=meter.hits, cache_misses=meter.misses,
             compiles_in_window=run.facts.get("compiles_in_window"),
             wall_s=time.perf_counter() - run.t_start)
    if rehearsal:
        # a CPU run says what it counted and which metrics the chip run
        # would carry; it never writes a number under a device metric's name
        line["rehearsal"] = {"would_report": sorted(metrics),
                             "counts": result.get("counts", {})}
        line["metrics"] = {}
    (out_dir / f"last_trace{args.trace}.json").write_text(
        json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

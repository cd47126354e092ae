"""Find the knee of a serve cell once, by one sweep on the chip.

    python -m benchmark.tools.find_knee --workload serve_gpt2_124m_chat_steady
        [--rates 12,16,20,...] [--seconds 15] [--seed 0]

One process: builds the cell's server once, then offers the cell's own mix
open loop at each rate in turn (ramp, window, drain), lowest first. A rate
SUSTAINS when its backlog (requests due and without a first token) did not
grow from the middle of the window to its end by more than one request or 2%
of the requests due in that half, and nothing failed. It HOLDS when it
sustains and at least 90% of the requests due in the window had their first
token within 500 ms of their due time. The knee is the highest rate that
holds; where no rate meets the 500 ms limit (the report says what share did)
it is the highest rate that sustains, and the file says which rule gave it.
The sweep stops after two rates in a row that do not sustain. The cell then
runs at 0.8 x the knee: write ``rate_rps`` and this tool's last line (the
``knee`` object, with the date and commit) into the mix's traffic file.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys

from benchmark import run as harness
from benchmark.drivers import serve

TTFT_LIMIT_SHARE = 0.9
GROWTH_SHARE = 0.02


def sustains(summary: dict) -> bool:
    half = summary["seconds"] / 2
    grew = summary["backlog_at_window_end"] - summary["backlog_mid_window"]
    return (summary["failed"] == 0 and grew <= max(
        1.0, GROWTH_SHARE * summary["rate_rps"] * half))


def holds(summary: dict) -> bool:
    return (sustains(summary)
            and summary["ttft_under_500ms_share"] >= TTFT_LIMIT_SHARE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="12,16,20,24,28,32,36,40,48,56,64")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--commit", default="unknown")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax

    bench, cell, config, traffic = harness.load_cell(args.workload,
                                                     args.rehearsal)
    devices = jax.devices()
    if not args.rehearsal and devices[0].platform != "tpu":
        print("find_knee: a sweep needs the chip", file=sys.stderr)
        return 1
    out_dir = harness.ROOT / "benchmark_out" / "find_knee"
    out_dir.mkdir(parents=True, exist_ok=True)
    run = harness.Run(cell=cell, config=config, traffic=traffic,
                      seed=args.seed, seconds=args.seconds, trace=False,
                      rehearsal=args.rehearsal, devices=devices, peaks=None,
                      compile=harness.CompileMeter(), out_dir=out_dir)
    job = serve.build(run)
    serve.check_against_reference(run, job)
    rows, misses = [], 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(traffic, loop="open", rate_rps=rate)
        got = serve.offer(run, job, mix, args.seconds, args.seed + i)
        s = got["summary"]
        row = {"rate_rps": rate, "sustains": sustains(s), "holds": holds(s),
               "ttft_p50_ms": s["ttft_ms"]["p50"],
               "ttft_p95_ms": s["ttft_ms"]["p95"],
               "tpot_p95_ms": s["tpot_ms"]["p95"],
               "ttft_under_500ms_share": s["ttft_under_500ms_share"],
               "backlog_mid": s["backlog_mid_window"],
               "backlog_end": s["backlog_at_window_end"],
               "backlog_growth_per_s": s["backlog_growth_per_s"],
               "completed_per_s": s["completed_per_s"],
               "out_tokens_per_s": s["out_tokens_per_s"],
               "generator_late_p95_ms": s["generator_late_ms"]["p95"]}
        rows.append(row)
        print("rate " + json.dumps(row), flush=True)
        misses = 0 if row["sustains"] else misses + 1
        if misses >= 2:
            break
        # no drain between rates: what a sustained rate leaves in flight
        # is the next, higher rate's ramp
    job["replica"].kill()
    held = [r["rate_rps"] for r in rows if r["holds"]]
    sustained = [r["rate_rps"] for r in rows if r["sustains"]]
    knee = {"knee_rps": max(held or sustained, default=None),
            "rule": ("highest rate that sustains (no growing backlog) with "
                     ">= 90% of requests under TTFT 500 ms from due time"
                     if held else
                     "highest rate that sustains (no growing backlog); no "
                     "rate met TTFT 500 ms for 90% of requests"),
            "seconds_per_rate": args.seconds, "rows": traffic["rows"],
            "date": datetime.date.today().isoformat(),
            "commit": args.commit,
            "device": devices[0].device_kind, "sweep": rows}
    (out_dir / "knee.json").write_text(json.dumps(knee, indent=1))
    print(json.dumps(knee), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark entry point — one process, prints ONE JSON line for the driver.

Headline metric (BASELINE.json:2): training throughput, samples/sec/chip, for
the ResNet-18/CIFAR-10 config (config 1, the reference's own workload,
/root/reference/train_ddp.py) in bf16, measured on the devices present.

The backend must be a TPU unless the CPU was asked for by name
(``JAX_PLATFORMS=cpu``, runtime.require_backend): a bench that found no chip
fails, it does not print a CPU row under a device metric's name. Everything
runs in THIS process — a process that touched JAX holds the chip, so there
is no parent, no probe child and no watchdog.

Self-verification: every config reports model-FLOPs utilization (MFU),
computed from XLA's cost analysis of the exact compiled step (cross-checked
against an analytic matmul/conv count) divided by the detected chip peak
(experiments/flops.py). An implied FLOP/s above the MXU peak fails the
config instead of reporting it.

`vs_baseline` is the bf16-vs-fp32 speedup on identical hardware — the
"AMP-vs-FP32 speedup curve" the reference's README promises but never fills
in (README.md:31, :35). The fp32 arm runs under
`jax.default_matmul_precision("highest")` so it is *real* fp32: without that,
TPU fp32 matmuls default to bf16 MXU passes and the ratio is 1.0 by
construction.

A selected config that fails is named in the JSON (`configs_failed`) and
makes the exit code non-zero; the configs that did measure are still
reported. Every TPU run that measured something appends its full result dict
to experiments/results/bench_history.jsonl with chip kind and timestamp, so
the README benchmark table is regenerable from committed JSON. The telemetry
stream goes to ./experiments/ (the repo's git-ignored run-output directory).

Usage: python bench.py [--batch-size 4096] [--steps 20] [--quick]
       python bench.py --only gpt2_124m,bert_base   # a chunk of the matrix
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from distributed_pytorch_training_tpu import telemetry as _telemetry  # noqa: E402

HISTORY_PATH = ROOT / "distributed_pytorch_training_tpu" / "experiments" / \
    "results" / "bench_history.jsonl"
# train.py's default --output-dir: the git-ignored home of run artifacts
TELEMETRY_PATH = ROOT / "experiments" / "telemetry_bench.jsonl"

# Non-headline configs of the BASELINE matrix: (label, model, kwargs).
# Labels are stable names for --only selection and for bench_history rows.
EXTRA_CONFIGS = (
    ("resnet50", "resnet50",
     dict(per_device_batch=128, image_hw=224, num_classes=1000, steps=10)),
    ("vit_b16", "vit_b16",
     dict(per_device_batch=64, image_hw=224, num_classes=1000, steps=10)),
    ("gpt2_124m", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10)),
    ("bert_base", "bert_base",
     dict(per_device_batch=16, seq_len=512, steps=10)),
    # long-context (flash kernels) and expert-parallel coverage
    ("gpt2_124m_s4096", "gpt2_124m",
     dict(per_device_batch=2, seq_len=4096, steps=10)),
    ("gpt2_moe", "gpt2_moe",
     dict(per_device_batch=8, seq_len=1024, steps=10)),
    # the BASELINE flagship architecture (config 5) at single-chip scale:
    # ~4.3GB params+moments fp32, fits v5e HBM at b=2
    ("gpt2_355m", "gpt2_355m",
     dict(per_device_batch=2, seq_len=1024, steps=6)),
    # headline batch-scaling probe: b4096 was +13% over b2048; if b8192
    # measures higher still, it becomes the headline default (activations
    # ~2x the b4096 run; expected to fit 16G HBM on CIFAR shapes)
    ("resnet18_b8192", "resnet18",
     dict(per_device_batch=8192, image_hw=32, num_classes=10, steps=20)),
    # true-fp32 arm of the GPT-2 config: extends the measured AMP-vs-FP32
    # curve (the reference's README:31 experiment) beyond the ResNet
    # headline to the LM family, same HIGHEST-precision semantics
    ("gpt2_124m_fp32", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10, bf16=False)),
    # ZeRO-1 sharded-weight-update arms (training/loop.py zero1): on one
    # chip the mode is an identity passthrough (same numbers as the plain
    # config — a cheap regression canary); on multi-chip meshes these rows
    # are the replicated-vs-sharded comparison the scaling target needs
    # (experiments/scaling.py `zero1` is the full instrumented arm)
    ("resnet18_zero1", "resnet18",
     dict(per_device_batch=4096, image_hw=32, num_classes=10, steps=20,
          zero1=True)),
    ("gpt2_124m_zero1", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10, zero1=True)),
    # Explicit bucketed/compressed gradient sync (training/loop.py
    # bucket_cap_mb / wire_dtype; parallel/grad_sync.py): on one chip the
    # reducer is an identity passthrough (regression canary, like the
    # zero1 arms); on multi-chip meshes these rows carry the bucket census
    # + exposed-comm fraction, the overlap-efficiency numbers BENCH_*
    # history tracks across PRs (experiments/scaling.py `grad_sync` is the
    # full instrumented arm)
    ("resnet18_gsync", "resnet18",
     dict(per_device_batch=4096, image_hw=32, num_classes=10, steps=20,
          grad_sync=dict(bucket_cap_mb=25.0))),
    ("gpt2_124m_gsync_bf16", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10,
          grad_sync=dict(bucket_cap_mb=25.0, wire_dtype="bf16"))),
    # DynamiQ-style multi-hop int8 wire (wire_dtype="int8_multihop"):
    # s8 all-to-all reduce-scatter + requantized s8 all-gather — exactly
    # 2 collectives/bucket and ~2 wire B/element at ANY DP degree (the
    # n-independent fix for the gather-form int8's (n-1)·S scaling);
    # rows carry wire_bytes_per_replica so the claim is a recorded number
    ("resnet18_gsync_mh", "resnet18",
     dict(per_device_batch=4096, image_hw=32, num_classes=10, steps=20,
          grad_sync=dict(bucket_cap_mb=25.0, wire_dtype="int8_multihop"))),
    ("gpt2_124m_gsync_mh", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10,
          grad_sync=dict(bucket_cap_mb=25.0, wire_dtype="int8_multihop"))),
    # Two-tier topology-aware wire (wire_dtype="int8_hier"): exact fp32
    # reduce-scatter INSIDE a slice (fast ICI tier), the s8+EF multihop
    # exchange ACROSS slices (slow DCN tier — ~2 B/element per slice
    # independent of the slice count), exact intra-slice all-gather back.
    # The mesh_spec carries the slice factorization; needs >= 2 chips
    # (slice=2 on one device fails the mesh build loudly and the
    # per-config guard records the skip, like the _tp arm) — on a
    # slice-axis-of-1 mesh the trainer instead resolves to the flat fp32
    # passthrough (bit-identical). Rows record wire_bytes_per_replica
    # with the slow-tier term split out so the slice-count-independence
    # claim is a committed number.
    ("resnet18_gsync_hier", "resnet18",
     dict(per_device_batch=4096, image_hw=32, num_classes=10, steps=20,
          grad_sync=dict(bucket_cap_mb=25.0, wire_dtype="int8_hier"),
          mesh_spec="slice=2,data=-1")),
    ("gpt2_124m_gsync_hier", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10,
          grad_sync=dict(bucket_cap_mb=25.0, wire_dtype="int8_hier"),
          mesh_spec="slice=2,data=-1")),
    # Explicit full-parameter FSDP (training/loop.py fsdp_explicit;
    # SimpleFSDP, PAPERS.md): params + moments flat-sharded 1/N at rest,
    # one just-in-time param all-gather per layer group, gradients
    # reduce-scattered straight into the shard layout. On one chip the
    # mode is an identity passthrough (regression canary); on multi-chip
    # meshes these rows carry the per-layer gather census, the at-rest
    # memory division, and the fsdp_gather_bytes wire term
    # (experiments/scaling.py `fsdp` is the full instrumented arm). The
    # _mh arm compresses BOTH wire directions (s8 scatter with EF + s8
    # param gathers — ~2 B/element total at any DP degree); the 355m arm
    # is the BASELINE flagship whose replicated params+moments cap the
    # v4-32 pod config — the model this mode exists to unlock.
    ("gpt2_124m_fsdp", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10,
          grad_sync=dict(fsdp_explicit=True))),
    ("gpt2_124m_fsdp_mh", "gpt2_124m",
     dict(per_device_batch=8, seq_len=1024, steps=10,
          grad_sync=dict(fsdp_explicit=True,
                         wire_dtype="int8_multihop"))),
    ("gpt2_355m_fsdp", "gpt2_355m",
     dict(per_device_batch=2, seq_len=1024, steps=6,
          grad_sync=dict(fsdp_explicit=True))),
    # Explicit TP x FSDP on the 2-D ("data","model") mesh (ISSUE 13): the
    # BASELINE flagship with megatron column/row-split blocks + the
    # vocab-parallel embedding inside the FSDP shard_map — params + AdamW
    # moments at rest 1/(N*M) for TP-split tensors, per-layer
    # gather/scatter wire 1/M per replica, one model-axis psum per
    # residual join. Rows carry tp_psum_bytes_per_replica next to the
    # data-axis terms and the tp-psum-signature contract verdict. Needs
    # >= 2 chips (model=2 on one device fails the mesh build loudly; the
    # per-config guard records the skip).
    ("gpt2_355m_fsdp_tp", "gpt2_355m",
     dict(per_device_batch=2, seq_len=1024, steps=6,
          grad_sync=dict(fsdp_explicit=True),
          mesh_spec="data=-1,model=2")),
    # Serving offered-load arms (ISSUE 17): latency rows, not train
    # throughput — the `serving` marker routes them past measure_config to
    # run_serving (experiments/harness measure_serving /
    # measure_serving_continuous), and their value is tokens/sec. The
    # iteration/token pair at the SAME offered load and shapes is the
    # continuous-batching A/B the acceptance gate reads: token-granular
    # (slot pool + paged KV, requests join/leave between tokens) must beat
    # iteration-granular (form batch -> decode to completion -> repeat) on
    # BOTH tok/s and p99 — the p99 win is the point, a long request no
    # longer convoys the short ones behind it. The int8 arm adds the
    # paged-vs-dense KV byte ratio (>= 3x is the HBM claim); the fleet arm
    # runs 2 router-fronted replicas and KILLS one mid-run — every request
    # must still complete (seed-pinned resubmit) with zero recompiles.
    # mixed_want gives every request its own decode length (1..max_new,
    # seed-pinned identically on both arms) — the serving-shaped workload
    # where convoying actually hurts: the iteration arm must decode the
    # full max_new for the whole batch and only the wanted tokens count.
    ("serving_iter_gpt2", "gpt2_124m",
     dict(serving=dict(kind="iteration", n_requests=24, offered_rps=16.0,
                       buckets=(8, 16), rows=8, max_new_tokens=8,
                       mixed_want=True))),
    ("serving_token_gpt2", "gpt2_124m",
     dict(serving=dict(kind="token", n_requests=24, offered_rps=16.0,
                       buckets=(8, 16), rows=8, max_new_tokens=8,
                       mixed_want=True))),
    ("serving_token_int8", "gpt2_124m",
     dict(serving=dict(kind="token", n_requests=24, offered_rps=16.0,
                       buckets=(8, 16), rows=8, max_new_tokens=8,
                       mixed_want=True, kv_dtype="int8", page_size=8))),
    ("serving_fleet2", "gpt2_124m",
     dict(serving=dict(kind="token", n_requests=24, offered_rps=16.0,
                       buckets=(8, 16), rows=8, max_new_tokens=8,
                       mixed_want=True, replicas=2, kill_replica=True))),
)

def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _parse(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", default=4096, type=int,
                   help="per-device batch for the ResNet headline; 4096 "
                        "saturates the chip on CIFAR shapes, ~13% over 2048 "
                        "(2026-07-31, one v5e, not re-measured since; the "
                        "reference default 128 is dispatch-bound — see "
                        "experiments 'batch')")
    p.add_argument("--steps", default=20, type=int)
    p.add_argument("--repeats", default=3, type=int)
    p.add_argument("--quick", action="store_true",
                   help="headline config + its fp32 arm only (skip the "
                        "extra configs)")
    p.add_argument("--only", default=None,
                   help="comma-separated config labels to run, from "
                        "{headline, fp32} plus the EXTRA_CONFIGS labels "
                        "(e.g. --only resnet50,vit_b16): a chunk of the "
                        "matrix per invocation; every run that measured "
                        "something still appends to bench_history.jsonl")
    return p.parse_args(argv)


def _record_history(result: dict) -> None:
    """Append the full result (all configs) to the committed provenance log
    so every README table row is regenerable from JSON in the repo."""
    HISTORY_PATH.parent.mkdir(parents=True, exist_ok=True)
    entry = dict(result)
    entry["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(HISTORY_PATH, "a") as f:
        f.write(json.dumps(entry) + "\n")
    _log(f"bench: appended result to {HISTORY_PATH}")


def _error_json(metric: str, error: str, **extra) -> str:
    return json.dumps({"metric": metric, "value": 0.0,
                       "unit": "samples/sec/chip", "vs_baseline": 0.0,
                       "error": error, **extra})


def main(argv=None) -> int:
    args = _parse(argv)
    t_start = time.monotonic()
    headline_metric = \
        f"resnet18_cifar10_train_throughput_bf16_b{args.batch_size}"

    # --only parsing happens before the backend is touched: an unknown label
    # must fail loudly without ever claiming the chip.
    only = None
    if args.only:
        only = {s.strip() for s in args.only.split(",") if s.strip()}
        known = {"headline", "fp32"} | {l for l, _, _ in EXTRA_CONFIGS}
        unknown = sorted(only - known)
        if unknown or not only:
            print(_error_json(
                "bench_only_filter",
                (f"unknown --only labels {unknown}" if unknown else
                 f"--only {args.only!r} selects nothing")
                + f"; known: {sorted(known)}"))
            return 1
        if "fp32" in only:
            only.add("headline")  # vs_baseline is a ratio against headline

    import jax

    from distributed_pytorch_training_tpu.runtime import (
        enable_persistent_compile_cache, require_backend,
    )

    try:
        backend = require_backend()
    except RuntimeError as e:
        print(_error_json(headline_metric, f"backend: {e}"))
        return 1
    devices = jax.devices()
    n_chips = len(devices)
    _log(f"bench: backend {backend}: {n_chips}x {devices[0].device_kind}")
    enable_persistent_compile_cache()
    TELEMETRY_PATH.parent.mkdir(parents=True, exist_ok=True)
    _telemetry.configure(str(TELEMETRY_PATH),
                         meta={"entry": "bench.py",
                               "batch_size": args.batch_size})
    try:
        return _bench(args, only, headline_metric, devices, t_start)
    finally:
        _telemetry.reset()


def _bench(args, only, headline_metric, devices, t_start) -> int:
    from distributed_pytorch_training_tpu.experiments.harness import (
        measure_config, measure_serving, measure_serving_continuous,
    )

    n_chips = len(devices)

    def run(name, **kw):
        _log(f"bench: === {name} {kw} ===")
        t0 = time.perf_counter()
        # exposed-comm split only where collectives exist (>1 chip); the
        # capture is try/except'd inside measure_config — a failed trace
        # never fails a bench row
        kw.setdefault("comm_trace", n_chips > 1)
        r = measure_config(name, repeats=args.repeats, **kw)
        r["wall_s"] = round(time.perf_counter() - t0, 1)
        # the per-arm HLO contract verdict (analysis/hlo_rules.py) rides
        # every history row; a failing arm is loud in the log but still a
        # measurement — the contract gate is `analysis check`, not bench
        contract = (r.get("contracts") or {}).get("pass")
        c_str = {True: "ok", False: "VIOLATED", None: "unchecked"}[contract]
        _log(f"bench: {name} done in {r['wall_s']:.1f}s: "
             f"{r['samples_per_sec_chip']:.0f} samples/s/chip, "
             f"mfu={r['mfu_pct']}%, contracts={c_str}")
        sb = r.get("save_blocked_ms")
        if sb and "error" not in sb:
            _log(f"bench: {name} checkpoint stall A/B: sync "
                 f"{sb['sync_blocked_ms']}ms -> async "
                 f"{sb['async_blocked_ms']}ms blocked (snapshot "
                 f"{sb['snapshot_ms']}ms, bg write {sb['write_ms']}ms)")
        if contract is False:
            _log(f"bench: {name} CONTRACT VIOLATIONS: "
                 f"{r['contracts']['violations']}")
        elif contract is None:
            # a broken CHECKER must be distinguishable from a benign skip
            why = (r.get("contracts") or {}).get(
                "error", "no contracts recorded")
            _log(f"bench: {name} contract checker did not run: {why}")
        return r

    def run_serving(label, name, **skw):
        """One serving offered-load row (the `serving` marker arms): routes
        to measure_serving (iteration-granular) or
        measure_serving_continuous (token-granular slot pool) and logs the
        latency/throughput shape a serving row has instead of run()'s
        samples/sec/chip. recompiles_after_warmup != 0 is loud here and a
        hard exit in `serving bench` — bench records it as a measurement."""
        kind = skw.pop("kind", "token")
        _log(f"bench: === {label} serving/{kind} {skw} ===")
        t0 = time.perf_counter()
        if kind == "iteration":
            r = measure_serving(name, **skw)
        else:
            r = measure_serving_continuous(name, **skw)
        r["wall_s"] = round(time.perf_counter() - t0, 1)
        contract = (r.get("contracts") or {}).get("pass")
        c_str = {True: "ok", False: "VIOLATED", None: "unchecked"}[contract]
        _log(f"bench: {label} done in {r['wall_s']:.1f}s: "
             f"p50={r['p50_ms']}ms p99={r['p99_ms']}ms "
             f"{r.get('tokens_per_sec', 0.0):.1f} tok/s, "
             f"recompiles_after_warmup={r['recompiles_after_warmup']}, "
             f"contracts={c_str}")
        if r.get("ttft_p99_ms") is not None:
            _log(f"bench: {label} ttft p50={r['ttft_p50_ms']}ms "
                 f"p99={r['ttft_p99_ms']}ms")
        if r.get("kv_bytes_ratio") is not None:
            _log(f"bench: {label} paged KV {r['paged_kv_bytes']}B vs dense "
                 f"{r['dense_kv_bytes']}B ({r['kv_bytes_ratio']}x)")
        for rep, stats in (r.get("per_replica") or {}).items():
            _log(f"bench: {label} replica {rep}: served={stats['served']} "
                 f"alive={stats['alive']} p50={stats['p50_ms']}ms "
                 f"p99={stats['p99_ms']}ms")
        if r["recompiles_after_warmup"]:
            _log(f"bench: {label} RECOMPILED after warmup "
                 f"({r['recompiles_after_warmup']}x) — the zero-recompile "
                 "census is broken")
        if contract is False:
            _log(f"bench: {label} CONTRACT VIOLATIONS: "
                 f"{r['contracts']['violations']}")
        return r

    # Every selected config runs; one that raises is recorded here by label
    # and turns the exit code non-zero — measured configs are still reported.
    failed = []

    def attempt(label, fn):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - the boundary that reports it
            _log(f"bench: config {label} FAILED:\n" + traceback.format_exc())
            failed.append({"label": label,
                           "error": f"{type(e).__name__}: {e}"})
            return None

    # Headline: ResNet-18/CIFAR-10 (the reference's workload) in bf16 first —
    # an fp32-arm failure (bigger memory footprint under HIGHEST precision)
    # degrades vs_baseline to null, it does not forfeit the headline number.
    headline = fp32 = None
    if only is None or "headline" in only:
        # ckpt_ab: the headline row carries save_blocked_ms — the
        # sync-vs-async checkpoint stall A/B on the real state (two
        # throwaway saves; cheap at resnet18 size, and only here so the
        # big-model arms don't pay double disk writes)
        headline = attempt("headline", lambda: run(
            "resnet18", per_device_batch=args.batch_size, steps=args.steps,
            bf16=True, ckpt_ab=True))
    if headline is not None and (only is None or "fp32" in only):
        fp32 = attempt("fp32", lambda: run(
            "resnet18", per_device_batch=args.batch_size, steps=args.steps,
            bf16=False))

    extras = []
    # An explicit --only selection overrides --quick: a requested config must
    # run (or fail loudly), never be silently dropped by an unrelated flag.
    if args.quick and only is not None:
        _log("bench: --only given; ignoring --quick for the selected labels")
    if (headline is not None or only) and (not args.quick or only is not None):
        # The rest of the BASELINE matrix (BASELINE.json:9-12) and the arms
        # later PRs added; bf16 by default, a config may override (fp32 arms)
        for label, name, kw in EXTRA_CONFIGS:
            if only is not None and label not in only:
                continue
            if "serving" in kw:
                r = attempt(label, lambda: run_serving(
                    label, name, **dict(kw["serving"])))
            else:
                r = attempt(label, lambda: run(name, **{"bf16": True, **kw}))
            if r is not None:
                r["label"] = label
                extras.append(r)
        by_label = {r.get("label"): r for r in extras}
        s_it = by_label.get("serving_iter_gpt2")
        s_tok = by_label.get("serving_token_gpt2")
        if s_it and s_tok:
            # the continuous-batching A/B as a measured sentence: same
            # offered load, same shapes, token-granular vs iteration-
            # granular (the history rows carry the full distributions)
            win = (s_tok.get("tokens_per_sec", 0.0)
                   > s_it.get("tokens_per_sec", 0.0)
                   and s_tok["p99_ms"] < s_it["p99_ms"])
            _log("bench: serving A/B: token-granular "
                 f"{s_tok.get('tokens_per_sec', 0.0):.1f} tok/s "
                 f"p99={s_tok['p99_ms']}ms vs iteration-granular "
                 f"{s_it.get('tokens_per_sec', 0.0):.1f} tok/s "
                 f"p99={s_it['p99_ms']}ms -> "
                 + ("token-granular wins both" if win else "no win"))

    common = {
        "n_chips": n_chips,
        "platform": devices[0].platform,
        "chip": devices[0].device_kind,
        "configs_failed": failed,
        "bench_seconds": round(time.monotonic() - t_start, 1),
        # where this invocation's typed event stream (save_blocked spans,
        # wire counters) landed — `telemetry summary <path>` reads it
        "telemetry_path": str(TELEMETRY_PATH),
    }
    if headline is not None:
        result = {
            "metric": headline_metric,
            "value": headline["samples_per_sec_chip"],
            "unit": "samples/sec/chip",
            # True AMP curve: bf16 vs HIGHEST-precision fp32, same chip.
            "vs_baseline": (round(headline["samples_per_sec"]
                                  / fp32["samples_per_sec"], 3)
                            if fp32 else None),
            "per_device_batch": args.batch_size,
            "mfu_pct": headline["mfu_pct"],
            "chip_peak_tflops_bf16": headline["chip_peak_tflops_bf16"],
            "tflops_per_sec": headline["tflops_per_sec"],
            "fp32_samples_per_sec_chip": (fp32["samples_per_sec_chip"]
                                          if fp32 else None),
            "fp32_true_precision": fp32 is not None,
            "configs": [c for c in [headline, fp32] + extras if c],
            **common,
        }
    elif extras:
        # a chunked --only run without the headline: report the first
        # selected config; every config is in `configs`
        first = extras[0]
        if str(first.get("mode", "")).startswith("serving"):
            # serving rows are latency rows: tokens/sec, no MFU
            metric = f"{first['label']}_serving_tokens_per_sec"
            value, unit = first.get("tokens_per_sec", 0.0), "tokens/sec"
        else:
            prec = "bf16" if first.get("bf16") else "fp32"
            metric = f"{first['label']}_train_throughput_{prec}"
            value, unit = first["samples_per_sec_chip"], "samples/sec/chip"
        result = {"metric": metric, "value": value, "unit": unit,
                  "vs_baseline": None, "mfu_pct": first.get("mfu_pct"),
                  "only": sorted(only), "configs": extras, **common}
    else:
        print(_error_json(
            headline_metric if only is None else "bench_only_chunk",
            "no selected config produced a measurement",
            configs_failed=failed), flush=True)
        return 1

    print(json.dumps(result), flush=True)
    if devices[0].platform == "tpu":
        # the committed log holds device rows only; a CPU run asked for by
        # name is a functional check and prints its line, nothing more
        _record_history(result)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

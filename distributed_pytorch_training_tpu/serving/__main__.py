"""``python -m distributed_pytorch_training_tpu.serving`` — serve a
manifest-verified checkpoint: a causal LM through the token server, BERT
and image models through the forward engine.

Also installed as the ``serving`` console script (pyproject.toml).

Commands:
  smoke [--ckpt-dir D] [--prompt 12,7,99 | --prompt-len N]
      One-shot: build the engine (restoring the newest verified checkpoint
      when --ckpt-dir is given; random-init weights otherwise — a smoke of
      the serving PATH, loudly labeled, never of a served model), serve a
      handful of synthetic prompts, print the generated tokens and the
      checkpoint provenance (label + manifest tree_digest). A causal LM
      goes through the token server's scheduler (slot engine + paged KV,
      serving/continuous.py), BERT and image models through the forward
      engine (serving/engine.py).
  bench [--requests N] [--offered-load RPS] [--json]
      A causal LM at fixed offered load through the token server: a
      deterministic load generator submits mixed-length prompts on a 1/RPS
      cadence; reports p50/p99 latency, TTFT, achieved request/token
      throughput, paged-vs-dense KV bytes, the compile census (zero
      recompiles after warmup is the contract), and the serving
      HLO-contract verdict
      (serving/loadtest.py::measure_serving_continuous).
      --replicas N spreads it over N in-process replicas behind the
      stdlib router and --kill-replica injects one replica death mid-load
      (every request must still complete, recompiles must stay 0).
      --draft MODEL arms speculative decoding (draft proposes --draft-k
      tokens, target verifies the K+1 window in one forward; the row
      gains accept_ratio and the stream stays bitwise the plain arm's);
      --shared-frac F gives F of the requests one shared prompt — after
      the primer each admits with ZERO prefill (prefill_skips + the
      warm/cold TTFT split are the receipts).
  serve [--port P] [--kv-dtype int8] [--page-size N]
      ONE long-lived continuous-batching replica: POST /generate
      ({"tokens": [...], "max_new_tokens"?, "temperature"?, "top_p"?,
      "seed"?, "want_logits"?}) blocks until the tokens are out; /healthz
      + /metrics ride --metrics-port (the router reads both). SIGTERM
      drains: admitted requests complete, then exit 0.
  fleet [--replicas N] [--port BASE] [--federation-port P]
      N `serve` replicas as supervised child processes (replica r on port
      BASE+r, metrics on --metrics-port+r): a replica that dies is
      relaunched within budget, SIGTERM drains the whole fleet, and
      --federation-port serves the ONE merged /metrics dashboard
      (resilience/fleet.py::ServingFleet).

Drain: SIGTERM closes the queue, DRAINS it (accepted requests complete, new
ones are refused), flushes a telemetry flight, and exits 0. Any abnormal
exit flushes a flight too.

Platform: like train.py, a CPU run must be asked for by name
(JAX_PLATFORMS=cpu, which also gets the 8-device virtual mesh); otherwise
the backend must be a TPU or the CLI raises (runtime.require_backend).
`fleet` children are pinned to virtual CPU meshes by the launcher
(resilience/fleet.py) — a CPU harness, one chip per replica is not built.

Checkpoint templates: orbax restores against the training run's full
TrainState structure, so a checkpoint written under --zero1 /
--fsdp-explicit / an int8 wire needs the same flags here (exactly the
resume-hint contract train.py documents).
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
from pathlib import Path
from typing import List, Optional

import numpy as np


def _parse_buckets(text: str) -> tuple:
    try:
        out = tuple(int(b) for b in text.split(",") if b.strip())
    except ValueError:
        out = ()
    if not out:
        raise SystemExit(f"serving: --buckets expects e.g. '16,32,64', "
                         f"got {text!r}")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="serving", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("command", choices=["smoke", "bench", "serve", "fleet"])
    p.add_argument("--model", default="gpt2_124m")
    p.add_argument("--ckpt-dir", default=None,
                   help="serve the newest manifest-verified checkpoint "
                        "from this directory (omit: random-init smoke)")
    p.add_argument("--serve-dtype", default="fp32",
                   choices=["fp32", "bf16", "int8"])
    p.add_argument("--mesh", default=None,
                   help="mesh spec, e.g. 'data=4,model=2' (default: pure "
                        "DP over all devices) — model>1 shards the served "
                        "weights over the model axis via the model's "
                        "GSPMD partition rules (multi-chip serving of "
                        "models too big for one chip); validate_mesh "
                        "rejects axes the served model cannot use")
    p.add_argument("--buckets", default="16,32",
                   help="prompt-length bucket ladder, e.g. '32,64,128'")
    p.add_argument("--rows", type=int, default=8,
                   help="batch rows per engine cycle")
    p.add_argument("--max-new-tokens", type=int, default=8)
    p.add_argument("--model-overrides", default="",
                   help="architecture overrides, e.g. "
                        "'hidden_dim=64,depth=2,num_heads=2'")
    # checkpoint TEMPLATE flags (must mirror the training run's — orbax
    # validates the TrainState structure, and the optimizer chain's
    # structure depends on these: see serving/build.py::build_serving_engine)
    p.add_argument("--zero1", action="store_true")
    p.add_argument("--fsdp-explicit", action="store_true")
    p.add_argument("--wire-dtype", default="fp32")
    p.add_argument("--bucket-cap-mb", type=float, default=0.0)
    p.add_argument("--optimizer", default="auto",
                   choices=["auto", "sgd", "adamw"],
                   help="the training run's optimizer (auto: adamw for "
                        "LMs, sgd for vision — train.py's own default is "
                        "sgd everywhere)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=5e-4)
    # smoke
    p.add_argument("--prompt", default=None,
                   help="smoke: comma-separated token ids")
    p.add_argument("--prompt-len", type=int, default=12,
                   help="smoke: synthetic prompt length when no --prompt")
    # the token server (serve, fleet, bench, smoke of a causal LM)
    p.add_argument("--replicas", type=int, default=1,
                   help="bench: in-process replicas behind the router; "
                        "fleet: serve children to supervise")
    p.add_argument("--kv-dtype", default="fp32", choices=["fp32", "int8"],
                   help="paged KV pool dtype (int8: per-row quantized "
                        "pages through the grad-sync int8 grid)")
    p.add_argument("--page-size", type=int, default=8,
                   help="positions per KV page (divide the top bucket + "
                        "max-new for a padding-free pool)")
    p.add_argument("--kill-replica", action="store_true",
                   help="bench --replicas>1: kill replica 0 "
                        "mid-load; the router must resubmit its requests")
    # speculative decoding + prefix-resident admission (bench)
    p.add_argument("--draft", default=None, metavar="MODEL",
                   help="bench: arm speculative decoding "
                        "with this (random-init, smaller) draft LM — "
                        "fp32 KV only; the emitted streams stay bitwise "
                        "the plain row's (acceptance is exact match)")
    p.add_argument("--draft-k", type=int, default=4,
                   help="draft tokens proposed per slot per verify round")
    p.add_argument("--shared-frac", type=float, default=0.0,
                   help="bench: fraction of requests that "
                        "share ONE page-aligned prompt — after the "
                        "primer, each admits with zero prefill dispatch "
                        "(prefill_skips + warm/cold TTFT in the row)")
    p.add_argument("--no-prefix-skip", action="store_true",
                   help="disable the prefix-resident admission fast path "
                        "(shared pages still dedupe; admission prefills)")
    p.add_argument("--port", type=int, default=8100,
                   help="serve: /generate port (0 = ephemeral, logged); "
                        "fleet: base port — replica r listens on base+r")
    p.add_argument("--federation-port", type=int, default=None,
                   help="fleet: one merged /metrics page over the "
                        "replicas' ports (needs --metrics-port)")
    # bench
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--offered-load", type=float, default=16.0,
                   help="bench: offered request rate (req/s)")
    p.add_argument("--mixed-want", action="store_true",
                   help="bench: per-request decode lengths (1..max_new, "
                        "seed-pinned); a slot retires at its want and "
                        "only the wanted tokens are credited")
    p.add_argument("--output-dir", default="./serving_out",
                   help="telemetry stream + flight directory")
    p.add_argument("--no-telemetry", action="store_true")
    p.add_argument("--metrics-port", default=None, type=int,
                   help="serve live /metrics + /healthz on this port "
                        "(+rank offset); default DPT_METRICS_PORT env, "
                        "else off (zero threads)")
    p.add_argument("--json", action="store_true", dest="as_json")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    buckets = _parse_buckets(args.buckets)

    # A CPU run asked for by name gets the 8-device virtual mesh (the
    # analysis CLI's recipe — `serving smoke` then exercises real
    # cross-device batch sharding with no TPU); anything else must be a TPU.
    from ..analysis.__main__ import _ensure_test_mesh

    _ensure_test_mesh()

    import jax

    from .. import telemetry
    from ..runtime import require_backend
    from ..utils.logging import log_main

    backend = require_backend()
    log_main(f"serving: backend={backend}, {len(jax.devices())}x "
             f"{jax.devices()[0].device_kind}")

    tele_rank = telemetry.rank_identity(jax.process_index())
    if not args.no_telemetry and telemetry.should_stream(tele_rank):
        Path(args.output_dir).mkdir(parents=True, exist_ok=True)
        telemetry.configure(
            str(Path(args.output_dir)
                / telemetry.stream_filename(tele_rank)),
            rank=tele_rank, gen=telemetry.generation_identity(),
            meta={"entry": "serving", "model": args.model,
                  "serve_dtype": args.serve_dtype,
                  "buckets": list(buckets)})
    # live /metrics + /healthz (telemetry/metrics_http.py): the serving
    # replica's scrape surface — the serving phases' histograms feed the
    # same phase metric the training loop's dispatch does, and the healthz
    # fence counts prefills as progress. Off (default) starts zero threads.
    metrics_port = telemetry.resolve_metrics_port(args.metrics_port,
                                                  tele_rank)
    if metrics_port and telemetry.is_configured():
        # None on a bind failure (stderr-noted): the live surface never
        # takes the serving process down. backend stamps dpt_build_info
        # (the federated-scrape identity satellite, ISSUE 15).
        if telemetry.start_metrics_server(
                metrics_port, telemetry.get(),
                backend=backend) is not None:
            log_main(f"serving: /metrics + /healthz on :{metrics_port}")

    try:
        return _run(args, buckets)
    except BaseException as e:
        # every abnormal serving exit leaves a postmortem flight (the
        # train.py contract); clean SystemExit(0) is not abnormal
        if not (isinstance(e, SystemExit) and e.code in (0, None)):
            telemetry.flush_flight(
                cause=f"{type(e).__name__}: {e}",
                detail="serving abnormal exit",
                rc=e.code if isinstance(e, SystemExit) else 1)
        raise
    finally:
        # guarded on the module having loaded: the metrics-off path never
        # imports metrics_http at all (its zero-cost-when-off contract)
        if "distributed_pytorch_training_tpu.telemetry.metrics_http" \
                in sys.modules:
            telemetry.stop_metrics_server()
        telemetry.reset()


def _run(args, buckets) -> int:
    import jax

    from .. import telemetry
    from ..training import TrainConfig
    from ..utils.config import parse_model_overrides
    from ..utils.logging import log_main
    from .batching import RequestQueue, drain, serve_forever
    from .build import build_serving_engine, build_slot_engine, has_cache
    from .continuous import serve_continuous
    from .loadtest import measure_serving_continuous

    overrides = (parse_model_overrides(args.model_overrides)
                 if args.model_overrides else None)
    train_config = TrainConfig(
        seed=0, zero1=args.zero1, fsdp_explicit=args.fsdp_explicit,
        wire_dtype=args.wire_dtype, bucket_cap_mb=args.bucket_cap_mb)
    # Warm-restart compilation cache: a restarted or autoscaled serving
    # replica re-AOT-compiles its whole bucket ladder — with the persistent
    # cache on, those compiles load from disk instead (the engine's
    # per-program `compile` telemetry spans are the cold-vs-warm
    # instrument). runtime.dist owns where it lives; "auto" refuses XLA:CPU.
    from ..runtime import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    if args.command == "serve":
        return _serve(args, buckets, overrides, train_config)
    if args.command == "fleet":
        return _fleet(args, buckets)

    if args.command == "bench":
        row = measure_serving_continuous(
            model_name=args.model, n_requests=args.requests,
            offered_rps=args.offered_load, buckets=buckets, rows=args.rows,
            max_new_tokens=args.max_new_tokens, kv_dtype=args.kv_dtype,
            page_size=args.page_size, mixed_want=args.mixed_want,
            replicas=args.replicas,
            kill_replica=args.kill_replica, model_overrides=overrides,
            ckpt_dir=args.ckpt_dir, seed=args.seed,
            optimizer=args.optimizer, momentum=args.momentum,
            weight_decay=args.weight_decay, train_config=train_config,
            mesh_spec=args.mesh, draft_model=args.draft,
            draft_k=args.draft_k, shared_frac=args.shared_frac,
            prefix_skip=not args.no_prefix_skip)
        if args.as_json:
            print(json.dumps(row, sort_keys=True, default=str))
        else:
            spec = (f", draft={row['draft']} k={row['draft_k']} "
                    f"accept {row['accept_ratio']} "
                    f"({row['accepted_per_verify']} tok/verify)"
                    if row.get("draft") else "")
            skip = (f", {row['prefill_skips']} prefill skips / "
                    f"{row['tail_resumes']} tail resumes"
                    + (f" (ttft warm {row['ttft_warm_p50_ms']}ms vs "
                       f"cold {row['ttft_cold_p50_ms']}ms)"
                       if "ttft_warm_p50_ms" in row else "")
                    if row.get("prefill_skips") or row.get("tail_resumes")
                    else "")
            log_main(
                f"serving bench [x{row['replicas']}]: "
                f"{row['model']} kv={row['kv_dtype']} "
                f"p50 {row['p50_ms']}ms p99 {row['p99_ms']}ms "
                f"ttft p50 {row['ttft_p50_ms']}ms at "
                f"{row['achieved_rps']}/{row['offered_rps']} req/s "
                f"({row['tokens_per_sec']} tok/s), KV "
                f"{row['paged_kv_bytes']}B vs dense "
                f"{row['dense_kv_bytes']}B ({row['kv_bytes_ratio']}x), "
                f"{row['compiles']} compiles "
                f"({row['recompiles_after_warmup']} after warmup, "
                f"{row['replica_deaths']} replica deaths)"
                + spec + skip)
            if row.get("contracts", {}).get("pass") is False:
                log_main(f"serving bench: CONTRACT VIOLATIONS: "
                         f"{row['contracts']['violations']}")
        return 0 if row.get("recompiles_after_warmup") == 0 else 1

    # -- smoke ---------------------------------------------------------------
    # a causal LM smokes its one server, the token server; a model without
    # a cache the forward engine and its loop
    causal = has_cache(args.model, overrides)
    common = dict(
        buckets=buckets, rows=args.rows,
        max_new_tokens=args.max_new_tokens, serve_dtype=args.serve_dtype,
        model_overrides=overrides, ckpt_dir=args.ckpt_dir,
        train_config=train_config, seed=args.seed,
        optimizer=args.optimizer, momentum=args.momentum,
        weight_decay=args.weight_decay, mesh_spec=args.mesh)
    if causal:
        engine, mesh = build_slot_engine(
            jax.devices(), args.model, kv_dtype=args.kv_dtype,
            page_size=args.page_size,
            prefix_skip=not args.no_prefix_skip, **common)
    else:
        engine, mesh = build_serving_engine(jax.devices(), args.model,
                                            **common)
    if engine.checkpoint_info:
        info = engine.checkpoint_info
        log_main(f"serving: checkpoint label={info['label']} "
                 f"step={info['step']} verified={info['verified']} "
                 f"tree_digest={info['tree_digest']}")
    else:
        log_main("serving: NOTE: random-init weights (no --ckpt-dir) — "
                 "this smokes the serving path, not a trained model")

    if not causal and not engine.is_token:
        rng = np.random.RandomState(args.seed)
        logits = engine.serve_images(
            rng.randint(0, 256, (2, 32, 32, 3)).astype(np.uint8),
            mean=(0.4914, 0.4822, 0.4465), std=(0.247, 0.243, 0.262))
        log_main(f"serving smoke: {logits.shape[0]} images -> logits "
                 f"{logits.shape}, top-1 {logits.argmax(-1).tolist()}")
        return 0

    if args.prompt:
        prompts = [np.asarray([int(t) for t in args.prompt.split(",")],
                              np.int32)]
    else:
        rng = np.random.RandomState(args.seed)
        # ids from the SERVED model's vocabulary (a chip's share of a model
        # holds a slice of it)
        vocab = int(engine.model.vocab_size)
        prompts = [rng.randint(0, vocab, n).astype(np.int32)
                   for n in (args.prompt_len, max(args.prompt_len // 2, 1),
                             min(args.prompt_len * 2, max(buckets)))]

    # the production wiring in miniature: queue + worker thread + SIGTERM
    # drain — smoke exercises the same path a real frontend would use
    queue = RequestQueue(buckets)
    stop = threading.Event()

    def on_sigterm(signum, frame):
        log_main("serving: SIGTERM — draining the queue, then exiting")
        stop.set()
        telemetry.flush_flight(cause="sigterm drain",
                               detail="serving graceful shutdown", rc=0)

    prev = signal.signal(signal.SIGTERM, on_sigterm)
    try:
        worker = threading.Thread(
            target=serve_continuous if causal else serve_forever,
            args=(engine, queue, stop), kwargs={"log": log_main},
            daemon=True)
        worker.start()
        reqs = [queue.submit(p) for p in prompts]
        for req, prm in zip(reqs, prompts):
            res = req.result(timeout=600.0)
            took = (f"first token after {res.queue_wait_s * 1e3:.1f}ms, "
                    f"decode {res.decode_s * 1e3:.1f}ms" if causal
                    else f"forward {res.prefill_s * 1e3:.1f}ms")
            log_main(
                f"serving smoke: prompt[{len(prm)} tok] bucket={res.bucket} "
                f"-> {res.tokens.tolist() if res.tokens.size else '[]'} "
                f"({took})")
        stop.set()
        worker.join(timeout=60.0)
        if not causal:
            # drain is idempotent here (queue already empty) — it exists so
            # a SIGTERM mid-smoke still completes accepted work before exit
            # (the scheduler's own loop drains before it returns)
            drain(engine, queue, log=log_main)
    finally:
        signal.signal(signal.SIGTERM, prev)
    log_main(f"serving smoke: ok ({engine.compiles} compiles)")
    return 0


def _serve(args, buckets, overrides, train_config) -> int:
    """ONE long-lived continuous-batching replica behind stdlib HTTP:
    POST /generate blocks the handler thread on the request's result
    (ThreadingHTTPServer gives each request its own thread; the slot
    scheduler worker is the single engine caller). SIGTERM drains."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import jax

    from .. import telemetry
    from ..utils.logging import log_main
    from .batching import RequestQueue
    from .build import build_slot_engine

    engine, _ = build_slot_engine(
        jax.devices(), args.model, buckets=buckets, rows=args.rows,
        max_new_tokens=args.max_new_tokens, kv_dtype=args.kv_dtype,
        page_size=args.page_size, model_overrides=overrides,
        ckpt_dir=args.ckpt_dir, train_config=train_config, seed=args.seed,
        optimizer=args.optimizer, momentum=args.momentum,
        weight_decay=args.weight_decay, mesh_spec=args.mesh)
    engine.warmup()
    log_main(f"serving: slot engine ready — {engine.compiles} programs, "
             f"kv={args.kv_dtype} pages of {args.page_size} "
             f"({engine.paged_bytes()}B paged vs "
             f"{engine.dense_baseline_bytes()}B dense)")
    queue = RequestQueue(buckets)
    sched = engine.scheduler_cls(engine, queue)
    stop = threading.Event()
    worker = threading.Thread(target=sched.run, args=(stop,),
                              kwargs={"log": log_main}, daemon=True)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *a):  # request logging rides telemetry
            pass

        def _reply(self, code: int, body: dict) -> None:
            data = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/healthz":
                # the metrics port's /healthz is the richer step-fence
                # verdict; this one answers 'is the replica accepting'
                self._reply(200 if not stop.is_set() else 503,
                            {"draining": stop.is_set(),
                             "served": sched.served})
            else:
                self._reply(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            if self.path != "/generate":
                self._reply(404, {"error": f"no route {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n).decode() or "{}")
                tokens = np.asarray(body["tokens"], np.int32)
            except (KeyError, ValueError, TypeError) as e:
                self._reply(400, {"error": f"bad request: {e}"})
                return
            try:
                req = queue.submit(
                    tokens, max_new_tokens=body.get("max_new_tokens"),
                    temperature=float(body.get("temperature", 0.0)),
                    top_p=float(body.get("top_p", 1.0)),
                    seed=body.get("seed"),
                    denoising_steps=body.get("denoising_steps"))
                res = req.result(timeout=600.0)
            except Exception as e:  # noqa: BLE001 - one request, one reply
                self._reply(503, {"error": f"{type(e).__name__}: {e}"})
                return
            out = {"tokens": res.tokens.tolist(), "bucket": res.bucket,
                   "queue_wait_ms": round(res.queue_wait_s * 1e3, 3),
                   "decode_ms": round(res.decode_s * 1e3, 3)}
            if body.get("want_logits"):
                out["last_logits"] = [float(v) for v in res.last_logits]
            self._reply(200, out)

    httpd = ThreadingHTTPServer(("127.0.0.1", args.port), Handler)
    port = httpd.server_address[1]

    def on_sigterm(signum, frame):
        log_main("serving: SIGTERM — draining the slot pool, then exiting")
        stop.set()

    prev = signal.signal(signal.SIGTERM, on_sigterm)
    worker.start()
    srv = threading.Thread(target=httpd.serve_forever, daemon=True)
    srv.start()
    log_main(f"serving: POST /generate on :{port} — SIGTERM drains")
    try:
        while not stop.wait(0.2):
            pass
    except KeyboardInterrupt:
        stop.set()
    finally:
        signal.signal(signal.SIGTERM, prev)
        queue.close()
        worker.join(timeout=600.0)
        httpd.shutdown()
    telemetry.flush_flight(cause="sigterm drain",
                           detail="serving replica graceful shutdown",
                           rc=0)
    log_main(f"serving: replica drained ({sched.served} served, "
             f"{engine.compiles} compiles)")
    return 0


def _fleet(args, buckets) -> int:
    """N `serve` replicas as supervised children (ServingFleet): ports
    base+r, metrics base+r (the child env's rank stamp applies the offset
    — the argv passes the BASE, resolve_metrics_port adds the rank),
    relaunch-on-death, SIGTERM drains the whole fleet."""
    from ..resilience.fleet import ServingFleet
    from ..telemetry.recorder import ALL_RANKS_ENV
    from ..utils.logging import log_main

    base = int(args.port)
    mbase = args.metrics_port

    def argv_for(rank: int, generation: int):
        argv = [sys.executable, "-m",
                "distributed_pytorch_training_tpu.serving", "serve",
                "--model", args.model, "--buckets",
                ",".join(str(b) for b in buckets),
                "--rows", str(args.rows),
                "--max-new-tokens", str(args.max_new_tokens),
                "--kv-dtype", args.kv_dtype,
                "--page-size", str(args.page_size),
                "--port", str(base + rank),
                "--output-dir",
                str(Path(args.output_dir) / f"replica{rank}"),
                "--seed", str(args.seed)]
        if args.model_overrides:
            argv += ["--model-overrides", args.model_overrides]
        if args.ckpt_dir:
            argv += ["--ckpt-dir", args.ckpt_dir]
        if args.mesh:
            argv += ["--mesh", args.mesh]
        if mbase:
            argv += ["--metrics-port", str(int(mbase))]
        if args.no_telemetry:
            argv += ["--no-telemetry"]
        return argv

    fleet = ServingFleet(
        argv_for, replicas=args.replicas,
        metrics_ports=([int(mbase) + r for r in range(args.replicas)]
                       if mbase else None),
        federation_port=args.federation_port,
        log_dir=Path(args.output_dir) / "fleet_logs",
        # every replica streams + serves /metrics, not just rank 0 —
        # the federation page must carry all of them
        env_extra={ALL_RANKS_ENV: "1"},
        log=log_main)
    stop = threading.Event()

    def on_sigterm(signum, frame):
        log_main("serving fleet: SIGTERM — draining every replica")
        stop.set()

    prev = signal.signal(signal.SIGTERM, on_sigterm)
    try:
        fleet.start()
        log_main(f"serving fleet: {args.replicas} replicas on ports "
                 f"{[base + r for r in range(args.replicas)]}")
        fleet.run(stop)
    finally:
        signal.signal(signal.SIGTERM, prev)
    report = fleet.report()
    if args.as_json:
        print(json.dumps(report, sort_keys=True, default=str))
    else:
        for rep in report["per_replica"]:
            log_main(f"serving fleet: replica {rep['rank']} — "
                     f"{rep['relaunches']} relaunches, "
                     f"rc history {rep['rc_history']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

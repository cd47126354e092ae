"""The load test behind the CLI's `bench` subcommand: a generator that
submits seeded mixed-length prompts at a FIXED offered rate to the token
server (engines built through `serving/build.py`, behind the `Router`), and
the row it prints (latency percentiles, achieved rate, compile census,
contract verdict). `chip_smoke.py` phase 3 serves a trained checkpoint
through it. The numbers that decide a PR come from `benchmark/`
(BENCHMARK.json), not from here."""

from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import numpy as np

from .build import build_slot_engine, build_spec_engine, has_cache


def measure_serving_continuous(model_name: str = "gpt2_124m",
                               n_requests: int = 24,
                               offered_rps: float = 16.0,
                               buckets: Sequence[int] = (8, 16),
                               rows: int = 8, max_new_tokens: int = 8,
                               kv_dtype: str = "fp32", page_size: int = 8,
                               mixed_want: bool = False,
                               replicas: int = 1,
                               kill_replica: bool = False,
                               temperature: float = 0.0, top_p: float = 1.0,
                               draft_model: Optional[str] = None,
                               draft_k: int = 4,
                               shared_frac: float = 0.0,
                               prefix_skip: bool = True,
                               devices: Optional[Sequence[jax.Device]] = None,
                               model_overrides: Optional[dict] = None,
                               ckpt_dir: Optional[str] = None, seed: int = 0,
                               optimizer: str = "auto",
                               momentum: float = 0.9,
                               weight_decay: float = 5e-4,
                               train_config=None,
                               mesh_spec: Optional[str] = None) -> dict:
    """Serving a causal LM at FIXED offered load — the row that `serving
    bench` prints. A load generator submits ``n_requests`` mixed-length
    prompts on a deterministic 1/``offered_rps`` cadence; per-request
    latency is submit -> result. Offered load is what the schedule ASKS
    for; ``achieved_rps`` is what the server absorbed — an overloaded
    server shows the gap honestly instead of averaging it away.
    ``mixed_want`` gives each request its own number of tokens to ask for
    (1..max_new, seed-pinned): a slot retires at its want, and only the
    wanted tokens are emitted and credited.

    ``replicas`` in-process slot engines sit behind the stdlib `Router`
    (least-depth dispatch, resubmit-on-death); ``kill_replica=True``
    injects one replica death mid-load — the acceptance drill: every
    request still completes, the survivors absorb the resubmissions, and
    the compile census stays at warmup (``recompiles_after_warmup`` must
    be 0 across joins, leaves, AND the death). The row also carries the
    paged pool's HBM bytes against the dense fp32 baseline
    (``kv_bytes_ratio`` — the int8-paged >= 3x claim is a recorded
    number, not prose) and per-request TTFT percentiles (prefill emits
    token #0, so TTFT is an admission-latency instrument).

    ``draft_model`` arms speculative decoding (fp32-only): each replica
    becomes a SpeculativeEngine + SpeculativeScheduler pair, and the row
    grows ``accept_ratio`` / ``accepted_per_verify`` / ``spec_rounds`` —
    the emitted streams stay BITWISE what the plain row emits (PARITY.md:
    acceptance is exact match), so the A/B is pure speed.
    ``shared_frac`` arms prefix-resident admission: that fraction of
    requests carry one identical page-aligned prompt, and the row grows
    ``prefill_skips`` / ``tail_resumes`` plus a warm/cold TTFT split —
    the zero-prefill admission claim as recorded numbers.
    """
    from .router import InProcessReplica, Router

    if not has_cache(model_name, model_overrides):
        # before any engine is built: the token server would refuse it
        # only after placing its weights
        raise ValueError(
            f"serving bench drives causal LMs through the token server; "
            f"{model_name} has no cache — `serving smoke` serves it "
            "through the forward engine (serving.engine.InferenceEngine)")
    if draft_model is not None and kv_dtype != "fp32":
        # fail at the bench boundary with the bench's vocabulary, not
        # three layers down in SpeculativeEngine.__init__
        raise ValueError(
            f"--draft needs kv_dtype=fp32 (got {kv_dtype}): the verify "
            "window's in-view rows are fresh fp32 while the int8 path "
            "reads dequantized page bytes — the bitwise pin would break")
    devices = list(devices) if devices is not None else jax.devices()
    # Each replica gets its own DISJOINT device slice — the fleet
    # topology (replicas never share chips), and a hard requirement
    # in-process: the row-sharded decode step carries collectives, and
    # two schedulers racing collective programs over OVERLAPPING devices
    # deadlock in the CPU backend's rendezvous.
    per = len(devices) // replicas
    slices = ([devices[i * per:(i + 1) * per] for i in range(replicas)]
              if replicas > 1 and per >= 1 else [devices] * replicas)
    engines = []
    for i in range(replicas):
        common = dict(
            buckets=buckets, rows=rows, max_new_tokens=max_new_tokens,
            page_size=page_size, prefix_skip=prefix_skip,
            model_overrides=model_overrides, ckpt_dir=ckpt_dir, seed=seed,
            optimizer=optimizer, momentum=momentum,
            weight_decay=weight_decay, train_config=train_config,
            mesh_spec=mesh_spec)
        if draft_model is not None:
            # the draft inherits the target's overrides: a vocab override
            # must hit BOTH sides (acceptance compares token ids)
            engine, _ = build_spec_engine(
                slices[i], model_name, draft_model, draft_k=draft_k,
                draft_overrides=model_overrides, **common)
        else:
            engine, _ = build_slot_engine(
                slices[i], model_name, kv_dtype=kv_dtype, **common)
        engine.warmup()
        engines.append(engine)
    compiles_warm = [e.compiles for e in engines]

    rng = np.random.RandomState(seed)
    vocab = int(getattr(engines[0].model, "vocab_size", 0)) or 256
    lens = [int(rng.randint(1, max(engines[0].config.buckets) + 1))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, max(vocab, 2), n).astype(np.int32)
               for n in lens]
    # a slot retires at its want and the freed capacity admits the next
    # request
    wants = ([int(rng.randint(1, max_new_tokens + 1))
              for _ in range(n_requests)] if mixed_want
             else [max_new_tokens] * n_requests)
    # prefix-resident arm: ``shared_frac`` of the requests carry ONE
    # identical page-aligned prompt. The first such request on a replica
    # prefills and registers the pages; every later one finds the whole
    # prefix resident and admits with ZERO prefill dispatch
    # (``prefill_skips`` is the census, the warm/cold TTFT split below is
    # the latency receipt). The shared indices are rng-spread over the
    # schedule so warm requests face the same queue depths cold ones do —
    # the extra draws come AFTER the lens/prompts/wants stream, which a
    # ``shared_frac`` therefore leaves as it was.
    shared_idx: set = set()
    if shared_frac > 0:
        n_shared = int(round(shared_frac * n_requests))
        top = max(engines[0].config.buckets)
        shared_len = min(max(page_size, top // page_size * page_size), top)
        shared_prompt = rng.randint(0, max(vocab, 2),
                                    shared_len).astype(np.int32)
        if n_shared >= 1:
            shared_idx = set(
                int(j) for j in rng.choice(n_requests, size=n_shared,
                                           replace=False))
            for j in shared_idx:
                prompts[j] = shared_prompt

    router = Router([InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)])
    kill_at = n_requests // 3 if (kill_replica and replicas > 1) else None
    gap = 1.0 / max(offered_rps, 1e-9)
    reqs, sub_at = [], []
    t_start = time.perf_counter()
    for i, p in enumerate(prompts):
        lag = t_start + i * gap - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        sub_at.append(time.perf_counter())
        reqs.append(router.submit(p, max_new_tokens=wants[i],
                                  temperature=temperature, top_p=top_p))
        if kill_at is not None and i == kill_at:
            # the injected death: everything in flight on r0 fails with
            # ReplicaDead and the router resubmits it to the survivors
            router.replicas["r0"].kill()
    results = [r.result(timeout=600.0) for r in reqs]
    # True completion stamps: RouterRequest.t_done is the WORKER's
    # set_result time, not the moment this collection loop got around to
    # calling result(). Stamping here instead would charge every request
    # that finished during the pacing loop for the rest of the submission
    # window — at 20 rps x 32 requests that's seconds of phantom p99.
    done_at = [r.t_done for r in reqs]
    # "alive" means survived the RUN — snapshot before stop() tears the
    # scheduler threads down (after it, every replica reads unhealthy)
    alive = {name: rep.healthy() for name, rep in router.replicas.items()}
    router.stop()

    # submit -> completion wall latency AT THE ROUTER (a resubmitted
    # request's clock keeps running through its replica's death — the retry
    # is paid, not hidden)
    lat_ms = np.array([(d - s) * 1e3 for s, d in zip(sub_at, done_at)])
    ttft_ms = np.array([res.queue_wait_s * 1e3 for res in results])
    window_s = max(max(done_at) - t_start, 1e-9)
    n_tokens = int(sum(res.tokens.size for res in results))
    per_replica = {}
    for name, rep in router.replicas.items():
        mine = [(reqs[i], lat_ms[i]) for i in range(n_requests)
                if reqs[i].replica_name == name]
        per_replica[name] = {
            "served": rep.scheduler.served,
            "alive": alive[name],
            **({"p50_ms": round(float(np.percentile(
                    [m for _, m in mine], 50)), 2),
                "p99_ms": round(float(np.percentile(
                    [m for _, m in mine], 99)), 2)} if mine else {}),
        }
    scheds = [rep.scheduler for rep in router.replicas.values()]
    engine = engines[0]
    row = {
        "mode": "serving_continuous",
        "granularity": "token",
        "model": model_name,
        "kv_dtype": kv_dtype,
        "page_size": page_size,
        "buckets": list(engine.config.buckets),
        "rows": rows,
        "max_new_tokens": max_new_tokens,
        "n_requests": n_requests,
        "mixed_want": mixed_want,
        "completed": len(results),
        "offered_rps": offered_rps,
        "achieved_rps": round(n_requests / window_s, 2),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "mean_ms": round(float(lat_ms.mean()), 2),
        "ttft_p50_ms": round(float(np.percentile(ttft_ms, 50)), 2),
        "ttft_p99_ms": round(float(np.percentile(ttft_ms, 99)), 2),
        "tokens": n_tokens,
        "tokens_per_sec": round(n_tokens / window_s, 1),
        "backend": jax.default_backend(),
        "compiles": sum(e.compiles for e in engines),
        "recompiles_after_warmup": sum(
            e.compiles - w for e, w in zip(engines, compiles_warm)),
        "replicas": replicas,
        "replica_deaths": sum(r.replica_deaths for r in reqs),
        "per_replica": per_replica,
        # the admission fast-path census: skips dispatched NO prefill,
        # resumes prefilled only the non-resident tail
        "prefix_skip": prefix_skip,
        "prefill_skips": sum(s.prefill_skips for s in scheds),
        "tail_resumes": sum(s.tail_resumes for s in scheds),
        "shared_frac": shared_frac,
        "draft": draft_model,
        # the HBM story: the paged (optionally int8) pool vs what the
        # dense fp32 cache would hold for the same rows at the top rung
        "paged_kv_bytes": engine.paged_bytes(),
        "dense_kv_bytes": engine.dense_baseline_bytes(),
        "checkpoint": engine.checkpoint_info,
    }
    row["kv_bytes_ratio"] = round(
        row["dense_kv_bytes"] / max(row["paged_kv_bytes"], 1), 2)
    if kv_dtype == "int8":
        # which int8 page codec the engine's programs were traced with
        from ..ops.quantize import resolve_fused

        row["kv_codec"] = ("pallas" if resolve_fused(engine._fused_quantize)
                           else "xla")
    if draft_model is not None:
        rounds = sum(s.spec_rounds for s in scheds)
        proposed = sum(s.spec_proposed for s in scheds)
        accepted = sum(s.spec_accepted for s in scheds)
        row["draft_k"] = draft_k
        row["spec_rounds"] = rounds
        # accept_ratio is the draft's hit rate; accepted_per_verify is
        # the speed-up currency — mean draft tokens banked per target
        # forward (the bonus token rides on top of it)
        row["accept_ratio"] = round(accepted / max(proposed, 1), 3)
        row["accepted_per_verify"] = round(accepted / max(rounds, 1), 2)
        row["draft_kv_bytes"] = engine.draft_bytes()
        if row["backend"] != "tpu":
            # same discipline as device_time_split's backend caveat:
            # a non-TPU row names its own limits instead of passing as
            # a chip measurement
            row["caveat"] = (
                "cpu mesh: draft and verify thunks serialize (no ICI "
                "overlap), so tok/s understates the speculative win; "
                "random-init drafts pin accept_ratio near zero — only "
                "trained draft/target pairs on a chip measure real "
                "acceptance economics")
    if shared_idx:
        # warm = shared-prompt requests AFTER their replica's primer (the
        # one that paid the prefill and registered the pages); everything
        # else is the cold arm. Attribution is by final replica, so a
        # resubmitted primer stays a primer on the survivor.
        primers, seen = set(), set()
        for i in sorted(shared_idx):
            name = reqs[i].replica_name
            if name not in seen:
                seen.add(name)
                primers.add(i)
        warm = [float(ttft_ms[i]) for i in shared_idx if i not in primers]
        cold = [float(ttft_ms[i]) for i in range(n_requests)
                if i not in shared_idx or i in primers]
        if warm:
            row["ttft_warm_p50_ms"] = round(
                float(np.percentile(warm, 50)), 2)
        if cold:
            row["ttft_cold_p50_ms"] = round(
                float(np.percentile(cold, 50)), 2)
    try:
        from ..analysis.hlo_rules import (
            check_artifacts, paged_serving_artifacts,
        )

        findings = check_artifacts(
            paged_serving_artifacts(engine, name="bench-paged"))
        if draft_model is not None:
            from ..analysis.hlo_rules import spec_serving_artifacts

            findings.extend(check_artifacts(
                spec_serving_artifacts(engine, name="bench-spec")))
        row["contracts"] = {
            "pass": not findings,
            "violations": [f.as_dict() for f in findings]}
    except Exception as e:  # noqa: BLE001 - observability never kills a row
        row["contracts"] = {"pass": None,
                            "error": f"{type(e).__name__}: {e}"}
    return row

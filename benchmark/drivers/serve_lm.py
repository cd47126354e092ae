"""The serving job of ``drivers/serve.py`` (same generator, same `offer`:
ramp, window, trace, what became of every request) for any language-model
family the token server runs: the plain reference, the operation counts and
the layer checks are found by the configuration's ``family``, as
``benchmark.reference.<family>``, ``benchmark.flops.<family>`` and
``benchmark.checks.<family>`` (`train_lm.family_modules`), so the next served
family is files alone.

A mix with ``plan_seed`` draws its plan (the offered requests' lengths and
token ids) from that seed in every run, and weights and the check's requests
from ``--seed``: in a closed loop whose window is about one request's
lifetime, which requests' prefills fall inside the window is decided by the
length draw, and a draw a run moves the cell's number by more than the
benchmark's bound allows (PERF.md section 6, PR 37).

The server is built as the CLI builds it
(``serving/build.py::build_slot_engine`` -> ``SlotEngine`` ->
``ContinuousScheduler`` -> ``Router``): the model by its registry name with
the configuration's ``model_overrides`` (one chip's share), weights random
from ``--seed`` in the served dtype.

What a family's modules give this driver:

* ``reference.layer_by_layer(program params, ids, sizes, share, rows)``:
  the plain reference's float32 logits of one sequence, one layer's weights
  cast up at a time (the program's tree whole in float32 does not fit beside
  the served state); ``reference.share_of(config)``;
* ``checks.layer_checks(config, traffic, seed)``: the family's layer alone
  at the timed shape (optional).

``correct`` (outside the window, through the router, at the timed sizes; the
limits are the configuration's ``correct``, with their reasons and the
readings they lie between). Eight seeded requests with prompts from half the
smallest bucket to the largest and 8 new tokens each: (a) the logits the
server kept after prefill against the reference's row, as max|diff| /
max|row| over the vocabulary slice: the MEDIAN request (``logits_rel_p50``)
and the WORST request (``logits_rel``); (b) every decoded token (prefill,
then the decode step through the pages) against the reference's best when
the reference is fed the same prefix, in units of max|logit|: a request's
reading is its worst token's gap, and the MEDIAN request (``token_gap_p50``)
and the WORST (``token_gap``) are read; (c) the family's layer checks; each
reading has to lie within the configuration's ``<reading>_tol``; (d) every
step counter the engine keeps under a name ending ``dropped_assignments`` is
0. (A median beside a worst: in a routed model one token that takes another
expert than the reference moves one request's reading by far more than the
compute precision moves every request's.) Plus the standing rules of ``drivers/serve.py``:
exact token counts, no compile inside the window, plan not exhausted.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.drivers.serve import offer
from benchmark.drivers.train_lm import family_modules

CHECK_REQUESTS = 8
CHECK_NEW_TOKENS = 8


def build(run):
    from distributed_pytorch_training_tpu.serving.build import (
        build_slot_engine,
    )
    from distributed_pytorch_training_tpu.serving.router import (
        InProcessReplica, Router,
    )

    job, mix = run.config["job"], run.traffic
    engine, _ = build_slot_engine(
        run.devices[:run.cell["chips"]], run.config["registry_model"],
        buckets=tuple(job["buckets"]), rows=int(mix["rows"]),
        max_new_tokens=int(job["max_new_tokens"]), kv_dtype=job["kv_dtype"],
        page_size=int(job["page_size"]),
        prefix_skip=bool(job["prefix_skip"]),
        serve_dtype=job["serve_dtype"],
        model_overrides=run.config.get("model_overrides", {}), seed=run.seed)
    cfg = engine.config
    # warm the programs this mix uses and no others (`drivers/serve.py`)
    if mix.get("warm_programs", "all") == "all":
        engine.warmup()
    else:
        for kind in mix["warm_programs"]:
            per_bucket = kind in ("paged_prefill", "paged_resume")
            for bucket in (cfg.buckets if per_bucket else (0,)):
                engine._executable(kind, bucket)
    replica = InProcessReplica("replica0", engine)
    router = Router([replica])
    run.note(rows=cfg.rows, buckets=cfg.buckets, cache_len=cfg.cache_len,
             programs_warmed=engine.compiles, pages=cfg.total_pages,
             page_size=cfg.page_size, page_bytes=engine.paged_bytes(),
             kv_path=engine.kv_path)
    return dict(model=engine.model, params=engine._served, engine=engine,
                replica=replica, router=router, cfg=cfg)


def check_against_reference(run, job, reference) -> dict:
    """`drivers/serve.py`'s check, the reference a layer at a time and the
    requests judged by their median and their worst."""
    model, cfg, router = job["model"], job["cfg"], job["router"]
    sizes, share = run.config["published"], reference.share_of(run.config)
    rng = np.random.default_rng(run.seed + 1)
    top = max(cfg.buckets)
    lens = np.linspace(max(2, min(cfg.buckets) // 2), top,
                       CHECK_REQUESTS).astype(int)
    want_new = min(CHECK_NEW_TOKENS, cfg.max_new_tokens)
    prompts = [rng.integers(0, model.vocab_size, size=int(n)).astype(np.int32)
               for n in lens]
    handles = [router.submit(p, max_new_tokens=want_new, seed=i)
               for i, p in enumerate(prompts)]
    results = [h.result(timeout=600.0) for h in handles]

    width = top + want_new       # one reference program for every length
    logits_rel, gaps = [], []
    counts_ok = True
    for prompt, res in zip(prompts, results):
        counts_ok &= len(res.tokens) == want_new
        ids = np.zeros((width,), np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(res.tokens)] = res.tokens
        ref = np.asarray(reference.layer_by_layer(
            job["params"], ids, sizes, share,
            rows=(len(prompt) - 1, want_new)))
        scale = float(np.abs(ref[0]).max())
        got = np.asarray(res.last_logits)[:model.vocab_size]
        logits_rel.append(float(np.abs(got - ref[0]).max()) / scale)
        gaps.append(max(float(row.max() - row[int(tok)])
                        / float(np.abs(row).max())
                        for row, tok in zip(ref, res.tokens)))
    out = {"logits_rel_p50": float(np.median(logits_rel)),
           "logits_rel": float(max(logits_rel)),
           "token_gap_p50": float(np.median(gaps)),
           "token_gap": float(max(gaps)), "token_counts_ok": bool(counts_ok)}
    run.note(check="server_vs_reference", requests=len(prompts),
             prompt_lens=[int(n) for n in lens], new_tokens=want_new,
             per_request_logits_rel=logits_rel, per_request_token_gap=gaps,
             **out)
    return out


def run(run) -> dict:
    reference, flops, checks = family_modules(run.config)
    limits = run.config["correct"]
    job = build(run)
    found = check_against_reference(run, job, reference)
    if checks is not None:
        found.update(checks.layer_checks(run.config, run.traffic, run.seed))
    engine = job["engine"]
    programs_before = engine.compiles
    # a mix may fix the seed of its PLAN (lengths and token ids of the
    # offered requests): weights, and the check's requests, are --seed's
    plan_seed = int(run.traffic.get("plan_seed", run.seed))
    got = offer(run, job, run.traffic, run.seconds, plan_seed,
                trace=run.trace)
    # the window is over: what is still in flight is in no count
    abandoned = job["replica"].kill()
    counters = engine.fetch_step_counters()
    found.update({k: v for k, v in counters.items()
                  if k.endswith("dropped_assignments")})
    # every ``<reading>_tol`` of the configuration holds the reading of that
    # name; a limit whose reading is missing is a fault, not a pass
    within = {name[:-4]: found.get(name[:-4], float("inf")) <= tol
              for name, tol in limits.items() if name.endswith("_tol")}
    within["token_counts"] = found["token_counts_ok"]
    within["dropped_assignments"] = all(
        v == 0 for k, v in found.items() if k.endswith("dropped_assignments"))
    summary = dict(got["summary"], abandoned_at_end=len(abandoned),
                   programs_compiled_after_warmup=(engine.compiles
                                                   - programs_before))
    run.note(check="limits", found=found, within=within,
             limits={k: v for k, v in limits.items() if k != "why"})
    run.note(**summary)
    run.note(step_counters=counters)
    run.facts.update(summary=summary, rows=int(run.traffic["rows"]),
                     step_counters=counters,
                     serve_shape={
                         "rows": int(run.traffic["rows"]),
                         "family": run.config["family"],
                         "layers": int(job["model"].depth),
                         "moe_assignments_per_token": getattr(
                             flops, "moe_assignments_per_token",
                             lambda _: 0)(run.config)})
    (run.out_dir / "serve_summary.json").write_text(json.dumps(summary))
    correct = (all(within.values()) and got["failed"] == 0
               and run.facts["compiles_in_window"] == 0
               and engine.compiles == programs_before
               and not got["exhausted"] and got["completed"] > 0)
    return {"correct": correct, "attempted": got["attempted"],
            "failed": got["failed"], "values": got["values"],
            "counts": {"due_in_window": got["due"],
                       "completed_in_window": got["completed"],
                       **{k: v for k, v in found.items()
                          if isinstance(v, float)}}}

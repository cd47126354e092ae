"""The serving job of ``drivers/serve_lm.py`` (same build through
``serving/build.py::build_slot_engine``, same generator, same `offer`: ramp,
window, trace, what became of every request) for a model that generates by
DIFFUSION OVER BLOCKS, whose check replays a block-diffusion trajectory:
``serve_lm``'s feeds the reference "the same prefix" and reads the next
token, which is an autoregressive rule.

What the family's modules give this driver (`train_lm.family_modules`):
``reference.layer_by_layer(program params, ids, masked bits, sizes, block
length, MASK id, rows)``: the plain reference's float32 logits of one
sequence under the block mask, one layer's weights cast up at a time;
``reference.choose``: one denoise step's choice; ``checks.layer_checks``.

``correct`` (outside the window, through the router, at the timed sizes; the
limits are the configuration's ``correct``, with their reasons and the
readings they lie between). Eight seeded requests, prompts from half the
smallest bucket to the largest with every ``L % B`` among them, 6 to 10 new
tokens each (two or three blocks), at the mix's ``denoising_steps``. The
server hands back its own trajectory: the tokens and, for each, the denoise
step of its block at which it was unmasked (``Result.unmask_steps``). For
every denoise step of every block the window's state is rebuilt from that
(the positions unmasked before the step hold their tokens, the others MASK)
and the reference makes that step's window logits by a full forward:

(a) ``token_gap``: at each position the server unmasked at that step, the
    reference's ``max(row) - row[token]`` in units of ``max|row|``;
(b) ``choice_gap``: the confidence ``max softmax`` the reference gives the
    position the server chose, below the confidence it had to beat (the
    n-th best still-masked position's, n = B / T), in units of the best; with
    random weights confidences lie close, the position flips as an expert
    does, and the gap stays small where the arithmetic is right;
(c) ``logits_rel``: the logits row the engine keeps a slot (the first
    denoise step's, at its window's last position) against the reference's,
    ``max|diff| / max|row|``;

each as the MEDIAN request (``*_p50``) and the WORST (a request's reading is
its worst step's), and ``choice_gap`` as the MEAN request as well
(``choice_gap_mean``: most requests read exactly 0 and the others a flip's
size, so the median of eight swings between the two and the mean does not);
(d) the family's layer checks (``kernel_rel_diff``: the
window read alone at the timed shape); (e) every step counter the engine
keeps under a name ending ``dropped_assignments`` is 0, exact token counts
(a ``want`` that is no multiple of B among the eight), plus the standing
rules of ``drivers/serve.py``: no compile inside the window, plan not
exhausted. Each reading has to lie within the configuration's
``<reading>_tol``; a reading the configuration gives no limit is noted and
judges nothing (its `why` says which, and why no limit lies between their
two readings). A request whose last block is cut to its ``want`` does
not hand back the cut positions' tokens, so of that block only the first
step, where every position is still masked, is replayed; six of the eight
requests end on a block's edge.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.drivers.serve import offer
from benchmark.drivers.serve_lm import build
from benchmark.drivers.train_lm import family_modules

CHECK_REQUESTS = 8


def check_requests(rng, buckets, block: int, vocab: int):
    """(prompts, wants): lengths from half the smallest bucket to the
    largest with ``L % block`` = 0, 1, .., block - 1 in turn; wants of 6 to
    10 such that the first six requests end on a block's edge and the last
    two are cut inside one."""
    top = max(buckets)
    lens = np.linspace(max(2, min(buckets) // 2), top,
                       CHECK_REQUESTS).astype(int)
    lens = np.minimum(lens - lens % block + np.arange(CHECK_REQUESTS) % block,
                      top)
    wants = []
    for i, n in enumerate(lens):
        # the least want of 6..10 that fills the last block, one more for
        # the last two
        fill = next(w for w in range(6, 11) if (n % block + w) % block == 0)
        wants.append(fill if i < CHECK_REQUESTS - 2 else fill + 1)
    prompts = [rng.integers(0, vocab, size=int(n)).astype(np.int32)
               for n in lens]
    return prompts, wants


def replay(prompt, tokens, steps, block: int):
    """The server's trajectory as (block start, window ids, masked bits,
    chosen bits) a denoise step, in order. A block whose tail the request's
    ``want`` cut off yields its first step only."""
    held = len(prompt) % block
    known = len(prompt) - held
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    at = np.concatenate([np.full(len(prompt), -1), steps])   # -1: never masked
    out = []
    for start in range(known, len(seq), block):
        ids = np.zeros(block, np.int32)
        when = np.full(block, np.iinfo(np.int64).max)
        n = min(block, len(seq) - start)
        ids[:n], when[:n] = seq[start:start + n], at[start:start + n]
        last = 0 if n < block else int(when.max())
        for step in range(last + 1):
            out.append((start, ids, when >= step, when == step))
    return out


def check_against_reference(run, job, reference) -> dict:
    model, cfg, router = job["model"], job["cfg"], job["router"]
    engine, sizes = job["engine"], run.config["published"]
    block, mask_id = engine.block_length, int(model.mask_token_id)
    per_step = block // int(run.traffic.get("denoising_steps", block))
    rng = np.random.default_rng(run.seed + 1)
    prompts, wants = check_requests(rng, cfg.buckets, block,
                                    model.vocab_size)
    handles = [router.submit(
        p, max_new_tokens=w, seed=i,
        denoising_steps=run.traffic.get("denoising_steps"))
        for i, (p, w) in enumerate(zip(prompts, wants))]
    results = [h.result(timeout=600.0) for h in handles]

    width = max(cfg.buckets) + -(-max(wants) // block) * block + block
    token_gaps, choice_gaps, logits_rel = [], [], []
    counts_ok = True
    for prompt, want, res in zip(prompts, wants, results):
        counts_ok &= len(res.tokens) == want
        gap = chose = 0.0
        for n, (start, window, masked, chosen) in enumerate(
                replay(prompt, res.tokens, res.unmask_steps, block)):
            ids = np.zeros((width,), np.int32)
            bits = np.zeros((width,), bool)
            seq = np.concatenate([prompt, res.tokens])[:start]
            ids[:start], ids[start:start + block] = seq, window
            bits[start:start + block] = masked
            ref = np.asarray(reference.layer_by_layer(
                job["params"], ids, bits, sizes, block, mask_id,
                rows=(start, block)))
            _, _, confidence = reference.choose(ref, masked, per_step)
            # the confidence the choice had to beat: the n-th best masked
            bar = np.sort(confidence[masked])[::-1][
                min(per_step, int(masked.sum())) - 1]
            for i in np.flatnonzero(chosen):
                row = ref[i]
                gap = max(gap, float(row.max() - row[int(window[i])])
                          / float(np.abs(row).max()))
                chose = max(chose, max(0.0, float(bar - confidence[i]))
                            / float(confidence[masked].max()))
            if n == 0:
                first = ref[block - 1]
                got = np.asarray(res.last_logits)[:model.vocab_size]
                logits_rel.append(float(np.abs(got - first).max())
                                  / float(np.abs(first).max()))
        token_gaps.append(gap)
        choice_gaps.append(chose)
    out = {"logits_rel_p50": float(np.median(logits_rel)),
           "logits_rel": float(max(logits_rel)),
           "token_gap_p50": float(np.median(token_gaps)),
           "token_gap": float(max(token_gaps)),
           "choice_gap_p50": float(np.median(choice_gaps)),
           "choice_gap_mean": float(np.mean(choice_gaps)),
           "choice_gap": float(max(choice_gaps)),
           "token_counts_ok": bool(counts_ok)}
    run.note(check="server_vs_reference", requests=len(prompts),
             prompt_lens=[len(p) for p in prompts], new_tokens=wants,
             per_request_logits_rel=logits_rel,
             per_request_token_gap=token_gaps,
             per_request_choice_gap=choice_gaps, **out)
    return out


def run(run) -> dict:
    reference, flops, checks = family_modules(run.config)
    limits = run.config["correct"]
    # the layer alone first, before the served state is built: at the timed
    # shape it holds 1.7 GB of its own, which nothing need hold beside the
    # weights and the pool
    alone = checks.layer_checks(run.config, run.traffic, run.seed) \
        if checks is not None else {}
    job = build(run)
    found = dict(check_against_reference(run, job, reference), **alone)
    engine = job["engine"]
    programs_before = engine.compiles
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
                         "window": int(engine.block_length),
                         "moe_assignments_per_token":
                             flops.moe_assignments_per_token(run.config)})
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

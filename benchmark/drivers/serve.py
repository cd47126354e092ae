"""The serving job: one replica of the token-granular server (``SlotEngine`` +
``ContinuousScheduler`` behind ``Router``/``InProcessReplica``) under a
traffic mix, open loop at a fixed rate or closed loop with fixed clients.

One generator thread offers the load; the scheduler's own thread serves it.
Open loop: every request has a due time drawn from the seed, latency runs
from that due time, and how late the generator sent is reported. Closed
loop: ``clients`` requests are always outstanding; a finished one is
replaced at the generator's next poll.

Weights are random from the seed, made on the device in one jitted call in
the dtype they are served in. Set-up warms every program the ladder has
(``engine.warmup``), checks the server against the plain reference on
seeded requests, then ramps the load for ``ramp_s`` so that the window
opens on a server in steady state; all of that is ``setup_s``.
"""

from __future__ import annotations

import json
import threading
import time
from typing import List, Optional

import numpy as np

from benchmark import loadgen, stats
from benchmark.reference import gpt2 as reference

# bf16 compute against the fp32 reference, judged as max|got - want| over
# max|want| on the whole vocabulary row: bf16 rounds each logit to 2^-9
# relative and the 12 layers before it add operand rounding of the same
# size; chip_smoke measured 2.6e-3..7.4e-3 for one bf16 attention against
# fp32. fp32 compute lands near 1e-5; an 8-bit float could not meet 2e-2.
LOGITS_REL_TOL = 2e-2
# A decoded token is right when the reference, fed the same prefix, scores
# it within this of its own best token (in units of max|logit|): with
# random weights the top logits are close, so argmax may flip on rounding,
# but never to a token the reference scores clearly lower.
TOKEN_GAP_TOL = 2e-2
CHECK_REQUESTS = 8
CHECK_NEW_TOKENS = 8


class Sent:
    """One request the generator sent."""

    __slots__ = ("plan", "due", "sent", "handle")

    def __init__(self, plan, due, sent, handle):
        self.plan, self.due, self.sent, self.handle = plan, due, sent, handle

    @property
    def request(self):
        """The program's own `Request` (its stamps are the TTFT source):
        `RouterRequest` keeps it as ``_inner``."""
        return self.handle._inner


def build(run):
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_training_tpu.models import get_model
    from distributed_pytorch_training_tpu.parallel import (
        MeshSpec, build_mesh,
    )
    from distributed_pytorch_training_tpu.serving.continuous import SlotEngine
    from distributed_pytorch_training_tpu.serving.paged import (
        PagedServeConfig,
    )
    from distributed_pytorch_training_tpu.serving.router import (
        InProcessReplica, Router,
    )

    job, mix = run.config["job"], run.traffic
    devices = run.devices[:run.cell["chips"]]
    mesh = build_mesh(MeshSpec(data=len(devices)), devices=devices)
    dtype = jnp.bfloat16 if job["serve_dtype"] == "bf16" else jnp.float32
    model = get_model(run.config["registry_model"], dtype=dtype,
                      **run.config.get("model_overrides", {}))
    sample = np.zeros((1, min(job["buckets"])), np.int32)
    params = jax.jit(lambda key: model.init(key, sample, train=False)
                     ["params"])(jax.random.PRNGKey(run.seed))
    cfg = PagedServeConfig(
        buckets=tuple(job["buckets"]), rows=int(mix["rows"]),
        max_new_tokens=int(job["max_new_tokens"]),
        serve_dtype=job["serve_dtype"], page_size=int(job["page_size"]),
        kv_dtype=job["kv_dtype"], prefix_skip=bool(job["prefix_skip"]))
    engine = SlotEngine(model, mesh, cfg, params)
    # warm the programs this mix uses and no others: a mix without shared
    # prefixes never reaches the skip and resume programs. ("all" is
    # engine.warmup(); a program the window needed and set-up did not warm
    # would compile inside the window, which the run counts and fails on.)
    if mix.get("warm_programs", "all") == "all":
        engine.warmup()
    else:
        for kind in mix["warm_programs"]:
            per_bucket = kind in ("paged_prefill", "paged_resume")
            for bucket in (cfg.buckets if per_bucket else (0,)):
                engine._executable(kind, bucket)
    programs = engine.compiles
    replica = InProcessReplica("replica0", engine)
    router = Router([replica])
    run.note(rows=cfg.rows, buckets=cfg.buckets, cache_len=cfg.cache_len,
             programs_warmed=programs, pages=cfg.total_pages,
             page_bytes=engine.paged_bytes())
    return dict(model=model, params=params, engine=engine, replica=replica,
                router=router, cfg=cfg)


def check_against_reference(run, job) -> dict:
    """Seeded requests through the router: the logits the server kept after
    prefill against the reference's full forward at that position, and every
    decoded token (prefill, then decode through the pages) against the
    reference fed the same prefix."""
    import jax

    model, cfg, router = job["model"], job["cfg"], job["router"]
    rng = np.random.default_rng(run.seed + 1)
    top = max(cfg.buckets)
    lens = np.linspace(max(2, min(cfg.buckets) // 2), top,
                       CHECK_REQUESTS).astype(int)
    want_new = min(CHECK_NEW_TOKENS, cfg.max_new_tokens)
    prompts = [rng.integers(0, model.vocab_size, size=int(n)).astype(np.int32)
               for n in lens]
    handles = [router.submit(p, max_new_tokens=want_new, seed=i)
               for i, p in enumerate(prompts)]
    results = [h.result(timeout=300.0) for h in handles]

    width = top + want_new       # one reference program for every length
    eps = run.config["published"]["layer_norm_epsilon"]
    # only the rows that are compared leave the device
    ref_rows = jax.jit(lambda params, ids, start: jax.lax.dynamic_slice_in_dim(
        reference.forward(reference.from_program_params(params), ids,
                          model.vocab_size, eps)[0], start, want_new))
    worst_logits = worst_gap = 0.0
    counts_ok = True
    for prompt, res in zip(prompts, results):
        counts_ok &= len(res.tokens) == want_new
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(prompt)] = prompt
        ids[0, len(prompt):len(prompt) + len(res.tokens)] = res.tokens
        ref = np.asarray(ref_rows(job["params"], ids, len(prompt) - 1))
        scale = float(np.abs(ref[0]).max())
        got = np.asarray(res.last_logits)[:model.vocab_size]
        worst_logits = max(worst_logits,
                           float(np.abs(got - ref[0]).max()) / scale)
        for k, tok in enumerate(res.tokens):
            row = ref[k]
            worst_gap = max(worst_gap, float(row.max() - row[int(tok)])
                            / float(np.abs(row).max()))
    ok = (counts_ok and worst_logits <= LOGITS_REL_TOL
          and worst_gap <= TOKEN_GAP_TOL)
    run.note(check="server_vs_reference", requests=len(prompts),
             prompt_lens=[int(n) for n in lens], new_tokens=want_new,
             last_logits_rel_diff=worst_logits, tol=LOGITS_REL_TOL,
             decoded_token_gap=worst_gap, gap_tol=TOKEN_GAP_TOL,
             token_counts_ok=bool(counts_ok))
    return {"ok": bool(ok), "logits_rel_diff": worst_logits,
            "token_gap": worst_gap}


class Outcome:
    """What became of one sent request, read before the replica is stopped."""

    def __init__(self, s: Sent, emitted_so_far: Optional[int], t_read: float):
        req = s.request
        self.due, self.sent, self.want = s.due, s.sent, s.plan.want
        self.t_first, self.t_done = req.t_first_token, req.t_done
        self.result = self.error = None
        if self.t_done is not None:
            try:
                self.result = s.handle.result(timeout=1.0)
            except Exception as e:  # noqa: BLE001 — counted as failed
                self.error = e
        # tokens out so far, and when that count was true
        if self.result is not None:
            self.emitted, self.t_last = len(self.result.tokens), self.t_done
        else:
            self.emitted, self.t_last = emitted_so_far or 0, t_read

    def tokens_between(self, t0: float, t1: float) -> float:
        """Output tokens that left inside [t0, t1]: token #0 at the
        first-token fence, the rest evenly up to the completion (or, for a
        request still running, up to when its slot was read). The decode
        step emits one token per live slot per step, so even is right up to
        the jitter of the step time."""
        if self.t_first is None or self.emitted <= 0:
            return 0.0
        inside = 1.0 if t0 <= self.t_first < t1 else 0.0
        span = self.t_last - self.t_first
        if self.emitted > 1 and span > 0:
            overlap = min(t1, self.t_last) - max(t0, self.t_first)
            inside += (self.emitted - 1) * max(0.0, overlap) / span
        return inside


def running_emitted(scheduler) -> dict:
    """request id -> tokens its slot has emitted, from the scheduler's own
    host mirror of the slots (``want - left``), read under its lock. The
    program fetches a slot's tokens only at completion, so this mirror is
    the one place that knows how far a running request is."""
    with scheduler._lock:
        return {st.req.id: st.want - st.left
                for st in scheduler.running.values()}


class Generator(threading.Thread):
    """The load: open loop (``rate_rps``) or closed loop (``clients``)."""

    def __init__(self, router, plan: List[loadgen.PlannedRequest],
                 clients: Optional[int]):
        super().__init__(name="benchmark-loadgen", daemon=True)
        self.router, self.plan, self.clients = router, plan, clients
        self.sent: List[Sent] = []
        self.stop_flag = threading.Event()
        self.t0 = None
        self.error: Optional[BaseException] = None
        self.exhausted = False

    def _submit(self, plan, due):
        sent = time.perf_counter()
        handle = self.router.submit(plan.tokens, max_new_tokens=plan.want,
                                    seed=plan.index)
        self.sent.append(Sent(plan, due, sent, handle))

    def run(self):
        try:
            self.t0 = time.perf_counter()
            if self.clients is None:
                self._open()
            else:
                self._closed()
        except BaseException as e:  # noqa: BLE001 — reported by the driver
            self.error = e

    def _open(self):
        for plan in self.plan:
            due = self.t0 + plan.due_s
            while not self.stop_flag.is_set():
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            if self.stop_flag.is_set():
                return
            self._submit(plan, due)
        self.exhausted = True

    def _closed(self):
        todo = iter(self.plan)
        live: List[Sent] = []
        while not self.stop_flag.is_set():
            live = [s for s in live if s.request.t_done is None]
            while len(live) < self.clients:
                plan = next(todo, None)
                if plan is None:
                    self.exhausted = True
                    return
                self._submit(plan, time.perf_counter())
                live.append(self.sent[-1])
            time.sleep(0.002)


def backlog_at(seen: List[Outcome], t: float) -> int:
    """Requests due before ``t`` that had no first token yet at ``t``."""
    return sum(1 for o in seen if o.due < t
               and (o.t_first is None or o.t_first >= t))


def offer(run, job, mix: dict, seconds: float, seed: int,
          trace: bool = False) -> dict:
    """Offer ``mix`` to the running server: ramp, a window of ``seconds``,
    then read what became of every request. Leaves the replica running
    (whatever is still in flight stays in flight)."""
    ramp_s = float(mix["ramp_s"])
    grace_s = float(mix.get("grace_s", 2.0))
    open_loop = mix["loop"] == "open"
    horizon = ramp_s + seconds + grace_s + 2.0
    if open_loop:
        rate = float(mix["rate_rps"])
        n, clients = int(rate * horizon * 1.2) + 16, None
    else:
        clients = int(mix["clients_per_row"] * mix["rows"])
        rate, n = None, int(mix["plan_requests_per_s"] * horizon) + clients
    plan = loadgen.plan_requests(mix, seed, n, job["model"].vocab_size,
                                 rate_rps=rate)
    gen = Generator(job["router"], plan, clients)
    gen.start()
    while gen.t0 is None and gen.error is None:
        time.sleep(0.001)
    t0 = gen.t0 + ramp_s
    t1 = t0 + seconds
    time.sleep(max(0.0, t0 - time.perf_counter()))
    wall0 = time.time() - (time.perf_counter() - t0)
    run.window_opens(t0)
    run.window = (wall0, wall0 + seconds)
    if trace:
        trace_s = min(float(mix["trace_seconds"]), seconds / 2)
        time.sleep(max(0.0, t1 - trace_s - 1.0 - time.perf_counter()))
        run.profile(lambda: time.sleep(trace_s))
    time.sleep(max(0.0, t1 - time.perf_counter()))
    run.window_closes()
    # first tokens of requests due late in the window land just after it
    deadline = t1 + grace_s
    while time.perf_counter() < deadline and any(
            s.request.t_first_token is None for s in gen.sent
            if t0 <= s.due < t1):
        time.sleep(0.01)
    gen.stop_flag.set()
    gen.join(timeout=10.0)
    if gen.error is not None:
        raise gen.error
    running = running_emitted(job["replica"].scheduler)
    t_read = time.perf_counter()
    seen = [Outcome(s, running.get(s.request.id), t_read) for s in gen.sent]

    due_in = [o for o in seen if t0 <= o.due < t1]
    done_in = [o for o in seen if o.t_done is not None and t0 <= o.t_done < t1]
    ttft, late, failed = [], [], 0
    for o in due_in:
        late.append(max(0.0, o.sent - o.due) * 1e3)
        if o.t_first is not None:
            ttft.append(stats.open_loop_latency(
                o.due, o.sent, o.t_first - o.sent)[0] * 1e3)
        elif open_loop:
            failed += 1    # due in the window, no first token `grace_s` on
    tpot, done_tokens = [], 0
    for o in done_in:
        if o.error is not None or len(o.result.tokens) != o.want:
            failed += 1    # refused, errored, or a wrong token count
            continue
        done_tokens += len(o.result.tokens)
        if len(o.result.tokens) >= 8:
            tpot.append(o.result.decode_s / (len(o.result.tokens) - 1) * 1e3)
    out_tokens = sum(o.tokens_between(t0, t1) for o in seen
                     if o.error is None)
    mid = backlog_at(seen, t0 + seconds / 2)
    end = backlog_at(seen, t1)
    summary = {
        "loop": mix["loop"], "rate_rps": rate, "clients": clients,
        "seconds": seconds, "sent": len(seen), "due_in_window": len(due_in),
        "completed_in_window": len(done_in), "failed": failed,
        "plan_exhausted": gen.exhausted,
        "out_tokens_per_s": out_tokens / seconds,
        "completed_requests_tokens_per_s": done_tokens / seconds,
        "completed_per_s": len(done_in) / seconds,
        "ttft_ms": stats.summarize(ttft), "tpot_ms": stats.summarize(tpot),
        "generator_late_ms": stats.summarize(late),
        "ttft_under_500ms_share": (sum(t <= 500.0 for t in ttft)
                                   / max(len(due_in), 1)),
        "backlog_mid_window": mid, "backlog_at_window_end": end,
        "backlog_growth_per_s": (end - mid) / (seconds / 2),
    }
    return {"summary": summary, "failed": failed,
            "attempted": len(due_in) if open_loop else len(done_in),
            "values": {"serve_out_tokens_per_s": out_tokens / seconds,
                       "ttft_p95_ms": stats.percentile(ttft, 95.0),
                       "tpot_p95_ms": stats.percentile(tpot, 95.0)},
            "exhausted": gen.exhausted, "completed": len(done_in),
            "due": len(due_in)}


def run(run) -> dict:
    job = build(run)
    check = check_against_reference(run, job)
    engine = job["engine"]
    programs_before = engine.compiles
    got = offer(run, job, run.traffic, run.seconds, run.seed, trace=run.trace)
    # the window is over: what is still in flight is in no count
    abandoned = job["replica"].kill()
    summary = dict(got["summary"], abandoned_at_end=len(abandoned),
                   programs_compiled_after_warmup=(engine.compiles
                                                   - programs_before))
    run.note(**summary)
    run.facts.update(summary=summary, rows=int(run.traffic["rows"]))
    (run.out_dir / "serve_summary.json").write_text(json.dumps(summary))
    correct = (check["ok"] and got["failed"] == 0
               and run.facts["compiles_in_window"] == 0
               and engine.compiles == programs_before
               and not got["exhausted"] and got["completed"] > 0)
    return {"correct": correct, "attempted": got["attempted"],
            "failed": got["failed"], "values": got["values"],
            "counts": {"due_in_window": got["due"],
                       "completed_in_window": got["completed"],
                       "logits_rel_diff": check["logits_rel_diff"],
                       "token_gap": check["token_gap"]}}

"""The one traffic generator: a mix is a data file, this reads it.

``lengths`` draws prompt and output lengths, ``arrivals`` the open-loop
schedule, ``prompts`` the token ids, all from ``numpy.random.default_rng``
seeded by the run's ``--seed``: the same seed gives the same requests.
A mix can share prefixes (``shared_prefix``: a share of the requests open
with one of ``n_prefixes`` fixed prefixes of ``length`` tokens) and can
burst (``burst``: arrivals come in groups of ``size`` at the same mean
rate); the first cells use neither, so both default to off.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class PlannedRequest:
    index: int
    due_s: float            # seconds after the generator starts; 0 if closed
    tokens: np.ndarray      # (prompt_len,) int32
    want: int               # output tokens asked for


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    or ``{"dist": "uniform", "min": a, "max": b}`` or
    ``{"dist": "fixed", "value": v}`` -> n integer lengths."""
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, size=n)
    if dist == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_arrivals(rng: np.random.Generator, rate_rps: float, n: int,
                  burst: Optional[dict] = None) -> np.ndarray:
    """Due times in seconds: a Poisson process at ``rate_rps``; with
    ``burst`` the events arrive ``size`` at a time (same mean rate)."""
    size = int((burst or {}).get("size", 1))
    groups = -(-n // size)
    gaps = rng.exponential(size / rate_rps, size=groups)
    return np.repeat(np.cumsum(gaps), size)[:n]


def plan_requests(mix: dict, seed: int, n: int, vocab_size: int,
                  rate_rps: Optional[float] = None) -> List[PlannedRequest]:
    """``n`` requests of the mix. ``rate_rps`` set = open loop with due
    times; None = closed loop (due 0, the clients pace themselves)."""
    rng = np.random.default_rng(seed)
    prompt_lens = draw_lengths(rng, mix["prompt_len"], n)
    wants = draw_lengths(rng, mix["output_len"], n)
    due = (draw_arrivals(rng, rate_rps, n, mix.get("burst"))
           if rate_rps else np.zeros(n))
    shared = mix.get("shared_prefix") or {}
    share = float(shared.get("share", 0.0))
    prefixes = None
    if share > 0.0:
        prefixes = rng.integers(0, vocab_size, size=(
            int(shared.get("n_prefixes", 1)), int(shared["length"])))
    out = []
    for i in range(n):
        tokens = rng.integers(0, vocab_size, size=int(prompt_lens[i]))
        if prefixes is not None and rng.random() < share:
            pre = prefixes[rng.integers(0, len(prefixes))]
            k = min(len(pre), len(tokens) - 1)
            tokens[:k] = pre[:k]
        out.append(PlannedRequest(i, float(due[i]),
                                  tokens.astype(np.int32), int(wants[i])))
    return out

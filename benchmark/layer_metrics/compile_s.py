"""Seconds of set-up spent in XLA's backend compile (a persistent-cache hit
counts its retrieval there): jax monitoring events up to the window's start,
as chip_smoke.py sums them."""


def read(run):
    return run.facts.get("compile_s_setup")

"""FLOPs accounting, chip peak detection, and MFU.

The reference's throughput meter reports samples/s with no notion of how much
compute a sample costs (/root/reference/train_ddp.py:224-243), so its numbers
cannot be sanity-checked against hardware. Here every benchmark result carries
model-FLOPs utilization (MFU): a samples/s claim that implies more FLOP/s than
the chip's MXU peak is a broken measurement, and `check_mfu` fails loudly
instead of reporting it.

Two independent FLOPs instruments, cross-checked against each other:

1. ``xla_flops_per_step`` — XLA's own cost analysis of the *compiled* train
   step (what the hardware will actually execute, post-fusion).
2. ``jaxpr_matmul_flops`` — an analytic matmul/conv model: walk the traced
   jaxpr and sum ``2*M*N*K``-style FLOPs for every ``dot_general`` /
   ``conv_general_dilated``, recursing into scan/pjit/remat sub-jaxprs
   (scan bodies multiplied by trip count). This is the "pen-and-paper" count
   a performance engineer would do — independent of XLA's bookkeeping.

A train step should cost ~3x the forward pass (backward = 2 matmuls per
forward matmul), so ``xla(train) / analytic(forward)`` is expected in [2.5, 4]
for matmul-dominated models; elementwise-heavy models (BatchNorm ResNets at
tiny images) run higher.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import numpy as np

# Peak dense bf16 TFLOP/s per JAX device, keyed by `jax.Device.device_kind`.
# NOTE v2/v3 expose one device per TensorCore (2 per chip); v4+ expose one
# device per chip (megacore). Values are per *device* so MFU math needs no
# core-vs-chip special case. Public figures (cloud.google.com/tpu/docs).
CHIP_PEAK_TFLOPS_BF16 = {
    "TPU v2": 22.5,
    "TPU v3": 61.25,
    "TPU v4": 275.0,
    "TPU v4 lite": 137.5,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,   # v6e / Trillium
    "TPU v6e": 918.0,
}

def chip_peak_tflops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Per-device peak dense bf16 TFLOP/s. None only for a non-TPU device
    (the CPU test backend, where MFU means nothing); a TPU whose
    ``device_kind`` is missing from the table raises — an unknown chip is
    an error to fix in the table, never an MFU silently left out."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    if device.device_kind not in CHIP_PEAK_TFLOPS_BF16:
        raise KeyError(
            f"no peak TFLOP/s on record for TPU device_kind "
            f"{device.device_kind!r}; add it to CHIP_PEAK_TFLOPS_BF16 "
            f"(known: {sorted(CHIP_PEAK_TFLOPS_BF16)})")
    return CHIP_PEAK_TFLOPS_BF16[device.device_kind]


def xla_flops_per_step(compiled) -> Optional[float]:
    """FLOPs of one execution of a compiled (lowered+compiled) computation,
    from XLA's cost analysis. None if the backend does not report it."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    flops = cost.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)


# -- analytic matmul/conv model (jaxpr walk) --------------------------------

def _dot_general_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    (lhs_c, rhs_c), (lhs_b, _) = dnums
    batch = math.prod(lhs.shape[d] for d in lhs_b)
    contract = math.prod(lhs.shape[d] for d in lhs_c)
    m = math.prod(lhs.shape[d] for d in range(len(lhs.shape))
                  if d not in lhs_c and d not in lhs_b)
    n = math.prod(rhs.shape[d] for d in range(len(rhs.shape))
                  if d not in rhs_c and d not in dnums[1][1])
    return 2.0 * batch * m * n * contract


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    dnums = eqn.params["dimension_numbers"]
    out_spatial = math.prod(out.shape[d] for d in dnums.out_spec[2:])
    out_ch = out.shape[dnums.out_spec[1]]
    batch = out.shape[dnums.out_spec[0]]
    kernel_spatial = math.prod(rhs.shape[d] for d in dnums.rhs_spec[2:])
    in_ch = rhs.shape[dnums.rhs_spec[1]]  # per feature group
    return 2.0 * batch * out_spatial * out_ch * kernel_spatial * in_ch


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            total += eqn.params["length"] * _jaxpr_flops(body)
        elif name == "while":
            # trip count unknown statically; count one iteration (lower bound)
            total += _jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif name == "shard_map":
            # the body is ONE shard's program: the whole call costs it once
            # per shard, i.e. times the size of every mesh axis that splits
            # an operand (axes no operand is split over only replicate the
            # same work, which a model-FLOPs count takes once)
            split = {a for spec in eqn.params["in_specs"] for entry in spec
                     if entry is not None
                     for a in (entry if isinstance(entry, tuple)
                               else (entry,))}
            shards = math.prod(eqn.params["mesh"].shape[a] for a in split)
            total += shards * _jaxpr_flops(eqn.params["jaxpr"])
        elif name == "pallas_call":
            # Prefer the kernel author's exact CostEstimate: our flash
            # kernels pass causal-aware counts (live diagonal blocks only).
            # Fallback — scale ONE tile's kernel body by the grid size, or
            # the kernel's matmuls vanish from the count; this overcounts
            # causal kernels ~2x (pl.when-skipped blocks), which is why the
            # estimate channel exists.
            ce = eqn.params.get("cost_estimate")
            ce_flops = getattr(ce, "flops", None) if ce is not None else None
            if ce_flops:
                total += float(ce_flops)
                continue
            grid = ()
            gm = eqn.params.get("grid_mapping")
            if gm is not None:
                grid = getattr(gm, "grid", ())
            body = eqn.params.get("jaxpr")
            if body is not None:
                tile = _jaxpr_flops(getattr(body, "jaxpr", body))
                total += tile * math.prod(int(g) for g in grid if
                                          isinstance(g, int))
        else:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr"):
                sub = eqn.params.get(key) if eqn.params else None
                if sub is not None:
                    total += _jaxpr_flops(getattr(sub, "jaxpr", sub))
            for key in ("branches",):
                subs = eqn.params.get(key) if eqn.params else None
                if subs:
                    # max over branches (cond executes one)
                    total += max(_jaxpr_flops(getattr(s, "jaxpr", s))
                                 for s in subs)
    return total


def jaxpr_matmul_flops(fn, *args, **kwargs) -> float:
    """Analytic matmul+conv FLOPs of `fn(*args)` — trace and walk the jaxpr."""
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)
    return _jaxpr_flops(jaxpr.jaxpr)


# -- MFU --------------------------------------------------------------------

def mfu_pct(flops_per_step: Optional[float], steps_per_sec: float,
            peak_tflops: Optional[float]) -> Optional[float]:
    if not flops_per_step or not peak_tflops:
        return None
    return 100.0 * flops_per_step * steps_per_sec / (peak_tflops * 1e12)


class MeasurementError(RuntimeError):
    """A benchmark number that cannot be true (e.g. implied FLOP/s > peak)."""


def check_mfu(mfu: Optional[float], context: str = "") -> Optional[str]:
    """Validate an MFU claim. Returns a warning string for suspicious-but-
    possible values; raises MeasurementError for impossible ones (>100% of
    the MXU peak means the timing or the FLOPs model is broken — the r2
    failure mode where 484 TFLOP/s was reported on a 197 TFLOP/s chip)."""
    if mfu is None:
        return None
    if mfu > 100.0:
        raise MeasurementError(
            f"measured MFU {mfu:.1f}% exceeds hardware peak ({context}); "
            "the timing harness or FLOPs model is broken — refusing to "
            "report an impossible number")
    if mfu > 60.0:
        return (f"MFU {mfu:.1f}% is above the ~60% typically achievable "
                f"({context}); verify the chip-peak table and timing")
    return None

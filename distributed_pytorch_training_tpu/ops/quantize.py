"""Fused int8 quantization — Pallas TPU kernels for the gradient-wire codecs.

The int8 wire codecs in ``parallel/grad_sync.py`` are XLA-composed today:
abs → max → divide → round → clip → convert for the quantize, and
convert → multiply → reduce for the dequant-accumulate. XLA schedules those
as separate HBM-roundtripping ops around the collective (visible as a fusion
chain on profiles), so each bucket pays several extra read/write passes of
bucket-sized fp32 data on the step's critical path. These kernels fuse each
codec hot loop into ONE VMEM pass (the ``ops/flash_attention.py`` machinery
applied to the wire):

* ``quantize_int8_rows_fused`` — the row-wise symmetric quantizer
  (``_quantize_int8_rows``'s grid): one running-absmax pass and one
  scale+round+clip pass over (block-sized) VMEM tiles, two-phase on the same
  Pallas grid so the input streams HBM→VMEM exactly twice and the s8 codes +
  fp32 scales are produced by one kernel launch.
* ``dequant_sum_rows_fused`` — the receive-side dequant-accumulate (the
  hop-1 local fp32 partial sum of ``_int8_multihop_sum``, and the same
  shape in the zero1 s8 scatter and the gather-form int8 sum): s8 codes ×
  per-row scales summed over rows in VMEM, one pass.

EXACTNESS CONTRACT (PARITY.md): both kernels are BIT-IDENTICAL to the
XLA-composed reference on the int8 grid — same absmax (exact, associative),
same ``max(amax, 1e-30)/127`` scale, same round/clip, same fp32
dequant-sum reduction order over the row axis. The fused path is a
scheduling change, never a numerics change; tests/test_quantize.py pins
code-for-code and bit-for-bit equality, and the int8/int8_multihop parity
suites run unchanged with the kernel path selected.

Gating (the ``flash_backend_supported`` convention): the kernels are worth
running only on real TPU — ``quantize_backend_supported()`` is the one
gate, and on CPU backends they run in interpreter mode (tests force the
fused path there to pin parity; the XLA-composed path stays the CPU/tier-1
reference by default). Selection order: an explicit
``TrainConfig.fused_quantize`` wins; else the ``DPT_FUSED_QUANTIZE`` env
("1"/"0") wins; else the backend gate decides.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Quantization grid half-width — MUST match parallel/grad_sync.py's _QMAX
# (symmetric [-127, 127]; -128 unused so dequantization is a pure scale).
QMAX = 127.0

# Env override for the fused-path default ("1" forces the kernels — on CPU
# that means interpreter mode, the parity-test configuration; "0" forces the
# XLA-composed reference). An explicit TrainConfig.fused_quantize beats it.
FUSED_QUANTIZE_ENV = "DPT_FUSED_QUANTIZE"


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def quantize_backend_supported(backend: Optional[str] = None) -> bool:
    """ONE place for the backend gate (the ``flash_backend_supported``
    convention): the fused codec kernels are worth running only on real
    TPU. CPU would run them in interpreter mode (pure overhead outside
    tests); the pltpu VMEM scratch shapes cannot lower on GPU."""
    return (backend or jax.default_backend()) == "tpu"


def fused_quantize_default() -> bool:
    """The auto gate: ``DPT_FUSED_QUANTIZE`` env override when set,
    otherwise TPU-only (`quantize_backend_supported`)."""
    env = os.environ.get(FUSED_QUANTIZE_ENV)
    if env is not None and env.strip() in ("0", "1"):
        return env.strip() == "1"
    return quantize_backend_supported()


def resolve_fused(flag: Optional[bool]) -> bool:
    """Resolve a TrainConfig-style tri-state (None = auto) to a concrete
    trace-time choice. Called at trace time by the grad_sync codecs."""
    return fused_quantize_default() if flag is None else bool(flag)


# fp32 input-tile budget per grid step: well under VMEM (~16MB on current
# parts) with room for the output/scratch refs riding the same step.
_TILE_BUDGET_BYTES = 512 * 1024


def _fit_block(s: int, n: int = 1) -> Tuple[int, int]:
    """(block_c, padded_s) for a length-``s`` lane axis of an ``n``-row
    tile: lane blocks must be multiples of 128 (TPU lane width) and tile
    the padded axis exactly. The block width scales inversely with the row
    count so one grid step streams ~``_TILE_BUDGET_BYTES`` of fp32 input
    regardless of shape — a single-row whole-bucket codec (the plain int8
    wire quantizes each bucket as one (1, ~1M) row) must not decay into
    thousands of DMA-latency-bound 2KB-tile steps. Block width never
    changes the numerics: row absmax is order-invariant and the dequant
    sum reduces over rows within a column, never across lane blocks.
    Inputs are zero-padded to ``padded_s`` by the wrappers — zeros never
    change a row's absmax (>= 0 with the 1e-30 floor) and dequantize-sum
    to exactly 0, so padding is invisible to the numerics."""
    if s <= 0:
        raise ValueError(f"quantize kernels need a non-empty row, got {s}")
    requested = max(512, _TILE_BUDGET_BYTES // (max(n, 1) * 4) // 128 * 128)
    block = min(requested, -(-s // 128) * 128)
    return block, -(-s // block) * block


# A whole-rows tile above this is split along the row axis as well: the
# int8 KV-page scatter quantizes (layers * positions * heads, head_dim)
# matrices — tens of thousands of SHORT rows — and an un-split (n, 128)
# tile of that outgrows VMEM (Mosaic: "ran out of memory in memory space
# vmem" from n ~ 36k on v5e). Wire buckets (n <= the replica count) never
# reach it, so their compiled programs are what they were.
_ROW_SPLIT_BYTES = 4 * 1024 * 1024


def _fit_rows(n: int, block_c: int) -> Tuple[int, int]:
    """(block_r, padded_n): all rows in one tile while the fp32 tile stays
    under `_ROW_SPLIT_BYTES`; otherwise ~`_TILE_BUDGET_BYTES` row blocks,
    a multiple of 32 rows (the int8 sublane tile). Rows are independent
    (one scale per row), so splitting them changes no value; the wrappers
    zero-pad to ``padded_n`` and slice the padding rows off."""
    if n * block_c * 4 <= _ROW_SPLIT_BYTES:
        return n, n
    block_r = max(32, _TILE_BUDGET_BYTES // (block_c * 4) // 32 * 32)
    return block_r, -(-n // block_r) * block_r


# ---------------------------------------------------------------------------
# fused quantize: running absmax pass + scale/round/clip pass, one launch
# ---------------------------------------------------------------------------


def _quantize_kernel(x_ref, q_ref, s_ref, amax_scr, *, nblocks: int):
    phase, j = pl.program_id(1), pl.program_id(2)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        amax_scr[...] = jnp.zeros_like(amax_scr)

    @pl.when(phase == 0)
    def _accumulate():
        # running per-row absmax across lane blocks — fp32 max is exact and
        # associative, so the blockwise running max IS the reference's
        # jnp.max(jnp.abs(rows), axis=1)
        amax_scr[...] = jnp.maximum(
            amax_scr[...],
            jnp.max(jnp.abs(x_ref[...]), axis=1, keepdims=True))

    # scale = amax * (1/127), an explicit multiply: XLA rewrites division
    # by a constant to exactly this inside compiled steps, so the multiply
    # IS the reference arithmetic (grad_sync._quantize_int8_rows matches).
    @pl.when((phase == 0) & (j == nblocks - 1))
    def _scales():
        s_ref[...] = jnp.maximum(amax_scr[...], 1e-30) * (1.0 / QMAX)

    @pl.when(phase == 1)
    def _codes():
        scale = jnp.maximum(amax_scr[...], 1e-30) * (1.0 / QMAX)
        q_ref[...] = jnp.clip(jnp.round(x_ref[...] / scale),
                              -QMAX, QMAX).astype(jnp.int8)


def quantize_int8_rows_fused(rows: jnp.ndarray
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused row-wise symmetric int8 quantization of a (n, s) fp32 matrix:
    one fp32 max-abs scale per row, s8 codes. Bit-identical to
    ``parallel.grad_sync._quantize_int8_rows`` (the XLA-composed
    reference) — same grid, same scale arithmetic, same round/clip."""
    n, s = rows.shape
    block_c, padded = _fit_block(s, n)
    block_r, padded_n = _fit_rows(n, block_c)
    nblocks = padded // block_c
    x = rows if (padded, padded_n) == (s, n) else jnp.pad(
        rows, ((0, padded_n - n), (0, padded - s)))
    q, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, nblocks=nblocks),
        # row blocks outermost: each runs its own two phases over the lane
        # blocks, with the (block_r, 1) running-absmax scratch re-zeroed at
        # the start of its phase 0
        grid=(padded_n // block_r, 2, nblocks),
        in_specs=[pl.BlockSpec((block_r, block_c),
                               lambda i, phase, j: (i, j))],
        out_shape=[
            jax.ShapeDtypeStruct((padded_n, padded), jnp.int8),
            jax.ShapeDtypeStruct((padded_n, 1), jnp.float32),
        ],
        out_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, phase, j: (i, j)),
            pl.BlockSpec((block_r, 1), lambda i, phase, j: (i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_r, 1), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            # two streaming passes (abs/max + div/round/clip), ~4 vector
            # ops per element; no transcendentals, no MXU
            flops=8 * padded_n * padded, transcendentals=0,
            bytes_accessed=(2 * padded_n * padded * 4 + padded_n * padded
                            + padded_n * 4)),
        interpret=_interpret(),
        name="fused_quantize_int8_rows",
    )(x)
    return q[:n, :s], scales[:n, 0]


# ---------------------------------------------------------------------------
# fused dequant-accumulate: codes x per-row scales summed over rows
# ---------------------------------------------------------------------------


def _dequant_sum_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = jnp.sum(q_ref[...].astype(jnp.float32) * s_ref[...],
                         axis=0, keepdims=True)


def dequant_sum_rows_fused(q: jnp.ndarray,
                           scales: jnp.ndarray) -> jnp.ndarray:
    """Fused SUM of dequantized rows: (n, s) s8 codes x (n,) fp32 per-row
    scales -> (s,) fp32 column sums — the receive-side accumulate of every
    int8 wire (the hop-1 local partial sum of ``_int8_multihop_sum``, the
    zero1 s8 scatter's sum, the gather-form int8 sum). Bit-identical to
    ``jnp.sum(q.astype(f32) * scales[:, None], axis=0)``: the reduction
    runs over the full row axis inside one VMEM tile, same order."""
    n, s = q.shape
    block_c, padded = _fit_block(s, n)
    x = q if padded == s else jnp.pad(q, ((0, 0), (0, padded - s)))
    out = pl.pallas_call(
        _dequant_sum_kernel,
        grid=(padded // block_c,),
        in_specs=[
            pl.BlockSpec((n, block_c), lambda j: (0, j)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
        ],
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        out_specs=pl.BlockSpec((1, block_c), lambda j: (0, j)),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * padded, transcendentals=0,
            bytes_accessed=n * padded + n * 4 + padded * 4),
        interpret=_interpret(),
        name="fused_dequant_sum_rows",
    )(x, scales[:, None])
    return out[0, :s]

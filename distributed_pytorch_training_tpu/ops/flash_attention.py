"""Blockwise (flash) attention — Pallas TPU kernels, forward AND backward.

Memory-efficient attention: O(S) live memory instead of materializing the
(S, S) score matrix, via online softmax over K/V blocks. This is the
long-context building block SURVEY.md §5 requires (the reference has no
attention at all — ResNet on 32x32 images; the capability enters through the
BERT-512/GPT-2 configs, BASELINE.json:11-12).

Design (per pallas_guide.md; FlashAttention-2 formulation):

* forward — grid (batch*heads, Sq/block_q, Sk/block_k), K block index
  innermost so VMEM scratch accumulators (running max m, denom l, output acc)
  carry across K iterations; ONLY one (block_q, d) + (block_k, d) tile lives
  in VMEM at a time — full K/V never does (the r2 kernel held all of K/V per
  (batch, head), capping sequence length at VMEM size). Emits the row
  logsumexp for the backward. MXU matmuls via jnp.dot(...,
  preferred_element_type=f32); softmax statistics in f32.
* causal masking skips whole K blocks past the diagonal (pl.when on the
  block index — no MXU work issued; the rectangular grid still walks the
  masked steps and their tile DMAs, which overlap live blocks' compute),
  masking only the diagonal blocks with broadcasted_iota.
* backward — two Pallas kernels, no O(S^2) rematerialization:
  - dK/dV: grid (..., Sk/block_k, Sq/block_q), Q innermost; for each Q block
    regenerate p = exp(s - lse), accumulate dv += p^T dO and
    dk += (p * (dO v^T - delta))^T q in VMEM scratch.
  - dQ: grid (..., Sq/block_q, Sk/block_k), K innermost; accumulate
    dq += (p * (dO v^T - delta)) k.
  delta = rowsum(dO * O) is a cheap elementwise XLA op outside the kernels.
  Causal variants skip fully-masked blocks entirely.
* on CPU backends (tests, dry-runs) the kernels run in interpreter mode —
  the S=4096 grad-parity test in tests/test_attention.py runs there.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)

# The three kernels are named `flash_fwd`, `flash_bwd_dkv`, `flash_bwd_dq`:
# each name is its `pallas_call`'s ``name`` and the innermost
# `jax.named_scope` around it, so the compiled instruction and its path in a
# profiler trace both carry it (the benchmark's `flash_fwd_ms` /
# `flash_bwd_ms` read device time by these names).


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_backend_supported(backend: Optional[str] = None) -> bool:
    """ONE place for the backend gate shared by the experiments harness and
    ``--attention auto``: the kernels are worth running only on real TPU.
    CPU would run pallas in interpreter mode (pure overhead); the pltpu
    VMEM scratch shapes cannot lower on GPU."""
    return (backend or jax.default_backend()) == "tpu"


def flash_supports_length(s: int, requested: int = 512) -> bool:
    """True iff `_fit_block` can pick a usable block for a length-`s` axis —
    lets ``--attention auto`` fall back to the einsum path instead of
    erroring on lengths with no multiple-of-8 divisor (> 1024)."""
    try:
        _fit_block(requested, s)
        return True
    except ValueError:
        return False


def _fit_block(requested: int, s: int) -> int:
    """Largest legal block size <= `requested` for a length-`s` axis.

    TPU lowering needs the sublane block dim divisible by 8 (or spanning the
    whole axis), and pallas grids need block | s. Prefers the largest
    divisor of s that is a multiple of 8 and <= requested; falls back to the
    full axis (always legal). 512 beat 128/256 on v5e for GPT-2 @ S=1024
    (90.7 vs 143.5 / 109.6 ms per train step), hence the public default.

    An explicit multiple-of-8 request that divides s is honored as-is (the
    %8 requirement is the TPU sublane rule; e.g. requested=100 with s=200
    divides evenly but still goes through the search) — a caller asking for
    small legal blocks gets them (minimal VMEM, their trade); the
    degenerate-grid floor below only guards the *auto-degradation* path
    where a large request would silently shrink to slivers."""
    b = min(max(requested, 8), s)
    if s % b == 0 and (b % 8 == 0 or b == s):
        return b
    # Degenerate divisors make degenerate grids (S=2056 = 8*257 would run
    # 8-wide tiles on a 128-wide MXU), so only accept blocks that keep the
    # grid reasonable: >= 128 wide, or at most 8 blocks along the axis.
    floor = min(128, max(1, s // 8))
    for cand in range(b - b % 8, 7, -8):
        if s % cand == 0 and cand >= floor:
            return cand
    # No usable divisor: spanning the axis is always legal and fine for
    # short sequences, but it would forfeit the blockwise VMEM bound for
    # long ones — fail loudly there instead.
    if s > 1024:
        raise ValueError(
            f"flash_attention: sequence length {s} has no usable block "
            f"size; pad the sequence to a multiple of 128")
    return s


def _reference_attention(q, k, v, causal: bool, sm_scale: float,
                         kv_valid=None):
    """XLA einsum attention — the parity oracle for tests."""
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * sm_scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))[None, None]
        logits = jnp.where(mask, logits, NEG_INF)
    if kv_valid is not None:
        logits = jnp.where(kv_valid[:, None, None, :] > 0, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", weights, v)


def _live_pairs(nqb: int, nkb: int, block_q: int, block_k: int,
                causal: bool) -> int:
    """Number of (q-block, k-block) grid pairs that issue MXU work — causal
    skips blocks fully above the diagonal, so FLOPs accounting that scales
    one tile by the whole grid would overcount attention ~2x."""
    if not causal:
        return nqb * nkb
    qb = np.arange(nqb)[:, None] * block_q + block_q - 1
    kb = np.arange(nkb)[None, :] * block_k
    return int(np.sum(qb >= kb))


def _cost(flops: float, transcendentals: float, bytes_accessed: float):
    """Exact per-call cost handed to pallas_call so FLOPs instruments (XLA's
    and experiments/flops.py's jaxpr walk) see the causal-aware count
    instead of scaling one tile's matmuls by the full rectangular grid."""
    return pl.CostEstimate(flops=int(flops),
                           transcendentals=int(transcendentals),
                           bytes_accessed=int(bytes_accessed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs,
                block_q: int, block_k: int, causal: bool, sm_scale: float,
                masked: bool):
    if masked:
        kvm_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        kvm_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = None, refs
    qb, kb = pl.program_id(1), pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: K blocks fully above the diagonal contribute nothing
    live = (qb * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if masked:
            # key-padding: masked keys contribute nothing to any query row.
            # Safe online-softmax interaction: an all-masked block leaves m
            # at NEG_INF, so p==1 garbage can accumulate only until the
            # first live block, whose alpha rescales it to exactly 0.
            s = jnp.where(kvm_ref[0, 0][None, :] > 0, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def _flash_fwd_lse(q, k, v, causal: bool, sm_scale: float,
                   block_q: int, block_k: int,
                   kv_valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (BH, Sq, d) folded back to (B, Sq, H, d), lse (BH, 1, Sq)).
    `kv_valid`: optional (B, Sk) float validity mask (1=real key, 0=pad)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    masked = kv_valid is not None

    grid = (b * h, sq // block_q, sk // block_k)
    live = _live_pairs(sq // block_q, sk // block_k, block_q, block_k, causal)
    # lse rides as (BH, 1, Sq): a 2-D (BH, Sq) output with block (1, block_q)
    # violates the TPU lowering rule that the second-to-last block dim be
    # divisible by 8 or span the array dim; the singleton middle axis spans
    # its dim, making the (1, 1, block_q) block legal on hardware.
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
    ]
    operands = [qf, kf, vf]
    if masked:
        # (B, 1, Sk) so the (1, 1, block_k) block lowers like lse does; the
        # index map folds heads back to the batch row — no BH-sized copy.
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, i, j, h=h: (bh // h, 0, j)))
        operands.append(kv_valid.astype(jnp.float32)[:, None, :])
    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale, masked=masked),
        name="flash_fwd",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        cost_estimate=_cost(
            # per live pair per bh: QK^T + PV, 2*2*bq*bk*d
            flops=b * h * live * 4 * block_q * block_k * d,
            # exp(s - m_new) per live tile + the finalize log per q row
            transcendentals=b * h * (live * block_q * block_k + sq),
            bytes_accessed=(
                b * h * grid[1] * grid[2] *
                (block_q * d + 2 * block_k * d) * q.dtype.itemsize
                + b * h * sq * (d * q.dtype.itemsize + 4))),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_fwd"):
        out, lse = fwd(*operands)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    block_q: int, block_k: int, causal: bool,
                    sm_scale: float, masked: bool):
    if masked:
        kvm_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        kvm_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = None, refs
    kb, qb = pl.program_id(1), pl.program_id(2)
    nqb = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (qb * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)                # (bq, d)
        lse = lse_ref[0, 0][:, None]                      # (bq, 1)
        delta = delta_ref[0, 0][:, None]                  # (bq, 1)
        s = sm_scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if masked:
            # re-mask in the backward: without it p=exp(s-lse) would be
            # nonzero at padded keys and leak gradient into padding K/V
            s = jnp.where(kvm_ref[0, 0][None, :] > 0, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qb == nqb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   block_q: int, block_k: int, causal: bool,
                   sm_scale: float, masked: bool):
    if masked:
        kvm_ref, dq_ref, dq_scr = refs
    else:
        kvm_ref, (dq_ref, dq_scr) = None, refs
    qb, kb = pl.program_id(1), pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (qb * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = sm_scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if masked:
            s = jnp.where(kvm_ref[0, 0][None, :] > 0, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kb == nkb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal: bool, sm_scale: float,
               block_q: int, block_k: int, kv_valid=None):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    masked = kv_valid is not None

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dof = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    of = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term;
    # (BH, 1, Sq) like lse so its (1, 1, block_q) block lowers on TPU.
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]
    kvm = kv_valid.astype(jnp.float32)[:, None, :] if masked else None

    nqb, nkb = sq // block_q, sk // block_k
    live = _live_pairs(nqb, nkb, block_q, block_k, causal)
    read_bytes = (b * h * nqb * nkb *
                  (2 * block_q * d + 2 * block_k * d) * q.dtype.itemsize)

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, j))
    dkv_in_specs = [
        q_spec,                                               # q by j
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
        q_spec,                                               # dO by j
        row_spec,                                             # lse by j
        row_spec,                                             # delta by j
    ]
    dkv_operands = [qf, kf, vf, dof, lse, delta]
    if masked:
        # the K-block index is i in this kernel's grid
        dkv_in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, i, j, h=h: (bh // h, 0, i)))
        dkv_operands.append(kvm)
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale, masked=masked),
        name="flash_bwd_dkv",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        grid=(b * h, nkb, nqb),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        cost_estimate=_cost(
            # per live pair: s, dv+=p^T dO, dp=dO v^T, dk+=ds^T q
            flops=b * h * live * 8 * block_q * block_k * d,
            transcendentals=b * h * live * block_q * block_k,
            bytes_accessed=read_bytes +
            b * h * 2 * sk * d * k.dtype.itemsize),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv(*dkv_operands)

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
    ]
    dq_operands = [qf, kf, vf, dof, lse, delta]
    if masked:
        dq_in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, i, j, h=h: (bh // h, 0, j)))
        dq_operands.append(kvm)
    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale, masked=masked),
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, nqb, nkb),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        cost_estimate=_cost(
            # per live pair: s, dp=dO v^T, dq+=ds k
            flops=b * h * live * 6 * block_q * block_k * d,
            transcendentals=b * h * live * block_q * block_k,
            bytes_accessed=read_bytes +
            b * h * sq * d * q.dtype.itemsize),
        interpret=_interpret(),
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(*dq_operands)

    def unflat(x, s):
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    kv_valid: Optional[jnp.ndarray] = None,  # (B, Sk), 1=real key, 0=pad
) -> jnp.ndarray:
    """Blockwise attention; numerically equivalent to softmax(QK^T*scale)V.

    `kv_valid` is a key-padding validity mask applied inside the blocks
    (forward AND backward recompute), so padded batches keep the flash fast
    path. Rows whose keys are ALL masked emit mean(V) — the standard
    contract that the loss zero-weights padded query rows (then their
    cotangent is exactly 0 and no gradient leaks through the garbage)."""
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, _ = _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k,
                            kv_valid)
    return out


def _vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_valid=None):
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, lse = _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k,
                              kv_valid)
    return out, (q, k, v, out, lse, kv_valid)


def _vjp_bwd(causal, sm_scale, block_q, block_k, residuals, g):
    q, k, v, out, lse, kv_valid = residuals
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q,
                            block_k, kv_valid)
    dmask = None if kv_valid is None else jnp.zeros_like(kv_valid)
    return dq, dk, dv, dmask


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


def _as_kv_valid(mask, batch: int, sk: int) -> Optional[jnp.ndarray]:
    """Extract a (B, Sk) key-validity vector from a models.layers-style
    attention mask (broadcastable to (B, H, Sq, Sk), True=attend), or None
    when the mask is not a pure key-padding pattern."""
    if mask is None:
        return None
    shape = tuple(mask.shape)
    # the padding_mask() form: (B, 1, 1, Sk) — constant over heads and rows
    if len(shape) == 4 and shape[0] in (1, batch) and shape[1] == 1 \
            and shape[2] == 1 and shape[3] == sk:
        kv = mask[:, 0, 0, :]
        return jnp.broadcast_to(kv, (batch, sk))
    if len(shape) == 2 and shape == (batch, sk):
        return mask
    return None


def make_flash_attention_fn(causal: bool, block_q: int = 512,
                            block_k: int = 512, mesh=None):
    """Adapter matching models.layers' `attention_fn(q, k, v, mask, dtype)`.

    Causal structure is handled inside the kernel via block skipping (faster
    than passing a causal mask to the einsum path). Key-padding masks — the
    (B, 1, 1, Sk) form layers.padding_mask produces — ride the kernel too,
    so real padded batches (BERT MLM) keep the flash path. Any other mask
    shape falls back to the XLA einsum path rather than erroring: the fast
    path must cover all data, and general (Sq, Sk)-structured masks have no
    blockwise formulation here.

    ``mesh``: GSPMD cannot partition a Mosaic kernel ("Mosaic kernels
    cannot be automatically partitioned" — a lowering error on any
    multi-device TPU program), so on a mesh of more than one device the
    call runs per shard inside a `shard_map` over the layout GSPMD already
    gives attention operands: batch over the batch axes, heads over
    ``model``. Traced from inside an explicit shard_map step (every axis
    already manual, operands already per-shard) it is the plain call."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import shard_map
    from ..parallel.mesh import BATCH_AXES, MODEL, batch_shard_count

    def kernel(q, k, v, kv_valid):
        return flash_attention(q, k, v, causal, None, block_q, block_k,
                               kv_valid)

    def attention_fn(q, k, v, mask=None, dtype=jnp.float32):
        kv_valid = _as_kv_valid(mask, q.shape[0], k.shape[1])
        if mask is not None and kv_valid is None:
            from ..models.layers import dot_product_attention

            if causal:
                cm = jnp.tril(jnp.ones((q.shape[1], k.shape[1]),
                                       bool))[None, None]
                mask = mask.astype(bool) & cm
            return dot_product_attention(q, k, v, mask=mask, dtype=dtype)
        if mesh is None or mesh.size == 1 \
                or jax.sharding.get_abstract_mesh().manual_axes:
            return kernel(q, k, v, kv_valid).astype(dtype)
        # an axis that does not divide its dimension (the B=1 init trace)
        # stays unsplit: every device then runs the whole (tiny) call
        b_axes = BATCH_AXES if q.shape[0] % batch_shard_count(mesh) == 0 \
            else None
        h_axis = MODEL if q.shape[2] % mesh.shape[MODEL] == 0 else None
        spec = P(b_axes, None, h_axis, None)
        masks = () if kv_valid is None else (kv_valid,)
        return shard_map(
            lambda q, k, v, kv_valid=None: kernel(q, k, v, kv_valid),
            mesh=mesh,
            in_specs=(spec, spec, spec) + (P(b_axes, None),) * len(masks),
            out_specs=spec)(q, k, v, *masks).astype(dtype)

    return attention_fn

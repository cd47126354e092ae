"""Blockwise (flash) attention — Pallas TPU kernels, forward AND backward.

Memory-efficient attention: O(S) live memory instead of materializing the
(S, S) score matrix, via online softmax over K/V blocks. This is the
long-context building block SURVEY.md §5 requires (the reference has no
attention at all — ResNet on 32x32 images; the capability enters through the
BERT-512/GPT-2 configs, BASELINE.json:11-12).

Design (per pallas_guide.md; FlashAttention-2 formulation):

* one head per grid step; q, k, v (and dO) are folded to (batch*heads, S, d)
  outside the kernels, and a softmax scale that is a power of two (1/8 at
  d=64, 1/16 at d=256) is multiplied into q there, exactly, so no score is.
* forward — grid (batch*heads, Sq/block_q, Sk/block_k), K block index
  innermost so VMEM scratch accumulators (running max m, denom l, output acc)
  carry across K iterations; ONLY one (block_q, d) + (block_k, d) tile lives
  in VMEM at a time — full K/V never does. Emits the row logsumexp for the
  backward.
* precision (PARITY.md, "Exactness model: the flash attention kernels"):
  tiles go to the MXU in the dtype they arrive in,
  `preferred_element_type=float32`; p and ds are cast once to that dtype
  before their products; scores, `exp`, the softmax statistics, the
  accumulators, lse and delta are float32. float32 inputs keep float32
  products.
* statistics are lane-dense: m and l are (block_q, 128) lane-replicated
  scratch (`_across` lays them against a tile as the same vregs again, no
  column -> lanes broadcast per score); the dK/dV kernel works on the
  TRANSPOSED tile (keys x queries), where lse and delta are the (1, block_q)
  rows they are stored as and p^T, ds^T are already the left operands of
  their products; the dQ kernel spreads lse and delta to (block_q, 128) once
  a q block.
* causal: the bodies are chosen by the block indices (`_run_live_tiles`). A
  tile above the diagonal runs nothing (pl.when — no MXU work issued; the
  rectangular grid still walks the step and its tile DMAs). A tile wholly
  under it runs with no iota, compare or select. The tile ON the diagonal of
  square blocks is walked in blocks of rows (`_FORWARD_WALK_ROWS` queries;
  `_BACKWARD_WALK_ROWS` queries or, transposed, keys), each against only the
  keys (queries) it may see, so the products and the mask shrink towards
  the causal half. `tile_census` counts all of this from the shapes.
* blocks: a caller's explicit `block_q` / `block_k` are honoured; left
  ``None`` they are chosen from the shapes (`_blocks`, which holds the
  sweep the rule came from).
* backward — two Pallas kernels, no O(S^2) rematerialization:
  - dK/dV: grid (..., Sk/block_k, Sq/block_q), Q innermost; for each Q block
    regenerate p^T = exp(s^T - lse), accumulate dv += p^T dO and
    dk += (p^T * (v dO^T - delta)) q in VMEM scratch.
  - dQ: grid (..., Sq/block_q, Sk/block_k), K innermost; accumulate
    dq += (p * (dO v^T - delta)) k.
  delta = rowsum(dO * O) is a cheap elementwise XLA op outside the kernels.
* on CPU backends (tests, dry-runs) the kernels run in interpreter mode —
  the S=4096 grad-parity test in tests/test_attention.py runs there.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)
# what the differentiated forward's output and log-sum-exp are called, as
# the residuals hold them, for a remat policy that keeps them
# (`save_only_these_names`: the backward then reads the first pass's and
# `flash_fwd` runs once); an identity under no policy or another
RESIDUAL_NAMES = ("flash_out", "flash_lse")

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
    full axis (always legal). What is requested when a caller names no size
    is `_blocks`' matter.

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


def _blocks(block_q: Optional[int], block_k: Optional[int], q, k
            ) -> Tuple[int, int]:
    """The (block_q, block_k) a call runs with: a caller's explicit size as
    it is, ``None`` chosen from what the call can observe, both fitted to
    their axis.

    The rule: square blocks of 1024 while one (1024, d) tile is at most
    512 KiB (bf16 up to d=256, float32 up to d=128), else 512 (float32 at
    d=256 in 1024-blocks does not fit the 16 MiB of VMEM a kernel gets).
    Where it came from (TPU v5 lite; PERF.md section 6, PR 34; ms a call,
    forward / dK/dV / dQ, bf16, causal, before the diagonal walk):

    ====================  ===================  ===================
    blocks                8x16 heads of 64,    16 heads of 256,
                          S=1024               S=8192
    ====================  ===================  ===================
    128 x 128             2.35 / 2.94 / 2.49   26.7 / 27.6 / -
    256 x 256             1.02 / 1.34 / 1.07   9.00 / 10.4 / -
    512 x 512             0.64 / 0.78 / 0.73   5.12 / 7.53 / 6.74
    1024 x 1024           0.54 / 0.80 / 0.58   4.44 / 7.03 / -
    rectangles of those   between their sides' squares, never better
    2048 x 512            -                    out of VMEM
    ====================  ===================  ===================

    A grid step costs its set-up whatever it computes, so the blocks that
    execute the fewest scores (128: 590k of a head's 1.05M at S=1024) are
    the slowest by 4x: at S=1024 one tile holds the whole head. The causal
    half comes back INSIDE the tile (`_run_live_tiles`' walk): with it the
    1024-blocks read 0.43 / 0.52 / 0.38 and 4.17 / 6.43 / 5.50."""
    chosen = 1024 if q.shape[-1] * q.dtype.itemsize <= 512 else 512
    return (_fit_block(block_q or chosen, q.shape[1]),
            _fit_block(block_k or chosen, k.shape[1]))


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


_LANES = 128
# a @ b.T as ONE product: the last axis of both contracts, which the MXU
# takes as it is (an explicit ``b.T`` is a pass through the transpose unit)
_NT = (((1,), (1,)), ((), ()))


def _tile_is_live(qb, kb, block_q: int, block_k: int):
    """Causal: does tile (qb, kb) hold a key some query of it may see?"""
    return qb * block_q + block_q - 1 >= kb * block_k


def _tile_is_full(qb, kb, block_q: int, block_k: int):
    """Causal: may EVERY query of tile (qb, kb) see every key of it? Such a
    tile lies wholly under the diagonal and runs the body without a mask."""
    return qb * block_q >= kb * block_k + block_k - 1


_WHOLE = slice(None)
# The tile on the diagonal is walked in blocks of this many queries (keys, in
# the transposed dK/dV kernel), each against only the keys (queries) it may
# see. On the chip (PERF.md section 6, PR 34; GPT-2's 8 x 16 heads of 64 at
# S=1024 in one 1024-tile a head, ms a call at 128 / 256 / 512 rows and
# unwalked): forward 0.501 / 0.442 / 0.426 / 0.544 — every block pays its
# own softmax statistics, so fewer and wider; dK/dV 0.523 / 0.556 / 0.632 /
# 0.805 and dQ 0.381 / 0.385 / 0.451 / 0.582 — no statistics there, so the
# fewest scores. 16 heads of 256 at S=8192 move under 2% between them.
_FORWARD_WALK_ROWS = 512
_BACKWARD_WALK_ROWS = 128


def _walk_rows(block_q: int, block_k: int, rows: int) -> int:
    """`rows` where a crossed tile is walked in blocks of that many rows, 0
    where it is taken whole: only square blocks put a crossed tile's first
    query on its first key, which is what makes each block's keys static."""
    walks = block_q == block_k and block_q % rows == 0 and block_q > rows
    return rows if walks else 0


class TileCensus(NamedTuple):
    """The grid's (q-block, k-block) tiles of one head by what a kernel does
    there: nothing, the masked body (or the walk), the unmasked body."""
    skipped: int
    crossed: int
    full: int
    block_q: int
    block_k: int

    def scores(self, walk_rows: int) -> int:
        """The scores a kernel that walks its diagonal tiles in blocks of
        `walk_rows` computes over one head: a walked tile computes its
        blocks' rectangles, not the square."""
        rows = _walk_rows(self.block_q, self.block_k, walk_rows)
        walked = sum(rows * stop
                     for stop in range(rows, self.block_q + 1, rows)) \
            if rows else self.block_q * self.block_k
        return self.full * self.block_q * self.block_k + self.crossed * walked


def tile_census(sq: int, sk: int, block_q: int, block_k: int,
                causal: bool) -> TileCensus:
    """How often each body engages, from the shapes alone: the kernels'
    `CostEstimate`s (FLOPs accounting that scaled one tile by the whole
    grid would overcount causal attention ~2x) and PERF.md quote it.
    S=1024 in 512-blocks: 1 skipped, 2 crossed, 1 full; S=8192: 120, 16,
    120; in 1024-blocks 0, 1, 0 and 28, 8, 28."""
    nqb, nkb = sq // block_q, sk // block_k
    if not causal:
        return TileCensus(0, 0, nqb * nkb, block_q, block_k)
    qb, kb = np.arange(nqb)[:, None], np.arange(nkb)[None, :]
    live = _tile_is_live(qb, kb, block_q, block_k)
    full = _tile_is_full(qb, kb, block_q, block_k)
    return TileCensus(int(np.sum(~live)), int(np.sum(live & ~full)),
                      int(np.sum(full)), block_q, block_k)


def _cost(flops: float, transcendentals: float, bytes_accessed: float):
    """Exact per-call cost handed to pallas_call so FLOPs instruments (XLA's
    and experiments/flops.py's jaxpr walk) see the causal-aware count
    instead of scaling one tile's matmuls by the full rectangular grid."""
    return pl.CostEstimate(flops=int(flops),
                           transcendentals=int(transcendentals),
                           bytes_accessed=int(bytes_accessed))


def _scale_folds(sm_scale: float) -> bool:
    """A power of two (1/8 at d=64, 1/16 at d=256) scales q exactly in any
    float dtype, so it rides the (b*h, s, d) folding outside the kernels and
    no score is multiplied; any other scale multiplies the float32 scores."""
    return math.frexp(sm_scale)[0] == 0.5


def _across(x, n: int):
    """(rows, 128) lane-replicated statistics against a (rows, n) tile: the
    same vregs again for every 128 columns, no column -> lanes broadcast."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _replicated(row):
    """A (n,) row of per-query numbers as (n, 128) lane-replicated columns:
    the one lanes -> sublanes relayout a q block pays, at its first step."""
    return jnp.broadcast_to(row[:, None], (row.shape[0], _LANES))


def _causal_masked(s, q0, k0, q_axis: int, causal_block: int = 1):
    """The scores `s` of a block whose first query is at position q0 and
    first key at k0 (queries along `q_axis`), NEG_INF where the key lies
    after its query; with ``causal_block`` B > 1 (a power of two), after
    the last position of its query's block of B (``k <= q | (B - 1)``).
    Tiles and the walk's blocks are multiples of B, so which tiles are
    dead, crossed or walked is the causal rule's (`_check_causal_block`)."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if causal_block > 1:
        q_pos = q_pos | (causal_block - 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _check_causal_block(causal_block: int, causal: bool, block_q: int,
                        block_k: int) -> None:
    """Block-causal attention rides the causal kernel's tiling: a dead tile
    is dead and a crossed tile crossed under either mask once every tile and
    every block of the diagonal walk starts on a block of B (a tile the
    causal rule calls full is full under the wider mask too; one it calls
    crossed runs masked, which is right wherever the diagonal lies)."""
    if causal_block == 1:
        return
    if not causal or causal_block < 1 or causal_block & (causal_block - 1):
        raise ValueError(
            f"causal_block={causal_block} needs causal=True and a power of "
            "two")
    if block_q % causal_block or block_k % causal_block \
            or _FORWARD_WALK_ROWS % causal_block:
        raise ValueError(
            f"blocks {block_q} x {block_k} are not whole blocks of "
            f"{causal_block} positions")


def _run_live_tiles(step, qb, kb, block_q: int, block_k: int, causal: bool,
                    walk_rows: int, walk_keys: bool = False):
    """``step(q_rows, k_rows, q0, k0)`` is a kernel's work on the scores of
    the tile's queries ``q_rows`` against its keys ``k_rows`` (static slices
    of the tile): with ``q0`` / ``k0``, the first query's and key's position,
    under the causal mask, with ``None`` unmasked. Traced as separate bodies
    chosen by the block indices:

    * a tile wholly under the diagonal: the whole tile, no iota, compare or
      select;
    * a tile above it: nothing;
    * the tile ON the diagonal of square blocks (first query = first key):
      walked in blocks of `walk_rows` queries, each against the keys up
      to its last query, so the masked rectangle and the products shrink to
      what the causal half needs plus half a block a row (`walk_keys`: the
      transposed kernel walks blocks of keys against the queries from its
      first key on);
    * any other crossed tile (block_q != block_k): the whole tile, masked.
    """
    if not causal:
        step(_WHOLE, _WHOLE, None, None)
        return
    live = _tile_is_live(qb, kb, block_q, block_k)
    full = _tile_is_full(qb, kb, block_q, block_k)
    pl.when(full)(lambda: step(_WHOLE, _WHOLE, None, None))

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _crossed():
        rows = _walk_rows(block_q, block_k, walk_rows)
        if not rows:
            step(_WHOLE, _WHOLE, qb * block_q, kb * block_k)
            return
        for start in range(0, block_q, rows):
            block = slice(start, start + rows)
            if walk_keys:
                step(slice(start, block_q), block, start, start)
            else:
                step(block, slice(0, start + rows), start, 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs,
                block_q: int, block_k: int, causal: bool,
                score_scale: Optional[float], masked: bool,
                causal_block: int = 1):
    if masked:
        kvm_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        kvm_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = None, refs
    qb, kb = pl.program_id(1), pl.program_id(2)
    nkb = pl.num_programs(2)
    d = v_ref.shape[-1]           # the accumulator's width is the values'

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(q_rows, k_rows, q0, k0):
        v = v_ref[0, k_rows, :]
        s = jax.lax.dot_general(q_ref[0, q_rows, :], k_ref[0, k_rows, :], _NT,
                                preferred_element_type=jnp.float32)
        if score_scale is not None:
            s = s * score_scale
        if q0 is not None:
            s = _causal_masked(s, q0, k0, q_axis=0,
                               causal_block=causal_block)
        if masked:
            # key-padding: masked keys contribute nothing to any query row.
            # Safe online-softmax interaction: an all-masked block leaves m
            # at NEG_INF, so p==1 garbage can accumulate only until the
            # first live block, whose alpha rescales it to exactly 0.
            s = jnp.where(kvm_ref[0, :, k_rows] > 0, s, NEG_INF)
        m_prev = m_scr[q_rows, :]                          # (rows, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _across(m_new, s.shape[1]))
        m_scr[q_rows, :] = m_new
        l_scr[q_rows, :] = l_scr[q_rows, :] * alpha \
            + p.sum(axis=-1, keepdims=True)
        acc_scr[q_rows, :] = acc_scr[q_rows, :] * _across(alpha, d) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _run_live_tiles(step, qb, kb, block_q, block_k, causal,
                    _FORWARD_WALK_ROWS)

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / _across(l, d)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def _folded(x):
    """(B, S, H, d) -> (B*H, S, d): a grid step's head in contiguous rows."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _flash_fwd_lse(q, k, v, causal: bool, sm_scale: float,
                   block_q: Optional[int], block_k: Optional[int],
                   kv_valid=None, causal_block: int = 1
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (B, Sq, H, d), lse (BH, 1, Sq)). `kv_valid`: optional
    (B, Sk) float validity mask (1=real key, 0=pad). Blocks left ``None``
    are chosen from the shapes (`_blocks`)."""
    block_q, block_k = _blocks(block_q, block_k, q, k)
    _check_causal_block(causal_block, causal, block_q, block_k)
    return _fwd_call(q, k, v, kv_valid, causal=causal,
                     sm_scale=float(sm_scale), block_q=block_q,
                     block_k=block_k, interpret=_interpret(),
                     causal_block=causal_block)


# `jit(inline=True)` here and on `_bwd_call`: a train step holds these
# kernels at 72 call sites (24 layers, forward and two backward), each kernel
# is traced as several bodies (unmasked, masked, every block of the walk),
# and tracing is paid by every process, compile cache or not. jit keeps the
# traced call by its shapes and static arguments, so a body is traced once a
# process; `inline` puts its equations into the caller at each site, under
# the caller's scope path (a jitted call proper is lowered once for all sites
# and its operations lose the path the region metrics read).
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret", "causal_block"))
def _fwd_call(q, k, v, kv_valid, *, causal: bool, sm_scale: float,
              block_q: int, block_k: int, interpret: bool,
              causal_block: int = 1):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    masked = kv_valid is not None
    folds = _scale_folds(sm_scale)
    qf, kf, vf = _folded(q * sm_scale if folds else q), _folded(k), _folded(v)

    grid = (b * h, sq // block_q, sk // block_k)
    scores = tile_census(sq, sk, block_q, block_k, causal).scores(
        _FORWARD_WALK_ROWS)
    # lse rides as (BH, 1, Sq): a 2-D (BH, Sq) output with block (1, block_q)
    # violates the TPU lowering rule that the second-to-last block dim be
    # divisible by 8 or span the array dim; the singleton middle axis spans
    # its dim, making the (1, 1, block_q) block legal on hardware.
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda bh, i, j: (bh, j, 0)),
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
                          causal=causal,
                          score_scale=None if folds else sm_scale,
                          masked=masked, causal_block=causal_block),
        name="flash_fwd",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        cost_estimate=_cost(
            # per computed score: QK^T over d and PV over the values' width
            flops=b * h * scores * 2 * (d + dv),
            # exp(s - m_new) per score + the finalize log per q row
            transcendentals=b * h * (scores + sq),
            bytes_accessed=(
                b * h * grid[1] * grid[2] *
                (block_q * d + block_k * (d + dv)) * q.dtype.itemsize
                + b * h * sq * (dv * q.dtype.itemsize + 4))),
        interpret=interpret,
    )
    with jax.named_scope("flash_fwd"):
        out, lse = fwd(*operands)
    return out.reshape(b, h, sq, dv).transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    block_q: int, block_k: int, causal: bool,
                    score_scale: Optional[float], masked: bool):
    """Works on the TRANSPOSED tile, keys down the sublanes and queries
    along the lanes: s^T = k q^T is one NT product, lse and delta are read
    as the (1, block_q) rows they are stored as and spread down the
    sublanes, and p^T, ds^T are the left operands dv += p^T dO and
    dk += ds^T q want, so no (block_q, block_k) tile is ever transposed."""
    if masked:
        kvm_ref, dk_ref, dv_ref, dk_scr, dv_scr, kvm_scr = refs
    else:
        kvm_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = None, refs
    kb, qb = pl.program_id(1), pl.program_id(2)
    nqb = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if masked:
            kvm_scr[...] = _replicated(kvm_ref[0, 0])

    def step(q_rows, k_rows, q0, k0):
        q, do = q_ref[0, q_rows, :], do_ref[0, q_rows, :]
        s = jax.lax.dot_general(k_ref[0, k_rows, :], q, _NT,  # (keys, queries)
                                preferred_element_type=jnp.float32)
        if score_scale is not None:
            s = s * score_scale
        if q0 is not None:
            s = _causal_masked(s, q0, k0, q_axis=1)
        if masked:
            # re-mask in the backward: without it p=exp(s-lse) would be
            # nonzero at padded keys and leak gradient into padding K/V
            s = jnp.where(_across(kvm_scr[k_rows, :], s.shape[1]) > 0, s,
                          NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, q_rows])
        dv_scr[k_rows, :] += jnp.dot(p.astype(do.dtype), do,
                                     preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, k_rows, :], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, q_rows])
        if score_scale is not None:
            ds = ds * score_scale
        # a folded scale is already in q: dk = ds^T (scale q)
        dk_scr[k_rows, :] += jnp.dot(ds.astype(q.dtype), q,
                                     preferred_element_type=jnp.float32)

    _run_live_tiles(step, qb, kb, block_q, block_k, causal,
                    _BACKWARD_WALK_ROWS, walk_keys=True)

    @pl.when(qb == nqb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   block_q: int, block_k: int, causal: bool,
                   score_scale: Optional[float], out_scale: float,
                   masked: bool):
    if masked:
        kvm_ref, dq_ref, dq_scr, lse_scr, delta_scr = refs
    else:
        kvm_ref, (dq_ref, dq_scr, lse_scr, delta_scr) = None, refs
    qb, kb = pl.program_id(1), pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _replicated(lse_ref[0, 0])
        delta_scr[...] = _replicated(delta_ref[0, 0])

    def step(q_rows, k_rows, q0, k0):
        k, do = k_ref[0, k_rows, :], do_ref[0, q_rows, :]
        s = jax.lax.dot_general(q_ref[0, q_rows, :], k, _NT,  # (queries, keys)
                                preferred_element_type=jnp.float32)
        if score_scale is not None:
            s = s * score_scale
        if q0 is not None:
            s = _causal_masked(s, q0, k0, q_axis=0)
        if masked:
            s = jnp.where(kvm_ref[0, :, k_rows] > 0, s, NEG_INF)
        p = jnp.exp(s - _across(lse_scr[q_rows, :], s.shape[1]))
        dp = jax.lax.dot_general(do, v_ref[0, k_rows, :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _across(delta_scr[q_rows, :], s.shape[1]))
        if score_scale is not None:
            ds = ds * score_scale
        dq_scr[q_rows, :] += jnp.dot(ds.astype(k.dtype), k,
                                     preferred_element_type=jnp.float32)

    _run_live_tiles(step, qb, kb, block_q, block_k, causal,
                    _BACKWARD_WALK_ROWS)

    @pl.when(kb == nkb - 1)
    def _finalize():
        # a folded scale: dq = scale (ds k), once a q block
        dq_ref[0] = (dq_scr[...] * out_scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal: bool, sm_scale: float,
               block_q: Optional[int], block_k: Optional[int],
               kv_valid=None):
    block_q, block_k = _blocks(block_q, block_k, q, k)
    return _bwd_call(q, k, v, out, lse, g, kv_valid, causal=causal,
                     sm_scale=float(sm_scale), block_q=block_q,
                     block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def _bwd_call(q, k, v, out, lse, g, kv_valid, *, causal: bool,
              sm_scale: float, block_q: int, block_k: int, interpret: bool):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    masked = kv_valid is not None
    folds = _scale_folds(sm_scale)
    score_scale = None if folds else sm_scale

    qf, kf, vf = _folded(q * sm_scale if folds else q), _folded(k), _folded(v)
    dof, of = _folded(g), _folded(out)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term;
    # (BH, 1, Sq) like lse so its (1, 1, block_q) block lowers on TPU.
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]
    kvm = kv_valid.astype(jnp.float32)[:, None, :] if masked else None

    nqb, nkb = sq // block_q, sk // block_k
    scores = tile_census(sq, sk, block_q, block_k, causal).scores(
        _BACKWARD_WALK_ROWS)
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
    dkv_scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, d), jnp.float32)]
    if masked:
        # the K-block index is i in this kernel's grid
        dkv_in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, i, j, h=h: (bh // h, 0, i)))
        dkv_operands.append(kvm)
        dkv_scratch.append(pltpu.VMEM((block_k, _LANES), jnp.float32))
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, score_scale=score_scale,
                          masked=masked),
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
        scratch_shapes=dkv_scratch,
        cost_estimate=_cost(
            # per computed score: s, dv+=p^T dO, dp=dO v^T, dk+=ds^T q
            flops=b * h * scores * 8 * d,
            transcendentals=b * h * scores,
            bytes_accessed=read_bytes +
            b * h * 2 * sk * d * k.dtype.itemsize),
        interpret=interpret,
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
                          causal=causal, score_scale=score_scale,
                          out_scale=sm_scale if folds else 1.0,
                          masked=masked),
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, nqb, nkb),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        cost_estimate=_cost(
            # per computed score: s, dp=dO v^T, dq+=ds k
            flops=b * h * scores * 6 * d,
            transcendentals=b * h * scores,
            bytes_accessed=read_bytes +
            b * h * sq * d * q.dtype.itemsize),
        interpret=interpret,
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(*dq_operands)

    def unflat(x, s):
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 8))
def flash_attention(
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    kv_valid: Optional[jnp.ndarray] = None,  # (B, Sk), 1=real key, 0=pad
    causal_block: int = 1,
) -> jnp.ndarray:
    """Blockwise attention; numerically equivalent to softmax(QK^T*scale)V.

    ``v`` may be (B, S, H, Dv) with ``Dv != D`` (latent attention: keys of
    192, values of 128): the forward kernel's accumulator and output take
    the values' width. Forward only: differentiating such a call raises.

    `block_q` / `block_k` left ``None`` are chosen from the shapes
    (`_blocks`); an explicit size is honoured (fitted to the axis).

    `kv_valid` is a key-padding validity mask applied inside the blocks
    (forward AND backward recompute), so padded batches keep the flash fast
    path. Rows whose keys are ALL masked emit mean(V) — the standard
    contract that the loss zero-weights padded query rows (then their
    cotangent is exactly 0 and no gradient leaks through the garbage).

    ``causal_block`` B > 1 (with ``causal``) widens the mask to whole blocks
    of B positions: key j is visible to query i iff ``j // B <= i // B``
    (generation by diffusion over blocks, models/sdar.py). B = 1 is the
    causal program, text unchanged. Forward only: differentiating such a
    call raises rather than compute the causal mask's gradient."""
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, _ = _flash_fwd_lse(q, k, v, causal, scale, block_q, block_k,
                            kv_valid, causal_block)
    return out


def _vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_valid=None,
             causal_block=1):
    if causal_block != 1:
        raise NotImplementedError(
            f"flash_attention: causal_block={causal_block} has the forward "
            "kernel only; the backward kernels mask by position, not by "
            "block")
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "flash_attention: values of another width than the keys "
            f"({v.shape[-1]} against {q.shape[-1]}) have the forward kernel "
            "only; the backward kernels fold q, k and v to one width")
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, lse = map(checkpoint_name, _flash_fwd_lse(
        q, k, v, causal, scale, block_q, block_k, kv_valid), RESIDUAL_NAMES)
    return out, (q, k, v, out, lse, kv_valid)


def _vjp_bwd(causal, sm_scale, block_q, block_k, causal_block, residuals, g):
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


def make_flash_attention_fn(causal: bool, block_q: Optional[int] = None,
                            block_k: Optional[int] = None, mesh=None,
                            causal_block: int = 1):
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
                               kv_valid, causal_block)

    def attention_fn(q, k, v, mask=None, dtype=jnp.float32):
        kv_valid = _as_kv_valid(mask, q.shape[0], k.shape[1])
        if mask is not None and kv_valid is None:
            from ..models.layers import dot_product_attention

            if causal:
                at = jnp.arange(q.shape[1])[:, None] | (causal_block - 1)
                cm = (jnp.arange(k.shape[1])[None, :] <= at)[None, None]
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

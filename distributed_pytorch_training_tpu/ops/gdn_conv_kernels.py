"""The Gated DeltaNet mixer's depthwise causal convolution with its SiLU as a
pair of Pallas TPU kernels (`gdn_conv_fwd`, `gdn_conv_bwd`), each ONE pass
that finds its operands by column in the in-projection's own output: what
`gdn_rule_kernels`' mixer runs before and behind the rule's kernels, so that
between ``in_proj_qkvz``'s product and the rule the only tables in HBM are
the projection's output and the table the rule reads, each written once, and
no slice, pad or shifted window of either.

The forward reads ``qkvz``, (B, S, q | k | v | z columns) in the model's
dtype, and the taps, (K, conv_dim). One grid step is a block of sequence rows
by a lane-dense block of the first ``conv_dim`` columns (the index map leaves
z's columns out: no copy). The sequence axis is the innermost, sequential one,
and a block goes through in passes of FWD_ROWS rows: a pass's rows are cast up
into a float32 window in VMEM scratch behind the last HALO rows of the pass
before, which the window still holds, across a block's edge too (zeros at
block 0: the causal padding), so no row is read twice and no padded copy
exists. In float32::

    pre[t] = sum_j taps[j] * x[t - (K - 1) + j]
    out[t] = pre[t] * sigmoid(pre[t])           # rounded once, to x's dtype

each of the K shifted operands a load from the window at its own row offset.

The backward walks the sequence blocks, and a block's passes, in REVERSE. It
reads ``qkvz`` (the block, and the one tile of rows before it for the K - 1
rows the block's ``pre`` reaches back to) and the table's cotangent where the
rule's backward left it (dq, dk, dv: three tables, each column block lying in
one, found by its index map: nothing joins them), makes ``pre`` again (four
multiply-adds in VMEM, not a table in HBM), and keeps the first rows of
``dpre`` of the pass behind in a second window::

    dpre[t] = dout[t] * sig * (1 + pre * (1 - sig))
    dx[t] = sum_j taps[j] * dpre[t + (K - 1) - j]
    dtaps[j] += sum_t dpre[t] * x[t - (K - 1) + j]

``dtaps`` is summed in a float32 output block that stays while the sequence
blocks pass, one partial a batch row. ``dx`` is written INTO the projection's
full-width cotangent: the array whose last columns already hold the rule's dz
is aliased to the output, and the kernel writes the first ``conv_dim`` columns
beside them, so nothing concatenates or copies the two.

Both bodies are bound by their vector arithmetic, not by HBM (PERF.md, PR 41:
every block size read the same time): what is tuned is the count of vector
operations (`_tap_tiles`) and the passes' size. Block sizes are constants
chosen by shape (`_blocks`). Where it runs is `gdn_rule_kernels`' to say: the
mixer takes both kernel pairs or neither.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HALO = 8                # rows a window holds of its neighbour: a float32 tile
TILE = 16               # rows of a block of the neighbour in HBM: a bf16 tile
FWD_ROWS = 64           # rows a pass of the forward's loop takes
BWD_ROWS = 32           # of the backward's, which holds four windows at once
BWD_PASSES = 2          # passes written out in one turn of the backward's loop
ROW_BLOCKS = (512, 256, 128, 64)     # the largest that divides the length
COLUMN_BLOCKS = (512, 256, 128)      # the largest that divides every table


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def gdn_conv_supports(widths: Sequence[int], taps: int) -> bool:
    """The shapes the kernels are written for: the convolved columns, table
    by table as the backward is handed their cotangent (q | k | v), each in
    whole 128-lane blocks (the interpreter has no lanes and takes any
    width), and a kernel that reaches back no further than a window holds."""
    return 1 <= taps <= HALO + 1 and (
        _interpret() or all(w % COLUMN_BLOCKS[-1] == 0 for w in widths))


def _blocks(s: int, widths: Sequence[int]):
    """(rows, columns) of a grid step's block: columns that divide every
    table of ``widths`` columns, so that a block lies in one of them."""
    if s % ROW_BLOCKS[-1]:
        raise ValueError(f"gdn_conv: a length of {s} is not whole blocks of "
                         f"{ROW_BLOCKS[-1]} rows (the mixer pads to them)")
    rows = next(r for r in ROW_BLOCKS if s % r == 0)
    columns = next((c for c in COLUMN_BLOCKS
                    if all(w % c == 0 for w in widths)), math.gcd(*widths))
    return rows, columns


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


# A tap is a (1, columns) row. Times a (rows, columns) window it would be
# spread along the sublanes anew at every use; spread once to a tile of HALO
# rows and taken times the window a tile of rows at a time it is not (on the
# chip, at the timed shape: the forward 0.59 -> 0.52 ms, the backward 1.07 ->
# 0.82; PERF.md, PR 41).

def _tap_tiles(taps_ref):
    return [jnp.broadcast_to(taps_ref[j:j + 1, :], (HALO, taps_ref.shape[1]))
            for j in range(taps_ref.shape[0])]


def _times(tap, rows):
    r, c = rows.shape
    return (rows.reshape(r // HALO, HALO, c) * tap[None]).reshape(r, c)


def _weighed(taps, window, first, rows):
    """``sum_j taps[j] * window[first + j : first + j + rows]`` and the
    windows themselves: each a load at its own (static) row offset."""
    shifted = [window[first + j:first + j + rows] for j in range(len(taps))]
    total = _times(taps[0], shifted[0])
    for tap, rows_j in zip(taps[1:], shifted[1:]):
        total = total + _times(tap, rows_j)
    return total, shifted


def _fwd_kernel(x_ref, taps_ref, out_ref, window):
    """``window``: (HALO + FWD_ROWS, columns) float32, a pass's rows behind
    the last HALO rows of the pass before: it lasts from pass to pass and
    from a grid step to the next of its sequence."""
    ts, k = x_ref.shape[1], taps_ref.shape[0]
    rows = window.shape[0] - HALO

    @pl.when(pl.program_id(2) == 0)
    def _sequence_start():       # the causal padding
        window[rows:] = jnp.zeros((HALO, window.shape[1]), jnp.float32)

    taps = _tap_tiles(taps_ref)

    def one_pass(p, _):
        first = pl.multiple_of(p * rows, rows)
        window[:HALO] = window[rows:]
        window[HALO:] = x_ref[0, pl.ds(first, rows)].astype(jnp.float32)
        pre, _ = _weighed(taps, window, HALO - (k - 1), rows)
        out_ref[0, pl.ds(first, rows)] = (pre * _sigmoid(pre)).astype(
            out_ref.dtype)

    lax.fori_loop(0, ts // rows, one_pass, None)


def _bwd_kernel(x_ref, before_ref, taps_ref, *rest, blocks: int, tables):
    """Blocks arrive last first, and so do a block's passes. After the
    cotangent's tables (``tables``: each one's first and last column
    block): ``into_ref``, which is ``dx_ref``'s own array (aliased) and
    never touched here, so its other columns stay as they came; the two
    outputs; and scratch. ``block``: (HALO + rows, columns) float32, the
    block's rows behind the last HALO of ``before_ref`` (the tile of rows
    before the block); ``dout``: the block of the one table this column
    block lies in; ``window``: a pass's part of ``block``; ``dwindow``:
    (BWD_ROWS + HALO, columns) float32, a pass's ``dpre`` before the first
    HALO rows of the pass behind, lasting as the forward's window does."""
    dout_refs, (into_ref, dx_ref, dtaps_ref, block, dout, window,
                dwindow) = rest[:len(tables)], rest[len(tables):]
    del into_ref
    ts, k = x_ref.shape[1], taps_ref.shape[0]
    rows = window.shape[0] - HALO
    c = pl.program_id(2)
    zeros = jnp.zeros((HALO, block.shape[1]), jnp.float32)

    @pl.when(c == 0)
    def _sequence_end():         # nothing behind the last row
        dwindow[:HALO] = zeros
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    @pl.when(c == blocks - 1)
    def _sequence_start():       # ``before_ref`` is the block's own first tile
        block[:HALO] = zeros

    @pl.when(c < blocks - 1)
    def _rows_before():
        block[:HALO] = before_ref[0, TILE - HALO:].astype(jnp.float32)

    block[HALO:] = x_ref[0].astype(jnp.float32)
    for ref, (lo, hi) in zip(dout_refs, tables):
        @pl.when((pl.program_id(1) >= lo) & (pl.program_id(1) < hi))
        def _this_table(ref=ref):
            dout[...] = ref[0]

    taps = _tap_tiles(taps_ref)
    turn = BWD_PASSES * rows

    def one_turn(t, sums):
        last = pl.multiple_of(ts - turn - t * turn, turn)
        for first in (last + p * rows for p in reversed(range(BWD_PASSES))):
            window[...] = block[pl.ds(first, HALO + rows)]
            pre, shifted = _weighed(taps, window, HALO - (k - 1), rows)
            sig = _sigmoid(pre)
            dpre = dout[pl.ds(first, rows)].astype(jnp.float32) * (
                sig * (1.0 + pre * (1.0 - sig)))
            dwindow[rows:] = dwindow[:HALO]
            dwindow[:rows] = dpre
            dx, _ = _weighed(taps[::-1], dwindow, 0, rows)
            dx_ref[0, pl.ds(first, rows)] = dx.astype(dx_ref.dtype)
            sums = [total + (dpre * rows_j).reshape(
                rows // HALO, HALO, -1).sum(0)
                for total, rows_j in zip(sums, shifted)]
        return sums

    sums = lax.fori_loop(0, ts // turn, one_turn, [zeros] * k)
    dtaps_ref[0] += jnp.concatenate(
        [jnp.sum(total, axis=0, keepdims=True) for total in sums], axis=0)


_SEQUENTIAL_BLOCKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 1024 * 1024)


# `jit(inline=True)` as on the rule's two calls (`gdn_rule_kernels._forward`
# has why): traced once a process by its shapes, its equations under the
# caller's scope path at every site. And the bodies are loops, not passes
# written out: a step lowers nine of these calls, each from its jaxpr.
@functools.partial(jax.jit, inline=True)
def conv_silu_forward(qkvz, taps):
    """qkvz: (B, S, columns) whose first ``taps.shape[1]`` columns are
    convolved; taps: (K, conv_dim). Returns (B, S, conv_dim) in qkvz's
    dtype. S in whole blocks of ROW_BLOCKS[-1] rows."""
    b, s, _ = qkvz.shape
    k, conv_dim = taps.shape
    ts, tc = _blocks(s, (conv_dim,))
    block = pl.BlockSpec((1, ts, tc), lambda i, j, c: (i, c, j))
    call = pl.pallas_call(
        _fwd_kernel, name="gdn_conv_fwd", grid=(b, conv_dim // tc, s // ts),
        in_specs=[block, pl.BlockSpec((k, tc), lambda i, j, c: (0, j))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, conv_dim), qkvz.dtype),
        scratch_shapes=[pltpu.VMEM((HALO + FWD_ROWS, tc), jnp.float32)],
        compiler_params=_SEQUENTIAL_BLOCKS, interpret=_interpret())
    with jax.named_scope("gdn_conv_fwd"):
        return call(qkvz, taps.astype(jnp.float32))


@functools.partial(jax.jit, inline=True)
def conv_silu_backward(qkvz, taps, douts, into):
    """The forward's operands; ``douts``: its output's cotangent as tables
    side by side, (B, S, columns) each, conv_dim columns in all (the rule's
    backward leaves dq, dk and dv apart: read where they stand, nothing
    joins them); and ``into``: an array of qkvz's shape and dtype. Returns
    (``into`` with its first conv_dim columns set to the cotangent of
    qkvz's, the rest as they came; the taps' cotangent, (K, conv_dim)
    float32)."""
    b, s, _ = qkvz.shape
    k, conv_dim = taps.shape
    widths = [d.shape[-1] for d in douts]
    if sum(widths) != conv_dim:
        raise ValueError(f"gdn_conv: cotangent tables of {widths} columns "
                         f"for {conv_dim} convolved ones")
    ts, tc = _blocks(s, widths)
    n = s // ts
    block = pl.BlockSpec((1, ts, tc), lambda i, j, c: (i, n - 1 - c, j))
    before = pl.BlockSpec(
        (1, TILE, tc),
        lambda i, j, c: (i, jnp.maximum((n - 1 - c) * (ts // TILE) - 1, 0), j))
    ends = list(itertools.accumulate(w // tc for w in widths))
    tables = tuple(zip([0] + ends[:-1], ends))

    def table(lo, hi):
        # outside its columns a table's block index stands still: no copy
        return pl.BlockSpec((1, ts, tc), lambda i, j, c: (
            i, jnp.where((j >= lo) & (j < hi), n - 1 - c, 0),
            jnp.clip(j - lo, 0, hi - lo - 1)))

    call = pl.pallas_call(
        functools.partial(_bwd_kernel, blocks=n, tables=tables),
        name="gdn_conv_bwd", grid=(b, conv_dim // tc, n),
        in_specs=[block, before, pl.BlockSpec((k, tc), lambda i, j, c: (0, j)),
                  *(table(lo, hi) for lo, hi in tables),
                  pl.BlockSpec(memory_space=pl.ANY)],
        # a batch row's sum over its blocks: the block stays while they pass
        out_specs=[block, pl.BlockSpec((1, k, tc), lambda i, j, c: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(into.shape, into.dtype),
                   jax.ShapeDtypeStruct((b, k, conv_dim), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HALO + ts, tc), jnp.float32),
                        pltpu.VMEM((ts, tc), douts[0].dtype),
                        pltpu.VMEM((HALO + BWD_ROWS, tc), jnp.float32),
                        pltpu.VMEM((BWD_ROWS + HALO, tc), jnp.float32)],
        input_output_aliases={3 + len(douts): 0},
        compiler_params=_SEQUENTIAL_BLOCKS, interpret=_interpret())
    with jax.named_scope("gdn_conv_bwd"):
        dqkvz, dtaps = call(qkvz, qkvz, taps.astype(jnp.float32), *douts,
                            into)
        return dqkvz, dtaps.sum(axis=0)

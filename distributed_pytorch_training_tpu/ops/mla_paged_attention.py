"""The absorbed latent-attention read for the S=1 decode step — a Pallas TPU
kernel over the paged LATENT pool in place, `ops/paged_attention.py`'s
sibling.

The pool (models/layers.py `PagedLatent`) holds one row a token a layer:
the compressed key-value ``c`` (V = 512 numbers in DeepSeek-V2) in one leaf,
(L, n_pages, page_size, V), and the one rotary key every head shares (R =
64) in another, two layers' keys side by side in a 128-lane row, (L / 2,
n_pages, page_size, 2 R). With ``W_uk`` folded into the query and ``W_uv``
applied afterwards (models/deepseek_v2.py), a row's attention is, for ALL
heads at once,

    s_hj = scale * (q_c,h . c_j + q_pe,h . k_pe,j)
    o_h  = sum_j softmax_j(s_h) * c_j

one (V + R)-wide "key" and one V-wide "value" shared by the H query heads,
never expanded to per-head keys and values: 2 H (2 V + R) FLOPs for every
2 (V + R) bytes a cached token holds, 242 FLOP/B at 128 heads against the
v5e's ridge of 240.

As in `paged_attention`: the stacked pool stays in HBM (``pl.ANY``), layer
and page table and per-row live counts arrive by scalar prefetch, grid =
(rows,), and per row only the pages that hold positions to read are copied
(a ``c`` page and a rotary page each), ``pages_per_chunk`` at a time into
one of two VMEM buffers, the next chunk (or the next row's first) in flight
while this one is computed; a row with nothing to read starts no copy. A
rotary page arrives with its pair's other layer beside it (Mosaic copies
whole 128-lane tiles); the query's rotary part stands in the lanes of ITS
layer and zeros in the other's, so the product over all 128 lanes is the
product over its own 64, and the layer stays a run-time scalar. The fresh
token's own row is an INPUT and seeds the online softmax (max = its score,
sum = 1, accumulator = its ``c``); its score is computed by the caller's
XLA. Scores, running max / sum and the accumulator are float32.

Equal to the gather form (`models.deepseek_v2._attend_view`) within a
tolerance, not bitwise (online softmax, another contraction order):
PARITY.md. A row's output is a function of its own query, fresh row, pages
and count alone.

The kernel is named `mla_paged_attention`: its `pallas_call`'s ``name`` and
the innermost `jax.named_scope` around it (the benchmark's
``mla_decode_roofline`` reads its calls by that name). On CPU backends it
runs in interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)

# positions per chunk: one chunk is one round of page copies, two score
# products and one value product; two chunks live in VMEM (2.5 MB at 1024
# bf16 positions). On a v5e, one layer, 112 rows of 128 heads holding
# 2.4k-4.7k positions (397k in all, 458 MB of rows), ms a call by page size
# and chunk (my chip run, PR 37, `experiments/pr37/sweep.py`):
#
#   page 16:  256 3.08   512 2.65   1024 2.48
#   page 32:  256 2.39   512 1.99   1024 1.80
#   page 64:  256 2.05   512 1.63   1024 1.45   (316 GB/s, 39% of the bound)
#
# A page is two copies (16 kB + 4 kB at 16 positions) and the scalar core
# issues and awaits each: fewer, larger copies win at every chunk, and a
# longer chunk amortises a round's set-up.
CHUNK_POSITIONS = 1024


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def mla_paged_attention_supports(page_size: int, rank: int, rope: int,
                                 dtype) -> bool:
    """Whether Mosaic can copy this pool's pages as whole tiles: the dtype's
    sublane tile (8 rows of 4 bytes, 16 of 2) divides ``page_size`` and 128
    lanes divide a ``c`` row and a pair of rotary keys. The interpreter has
    no tiles and takes any shape."""
    if _interpret():
        return True
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return page_size % sublanes == 0 and rank % 128 == 0 \
        and (2 * rope) % 128 == 0


def _kernel(layer_ref, table_ref, live_ref, q_c_ref, q_pe_ref, s0_ref,
            fresh_ref, c_pool, pe_pool, o_ref, cbuf, pebuf, sems, slot_ref, *,
            pages_per_row: int, pages_per_chunk: int, sm_scale: float):
    row, rows = pl.program_id(0), pl.num_programs(0)
    page_size = c_pool.shape[2]
    chunk = pages_per_chunk * page_size
    layer = layer_ref[0]

    def pages_of(r):
        return pl.cdiv(live_ref[r], page_size)

    def each_page(r, c, slot, act):
        """``act`` on the two copies of every page of chunk ``c`` of row
        ``r`` that holds a position to read."""
        first = c * pages_per_chunk

        def one(i, carry):
            page = table_ref[r * pages_per_row + first + i]
            dst = pl.ds(pl.multiple_of(i * page_size, page_size), page_size)
            act(pltpu.make_async_copy(c_pool.at[layer, page],
                                      cbuf.at[slot, dst], sems.at[0, slot]))
            act(pltpu.make_async_copy(pe_pool.at[layer // 2, page],
                                      pebuf.at[slot, dst], sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(
            0, jnp.clip(pages_of(r) - first, 0, pages_per_chunk), one, 0)

    def start(r, c, slot):
        each_page(r, c, slot, lambda copy: copy.start())

    def wait(r, c, slot):
        each_page(r, c, slot, lambda copy: copy.wait())

    @pl.when(row == 0)
    def _first():
        # a chunk's tail past the last live page is never copied: what the
        # buffers hold there must be finite (its weight is exactly 0.0)
        cbuf[...] = jnp.zeros_like(cbuf)
        pebuf[...] = jnp.zeros_like(pebuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    n = live_ref[row]
    n_chunks = pl.cdiv(pages_of(row), pages_per_chunk)
    base = slot_ref[0]            # the buffer this row's chunk 0 went into

    q_c, q_pe = q_c_ref[0], q_pe_ref[0]                # (H, V), (H, 2 R)
    # the fresh token is the row's last position and seeds the softmax
    m0 = s0_ref[0] * sm_scale                                       # (H, 1)
    l0 = jnp.ones_like(m0)
    acc0 = jnp.broadcast_to(fresh_ref[0].astype(jnp.float32), q_c.shape)
    nt = (((1,), (1,)), ((), ()))

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = (base + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            start(row, c + 1, 1 - slot)

        @pl.when((c + 1 == n_chunks) & (row + 1 < rows))
        def _next_row():
            start(row + 1, 0, 1 - slot)

        wait(row, c, slot)
        lat_c, lat_pe = cbuf[slot], pebuf[slot]        # (T, V), (T, 2 R)
        s = (jax.lax.dot_general(q_c, lat_c, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_pe, lat_pe, nt,
                                   preferred_element_type=jnp.float32)
             ) * sm_scale                                           # (H, T)
        col = c * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < n, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(lat_c.dtype), lat_c,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))

    @pl.when((n_chunks == 0) & (row + 1 < rows))
    def _nothing_read():
        start(row + 1, 0, base)

    slot_ref[0] = (base + n_chunks) % 2
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_chunk", "sm_scale"))
def _call(layer, table, live, q_c, q_pe, s_fresh, fresh_c, c_pool, pe_pool,
          *, pages_per_chunk: int, sm_scale: float):
    """The `pallas_call`, jitted with the layer as a run-time scalar: a
    decode step's calls are then ONE kernel, traced and lowered to Mosaic
    once (`paged_attention._call` has the measurement)."""
    rows, heads, rank = q_c.shape
    pair = q_pe.shape[-1]
    page_size = c_pool.shape[2]
    pages_per_row = table.shape[0] // rows
    chunk = pages_per_chunk * page_size
    positions = rows * pages_per_row * page_size    # were every entry live
    item = c_pool.dtype.itemsize
    per_row = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1,) + tail, lambda r, *prefetched: (r,) + (0,) * len(tail))
    return pl.pallas_call(
        functools.partial(
            _kernel, pages_per_row=pages_per_row,
            pages_per_chunk=pages_per_chunk, sm_scale=sm_scale),
        name="mla_paged_attention",
        out_shape=jax.ShapeDtypeStruct((rows, heads, rank), q_c.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows,),
            in_specs=[per_row(heads, rank), per_row(heads, pair),
                      per_row(heads, 1), per_row(1, rank),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_row(heads, rank),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, rank), c_pool.dtype),
                pltpu.VMEM((2, chunk, pair), pe_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        # the buffer parity and the next row's first chunk are carried from
        # one grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # a bound: how many positions are live is a run-time value
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * positions * (2 * rank + pair),
            transcendentals=heads * positions,
            bytes_accessed=(positions * (rank + pair) + rows * heads
                            * (2 * rank + pair)) * item),
        interpret=_interpret(),
    )(layer, table, live, q_c, q_pe, s_fresh, fresh_c, c_pool, pe_pool)


def mla_paged_attention(q_c: jnp.ndarray, q_pe: jnp.ndarray,
                        fresh_c: jnp.ndarray, fresh_pe: jnp.ndarray,
                        c_pool: jnp.ndarray, pe_pool: jnp.ndarray,
                        page_table: jnp.ndarray, live: jnp.ndarray, *,
                        layer: int, sm_scale: float,
                        pages_per_chunk: Optional[int] = None) -> jnp.ndarray:
    """One decode token's absorbed latent attention per slot row.

    ``q_c`` (rows, H, V) every head's absorbed query ``W_uk^T q_nope``,
    ``q_pe`` (rows, H, R) its rotary part. ``fresh_c`` (rows, V), ``fresh_pe``
    (rows, R): the token's own row (not yet in the pool). ``c_pool`` (L,
    n_pages, page_size, V) and ``pe_pool`` (ceil(L / 2), n_pages, page_size,
    2 R), the two leaves of a `layers.PagedLatent`, of which layer ``layer``
    is read. ``page_table`` (rows, P) int32; ``live`` (rows,) int32:
    positions [0, live) of the row are read from its pages and the fresh row
    stands at position ``live`` (0 reads nothing: the output is ``fresh_c``
    for every head). Returns (rows, H, V) in ``q_c``'s dtype: the weighted
    sums of ``c``, for the caller's ``W_uv``."""
    if pages_per_chunk is None:
        pages_per_chunk = max(1, CHUNK_POSITIONS // c_pool.shape[2])
    pages_per_chunk = min(pages_per_chunk, page_table.shape[1])
    rope = q_pe.shape[-1]
    s_fresh = (jnp.einsum("rhv,rv->rh", q_c, fresh_c.astype(q_c.dtype),
                          preferred_element_type=jnp.float32)
               + jnp.einsum("rhd,rd->rh", q_pe, fresh_pe.astype(q_pe.dtype),
                            preferred_element_type=jnp.float32))[..., None]
    # this layer's half of a pair's 128 lanes; zeros meet the other layer's
    half = layer % 2
    q_pair = jnp.pad(q_pe, ((0, 0), (0, 0),
                            (half * rope, (1 - half) * rope)))
    with jax.named_scope("mla_paged_attention"):
        return _call(jnp.full((1,), layer, jnp.int32),
                     page_table.reshape(-1).astype(jnp.int32),
                     live.astype(jnp.int32), q_c, q_pair, s_fresh,
                     fresh_c[:, None].astype(c_pool.dtype), c_pool, pe_pool,
                     pages_per_chunk=pages_per_chunk,
                     sm_scale=float(sm_scale))

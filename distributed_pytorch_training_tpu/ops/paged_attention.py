"""Page-table attention for the S=1 decode step — a Pallas TPU kernel that
reads the paged KV pool in place, in its at-rest layout.

The pool (models/layers.py `PagedKV`) is (L, n_pages, page_size, H*D):
lane-dense pages, a position's heads side by side in the last axis. The
reference read gathers every slot's pages into a dense (rows, T, H, D) view
per layer; XLA paid for that view, and for relaying the pool out around it,
with 145 ms of a 197.7 ms decode step on a v5e (ledger, PR 24). This kernel
builds no view:

* the stacked pool stays in HBM (``memory_space=pl.ANY``) and the layer is
  indexed inside the kernel, so no per-layer slice is ever materialized;
* the layer, the page table and, per slot row, the number of positions to
  read arrive by scalar prefetch; grid = (rows,), one slot row per step;
* per row the kernel DMAs only the pages that hold positions to read,
  ``pages_per_chunk`` at a time into one of two VMEM buffers, the next
  chunk (or the next row's first) in flight while this one is computed; a
  row with nothing to read (a dead slot, a slot at position 0) starts none;
* the fresh token's own k/v row is an INPUT, folded in as the row's last
  position: it seeds the online softmax (max = its score, sum = 1,
  accumulator = its v). The pool is read-only here, and the step's write
  stays one scatter at its end (`scatter_paged_rows`). Scattering first
  would put twelve in-place pool updates between twelve kernel calls that
  read the same buffer, for XLA to order or to copy around.
* heads: the query is spread over ``num_heads`` sublanes, row h holding
  head h's lanes and zeros elsewhere (block-diagonal), so scores for every
  head are ONE (heads, H*D) x (T, H*D)^T matmul, lane-dense along T, and
  the weighted sum ONE (heads, T) x (T, H*D) matmul whose diagonal blocks
  are the answer. Scores, running max / sum and the accumulator are float32.

Equal to `gather_paged_kv` + `decode_dot_product_attention` within a
tolerance, not bitwise (online softmax, another contraction order):
PARITY.md "Exactness model: paged + int8 KV". A row's output is a function
of its own query, fresh row, pages and count alone.

The kernel is named `paged_attention`: its `pallas_call`'s ``name`` and the
innermost `jax.named_scope` around it, as the flash kernels are
(ops/flash_attention.py). On CPU backends it runs in interpreter mode.
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

# positions per chunk: one chunk is one pair of matmuls and one round of
# page DMAs; two chunks of k and of v live in VMEM (1.6 MB at 256 x 768
# bf16). On a v5e, 12 layers x 64 rows of 768 lanes, ~22 live pages of 16 a
# row: 32 positions a chunk read 189 GB/s, 64 270, 128 437, 256 511, 512 441
# (the whole table: 199, 295, 514, 678, 738 of the chip's 819); with nothing
# to read a call costs 20-70 us, more the more there is to zero (my chip
# run, PR 25)
CHUNK_POSITIONS = 256


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def paged_attention_backend_supported(backend: Optional[str] = None) -> bool:
    """ONE place for the backend gate of the kernel read (the decode step
    asks it at trace time; `flash_backend_supported` is its sibling): a
    real TPU. Anywhere else the decode step takes the reference read."""
    return (backend or jax.default_backend()) == "tpu"


def paged_attention_supports(page_size: int, width: int, dtype) -> bool:
    """Whether Mosaic can DMA this pool's pages as whole tiles: a page is
    (page_size, width) with the dtype's sublane tile (8 rows of 4 bytes, 16
    of 2) dividing ``page_size`` and 128 lanes dividing ``width``. The
    interpreter has no tiles and takes any shape."""
    if _interpret():
        return True
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return page_size % sublanes == 0 and width % 128 == 0


def _padded_heads(num_heads: int) -> int:
    """Heads padded to whole sublane tiles (16 rows of 2 bytes)."""
    return -(-num_heads // 16) * 16


def _kernel(layer_ref, table_ref, live_ref, q_ref, kf_ref, vf_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sems, slot_ref, *, num_heads: int,
            pages_per_row: int, pages_per_chunk: int, sm_scale: float):
    row, rows = pl.program_id(0), pl.num_programs(0)
    page_size, width = k_hbm.shape[2], k_hbm.shape[3]
    head_dim = width // num_heads
    chunk = pages_per_chunk * page_size
    hp = _padded_heads(num_heads)
    layer = layer_ref[0]

    def pages_of(r):
        return pl.cdiv(live_ref[r], page_size)

    def each_page(r, c, slot, act):
        """``act`` on the k and the v copy of every page of chunk ``c`` of
        row ``r`` that holds a position to read."""
        first = c * pages_per_chunk

        def one(i, carry):
            page = table_ref[r * pages_per_row + first + i]
            dst = pl.ds(pl.multiple_of(i * page_size, page_size), page_size)
            act(pltpu.make_async_copy(k_hbm.at[layer, page],
                                      kbuf.at[slot, dst], sems.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[slot, dst], sems.at[1, slot]))
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
        # buffers hold there must be finite (its weight is exactly 0.0),
        # so they start as zeros and only ever take pool pages after
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    n = live_ref[row]
    n_chunks = pl.cdiv(pages_of(row), pages_per_chunk)
    base = slot_ref[0]            # the buffer this row's chunk 0 went into

    lane_head = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
    head_lo = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 0) * head_dim
    own = (lane_head >= head_lo) & (lane_head < head_lo + head_dim)
    # block-diagonal query: sublane h holds head h's lanes, zeros elsewhere
    # (selected as float32: a 16-bit select would relay the mask out)
    qbd = jnp.where(own, q_ref[0].astype(jnp.float32), 0.0)       # (hp, W)

    # the fresh token is the row's last position and seeds the softmax
    m0 = jnp.sum(qbd * kf_ref[0].astype(jnp.float32), axis=1,
                 keepdims=True) * sm_scale                         # (hp, 1)
    qbd = qbd.astype(kbuf.dtype)
    l0 = jnp.ones_like(m0)
    acc0 = jnp.broadcast_to(vf_ref[0].astype(jnp.float32), (hp, width))

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
        k, v = kbuf[slot], vbuf[slot]                             # (T, W)
        s = jax.lax.dot_general(
            qbd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale        # (hp, T)
        col = c * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < n, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))

    @pl.when((n_chunks == 0) & (row + 1 < rows))
    def _nothing_read():
        start(row + 1, 0, base)

    slot_ref[0] = (base + n_chunks) % 2
    # head h's answer is the h-th diagonal block of its sublane
    o_ref[0] = jnp.sum(jnp.where(own, acc / l, 0.0), axis=0,
                       keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "pages_per_chunk"))
def _call(layer, table, live, q, k_fresh, v_fresh, k_pool, v_pool, *,
          num_heads: int, pages_per_chunk: int):
    """The `pallas_call`, jitted with the layer as a run-time scalar: the
    twelve calls of a decode step are then ONE kernel, traced and lowered
    to Mosaic once. A kernel per layer, its page loop unrolled, made the
    step's lowering 10 s here and ~30 s on the chip's host, where the
    reference read's is 1.3: the program is lowered in every process, so
    warm `setup_s` was 79-83 s against the parent's 47-48; it is 46 this
    way (my chip runs, PR 25)."""
    rows, _, width = q.shape
    page_size = k_pool.shape[2]
    pages_per_row = table.shape[0] // rows
    chunk = pages_per_chunk * page_size
    item = k_pool.dtype.itemsize
    positions = rows * pages_per_row * page_size    # were every entry live
    row_spec = pl.BlockSpec((1, 1, width), lambda r, *prefetched: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _kernel, num_heads=num_heads, pages_per_row=pages_per_row,
            pages_per_chunk=pages_per_chunk,
            sm_scale=float(1.0 / np.sqrt(width // num_heads))),
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((rows, 1, width), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows,),
            in_specs=[row_spec, row_spec, row_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((2, chunk, width), k_pool.dtype),
                pltpu.VMEM((2, chunk, width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        # the buffer parity and the next row's first chunk are carried from
        # one grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # a bound: how many positions are live is a run-time value
        cost_estimate=pl.CostEstimate(
            flops=4 * _padded_heads(num_heads) * positions * width,
            transcendentals=_padded_heads(num_heads) * positions,
            bytes_accessed=(2 * positions * item
                            + 4 * rows * q.dtype.itemsize) * width),
        interpret=_interpret(),
    )(layer, table, live, q, k_fresh, v_fresh, k_pool, v_pool)


def paged_attention(q: jnp.ndarray, k_fresh: jnp.ndarray,
                    v_fresh: jnp.ndarray, k_pool: jnp.ndarray,
                    v_pool: jnp.ndarray, page_table: jnp.ndarray,
                    live: jnp.ndarray, *, layer: int, num_heads: int,
                    pages_per_chunk: Optional[int] = None) -> jnp.ndarray:
    """One decode token's attention per slot row, over that row's pages.

    ``q``, ``k_fresh``, ``v_fresh`` (rows, H*D): the token's query and its
    own k/v row (not yet in the pool). ``k_pool`` / ``v_pool`` (L, n_pages,
    page_size, H*D) unquantized, of which layer ``layer`` is read.
    ``page_table`` (rows, P) int32; ``live`` (rows,) int32: positions
    [0, live) of the row are read from its pages and the fresh row stands
    at position ``live`` (0 reads nothing: the output is ``v_fresh``).
    Returns (rows, H*D) in ``q``'s dtype."""
    if pages_per_chunk is None:
        pages_per_chunk = max(1, CHUNK_POSITIONS // k_pool.shape[2])
    pages_per_chunk = min(pages_per_chunk, page_table.shape[1])
    with jax.named_scope("paged_attention"):
        out = _call(jnp.full((1,), layer, jnp.int32),
                    page_table.reshape(-1).astype(jnp.int32),
                    live.astype(jnp.int32), q[:, None], k_fresh[:, None],
                    v_fresh[:, None], k_pool, v_pool, num_heads=num_heads,
                    pages_per_chunk=pages_per_chunk)
    return out[:, 0]

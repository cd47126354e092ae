"""Page-table attention for the S=1 decode step, and for a WINDOW of W query
positions a row with grouped queries — a Pallas TPU kernel that reads the
paged KV pool in place, in its at-rest layout.

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

* a window (serving/block_diffusion.py: a block of W positions denoised
  together over a pool of ``H_kv`` key/value heads, each read by ``G`` query
  heads): the same layout, wider. The query tile is ``W x H_q`` rows, row
  (w, n) holding head n's D lanes at key head ``n // G``'s place in the
  pool's ``H_kv * D`` lanes; the W fresh k/v rows seed the online softmax as
  the one fresh row does, all of them visible to all W queries; the pages
  are read once a row, not once a position. W = 1 with G = 1 is the decode
  step's call, letter for letter in its lowering.

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


def paged_attention_supports(page_size: int, width: int, dtype,
                             window: int = 1, num_heads: int = 1,
                             num_kv_heads: int = 1) -> bool:
    """Whether Mosaic can DMA this pool's pages as whole tiles: a page is
    (page_size, width) with the dtype's sublane tile (8 rows of 4 bytes, 16
    of 2) dividing ``page_size`` and 128 lanes dividing ``width``; a window
    call (``window`` > 1, or fewer key/value heads than query heads) besides
    needs a head of whole 128-lane tiles, since its query tile is built and
    its answer taken apart a head's lanes at a time, and whole query heads a
    key head. The interpreter has no tiles and takes any shape."""
    if num_heads % num_kv_heads:
        return False
    if _interpret():
        return True
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    if page_size % sublanes or width % 128:
        return False
    windowed = window > 1 or num_heads != num_kv_heads
    return not windowed or (width // num_kv_heads) % 128 == 0


def _padded_heads(num_heads: int) -> int:
    """Heads padded to whole sublane tiles (16 rows of 2 bytes)."""
    return -(-num_heads // 16) * 16


def _kernel(layer_ref, table_ref, live_ref, q_ref, kf_ref, vf_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sems, slot_ref, *, num_heads: int,
            pages_per_row: int, pages_per_chunk: int, sm_scale: float,
            window: int = 0, group: int = 1):
    """``window`` 0 is the decode step's kernel, its text unchanged;
    ``window`` W >= 1 is the window form (`_window_tile`: W query positions
    of ``num_heads`` heads, ``group`` to a key head), which shares the page
    walk and the online softmax."""
    row, rows = pl.program_id(0), pl.num_programs(0)
    page_size, width = k_hbm.shape[2], k_hbm.shape[3]
    windowed = window > 0
    head_dim = width // (num_heads // group)
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

    if windowed:
        own, qbd, m0, l0, acc0 = _window_tile(
            q_ref[0], kf_ref[0], vf_ref[0], num_heads, group, width,
            sm_scale)
        qbd = qbd.astype(kbuf.dtype)
    else:
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
        head_lo = jax.lax.broadcasted_iota(
            jnp.int32, (hp, width), 0) * head_dim
        own = (lane_head >= head_lo) & (lane_head < head_lo + head_dim)
        # block-diagonal query: sublane h holds head h's lanes, zeros
        # elsewhere (selected as float32: a 16-bit select would relay the
        # mask out)
        qbd = jnp.where(own, q_ref[0].astype(jnp.float32), 0.0)   # (hp, W)

        # the fresh token is the row's last position and seeds the softmax
        m0 = jnp.sum(qbd * kf_ref[0].astype(jnp.float32), axis=1,
                     keepdims=True) * sm_scale                     # (hp, 1)
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
    if windowed:
        # query row (w, n)'s answer is its key head's block of its sublane
        o_ref[0] = sum(
            jnp.where(own[:, lo:lo + head_dim],
                      (acc / l)[:, lo:lo + head_dim], 0.0)
            for lo in range(0, width, head_dim)).astype(o_ref.dtype)
    else:
        # head h's answer is the h-th diagonal block of its sublane
        o_ref[0] = jnp.sum(jnp.where(own, acc / l, 0.0), axis=0,
                           keepdims=True).astype(o_ref.dtype)


def _window_tile(q, k_fresh, v_fresh, num_heads: int, group: int, width: int,
                 sm_scale: float):
    """The window form's query tile and the seed of its online softmax.
    ``q`` (W * H_q, D): row (w, n) is query head n at window position w;
    ``k_fresh`` / ``v_fresh`` (W, H_kv * D) float32. Returns ``own`` (which
    lanes of a row are its key head's), the block-diagonal tile in float32,
    and (m, l, acc) after the W fresh positions, which every row sees."""
    rows, head_dim = q.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    lo = jax.lax.div(jax.lax.rem(at, num_heads), group) * head_dim
    own = (lane >= lo) & (lane < lo + head_dim)
    qbd = jnp.where(own, jnp.tile(q.astype(jnp.float32),
                                  (1, width // head_dim)), 0.0)
    scores = [jnp.sum(qbd * k_fresh[w:w + 1], axis=1, keepdims=True)
              * sm_scale for w in range(k_fresh.shape[0])]       # (rows, 1)
    m = functools.reduce(jnp.maximum, scores)
    weights = [jnp.exp(s - m) for s in scores]
    acc = sum(p * v_fresh[w:w + 1] for w, p in enumerate(weights))
    return own, qbd, m, sum(weights), acc


def _pool_walk_call(kernel, q_spec, fresh_spec, out_shape, rows: int,
                    chunk: int, k_pool, v_pool, cost):
    """The `pallas_call` both forms share: a grid step a slot row, the layer,
    the page table and the rows' counts by scalar prefetch, the pools left in
    HBM, two chunks of k and of v in VMEM."""
    width = k_pool.shape[3]
    return pl.pallas_call(
        kernel,
        name="paged_attention",
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows,),
            in_specs=[q_spec, fresh_spec, fresh_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
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
        cost_estimate=cost,
        interpret=_interpret(),
    )


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
    item = k_pool.dtype.itemsize
    positions = rows * pages_per_row * page_size    # were every entry live
    row_spec = pl.BlockSpec((1, 1, width), lambda r, *prefetched: (r, 0, 0))
    return _pool_walk_call(
        functools.partial(
            _kernel, num_heads=num_heads, pages_per_row=pages_per_row,
            pages_per_chunk=pages_per_chunk,
            sm_scale=float(1.0 / np.sqrt(width // num_heads))),
        row_spec, row_spec, jax.ShapeDtypeStruct((rows, 1, width), q.dtype),
        rows, pages_per_chunk * page_size, k_pool, v_pool,
        # a bound: how many positions are live is a run-time value
        pl.CostEstimate(
            flops=4 * _padded_heads(num_heads) * positions * width,
            transcendentals=_padded_heads(num_heads) * positions,
            bytes_accessed=(2 * positions * item
                            + 4 * rows * q.dtype.itemsize) * width),
    )(layer, table, live, q, k_fresh, v_fresh, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "pages_per_chunk"))
def _window_call(layer, table, live, q, k_fresh, v_fresh, k_pool, v_pool, *,
                 num_heads: int, num_kv_heads: int, pages_per_chunk: int):
    """`_call` for a window: ``q`` (rows, W * H_q, D), the fresh rows (rows,
    W, H_kv * D) in float32, one kernel for every layer as there."""
    rows, q_rows, head_dim = q.shape
    window, width = k_fresh.shape[1], k_pool.shape[3]
    page_size = k_pool.shape[2]
    pages_per_row = table.shape[0] // rows
    positions = rows * pages_per_row * page_size    # were every entry live
    return _pool_walk_call(
        functools.partial(
            _kernel, num_heads=num_heads, pages_per_row=pages_per_row,
            pages_per_chunk=pages_per_chunk,
            sm_scale=float(1.0 / np.sqrt(head_dim)), window=window,
            group=num_heads // num_kv_heads),
        pl.BlockSpec((1, q_rows, head_dim), lambda r, *prefetched: (r, 0, 0)),
        pl.BlockSpec((1, window, width), lambda r, *prefetched: (r, 0, 0)),
        jax.ShapeDtypeStruct(q.shape, q.dtype), rows,
        pages_per_chunk * page_size, k_pool, v_pool,
        # a bound, as `_call`'s: the block-diagonal products as executed
        pl.CostEstimate(
            flops=4 * q_rows * positions * width,
            transcendentals=q_rows * positions,
            bytes_accessed=2 * positions * k_pool.dtype.itemsize * width
            + 2 * q.size * q.dtype.itemsize + 2 * k_fresh.size * 4),
    )(layer, table, live, q, k_fresh, v_fresh, k_pool, v_pool)


def paged_attention(q: jnp.ndarray, k_fresh: jnp.ndarray,
                    v_fresh: jnp.ndarray, k_pool: jnp.ndarray,
                    v_pool: jnp.ndarray, page_table: jnp.ndarray,
                    live: jnp.ndarray, *, layer: int, num_heads: int,
                    num_kv_heads: Optional[int] = None,
                    pages_per_chunk: Optional[int] = None) -> jnp.ndarray:
    """One decode token's attention per slot row, over that row's pages.

    ``q``, ``k_fresh``, ``v_fresh`` (rows, H*D): the token's query and its
    own k/v row (not yet in the pool). ``k_pool`` / ``v_pool`` (L, n_pages,
    page_size, H*D) unquantized, of which layer ``layer`` is read.
    ``page_table`` (rows, P) int32; ``live`` (rows,) int32: positions
    [0, live) of the row are read from its pages and the fresh row stands
    at position ``live`` (0 reads nothing: the output is ``v_fresh``).
    Returns (rows, H*D) in ``q``'s dtype.

    A WINDOW: ``q`` (rows, W, H_q*D) and ``k_fresh``, ``v_fresh`` (rows, W,
    H_kv*D) with ``num_kv_heads`` = H_kv key/value heads in the pool's lanes,
    each read by ``num_heads // num_kv_heads`` query heads. Positions [0,
    live) are read from the pages and the W fresh rows stand at ``live ..
    live + W - 1``, all of them visible to all W queries. Returns (rows, W,
    H_q*D)."""
    if pages_per_chunk is None:
        pages_per_chunk = max(1, CHUNK_POSITIONS // k_pool.shape[2])
    pages_per_chunk = min(pages_per_chunk, page_table.shape[1])
    if q.ndim == 3:
        rows, window, _ = q.shape
        kv_heads = num_kv_heads or num_heads
        head_dim = k_pool.shape[3] // kv_heads
        # the fresh rows as the pool would hold them, handed over in
        # float32: the kernel takes them a position at a time
        as_held = lambda x: x.astype(k_pool.dtype).astype(jnp.float32)  # noqa: E731
        with jax.named_scope("paged_attention"):
            out = _window_call(
                jnp.full((1,), layer, jnp.int32),
                page_table.reshape(-1).astype(jnp.int32),
                live.astype(jnp.int32),
                q.reshape(rows, window * num_heads, head_dim),
                as_held(k_fresh), as_held(v_fresh), k_pool, v_pool,
                num_heads=num_heads, num_kv_heads=kv_heads,
                pages_per_chunk=pages_per_chunk)
        return out.reshape(q.shape)
    with jax.named_scope("paged_attention"):
        out = _call(jnp.full((1,), layer, jnp.int32),
                    page_table.reshape(-1).astype(jnp.int32),
                    live.astype(jnp.int32), q[:, None], k_fresh[:, None],
                    v_fresh[:, None], k_pool, v_pool, num_heads=num_heads,
                    pages_per_chunk=pages_per_chunk)
    return out[:, 0]

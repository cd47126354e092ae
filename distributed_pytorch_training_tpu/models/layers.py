"""Shared transformer building blocks (attention, MLP, embeddings).

No analogue exists in the reference (ResNet-only, /root/reference/train_ddp.py:154);
these serve the ViT/BERT/GPT-2 configs (BASELINE.json:9-12) that the
reference's dependency stack (torchvision/transformers model zoos) would
provide on GPU.

TP design (megatron-style over the mesh's ``model`` axis, SURVEY.md §2c):
* qkv projection kernels partitioned on the *output* (head) dim,
* attention-out and MLP-down kernels partitioned on the *input* dim,
so each device holds a head/neuron slice and XLA inserts exactly one
all-reduce per residual join. The rules live in `tp_fsdp_rules()`
(one table covers TP, FSDP, and their composition; trivial axes are inert).

The attention inner product is pluggable (`attention_fn`) so the Pallas
flash/ring kernels in `ops/` can replace the XLA einsum path per-config.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.collectives import copy_to_tp, reduce_from_tp
from ..parallel.mesh import FSDP, MODEL
from ..parallel.sharding import PartitionRules
from jax.sharding import PartitionSpec as P

Dtype = Any


class RowParallelDense(nn.Module):
    """Megatron row-parallel linear for the EXPLICIT TP forward (inside a
    shard_map with the ``model`` axis bound): the kernel's contracting
    (input) dims are a per-shard slice, the partial product is psum'd over
    the TP axis (`reduce_from_tp` — THE one forward psum per residual
    join), and the bias — a full, model-replicated parameter — is added
    AFTER the psum so it lands exactly once. Param paths match the GSPMD
    module's (``<name>/kernel``, ``<name>/bias``): the same checkpoint tree,
    just with the kernel holding this shard's rows."""

    features: int
    tp_axis: str
    n_contract_dims: int = 1  # trailing input dims contracted (DenseGeneral axis)
    use_bias: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        nd = self.n_contract_dims
        contract_shape = x.shape[-nd:]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(
                in_axis=tuple(range(nd)), out_axis=-1),
            contract_shape + (self.features,), self.param_dtype)
        y = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            ((tuple(range(x.ndim - nd, x.ndim)), tuple(range(nd))),
             ((), ())))
        y = reduce_from_tp(y, self.tp_axis)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        return y


def dot_product_attention(
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,  # (B, T, H, D)
    v: jnp.ndarray,  # (B, T, H, D)
    mask: Optional[jnp.ndarray] = None,  # broadcastable to (B, H, S, T), True=attend
    dtype: Dtype = jnp.float32,
) -> jnp.ndarray:
    """Reference XLA attention: softmax(QK^T/sqrt(d))V. Softmax in fp32 for
    bf16 stability; output cast back to `dtype`."""
    d = q.shape[-1]
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    logits = logits / np.sqrt(d).astype(np.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhst,bthd->bshd", weights, v)


def decode_dot_product_attention(
    q: jnp.ndarray,  # (B, S, H, D) — S=1 decode, S=K+1 verify window
    k: jnp.ndarray,  # (B, T, H, D) — the KV cache
    v: jnp.ndarray,  # (B, T, H, D)
    mask: Optional[jnp.ndarray] = None,  # (B, 1, S, T), True=attend
    dtype: Dtype = jnp.float32,
) -> jnp.ndarray:
    """`dot_product_attention` for the cached decode step, formulated so
    its fp32 output rows are BITWISE-equal to the corresponding rows of
    the full forward on the CPU mesh (the serving parity pin, PARITY.md).

    Same math, one deliberate difference: the weights x V contraction runs
    through an explicit `lax.dot_general` with (B, H) batch dims. The
    einsum form ``bhst,bthd->bshd`` lowers to a GEMV for s=1 whose
    accumulation order differs from the s=S GEMM's — ~1e-7-level
    reassociation noise that would break the decode-vs-full bitwise parity
    contract. The dot_general form accumulates like the GEMM row does
    (pinned empirically by tests/test_serving.py; the QK^T einsum and the
    softmax are already row-stable at s=1, so they stay as-is).

    The same formulation serves the speculative VERIFY window (S = K+1
    query rows per slot, serving/speculative.py): every op is
    row-independent over the query axis, so window row ``j`` under its own
    causal mask is bitwise the s=1 decode step at that position — the
    acceptance comparison compares exact tokens, never float
    intermediates."""
    d = q.shape[-1]
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    logits = logits / np.sqrt(d).astype(np.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1).astype(dtype)  # (B, H, 1, T)
    out = jax.lax.dot_general(
        weights, v.transpose(0, 2, 1, 3),
        (((3,), (2,)), ((0, 1), (0, 1))))  # (B, H, 1, D)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Paged KV cache substrate (fleet-scale serving, ISSUE 17)
#
# The dense per-request cache above allocates (rows, bucket + max_new, H, D)
# per block whether a slot is live or not — the HBM ceiling at long
# max_new_tokens. The paged form stores k/v in a POOL of fixed-size pages
# (L, n_pages, page_size, H*D), stacked over every block; each serving slot
# owns a row of a page TABLE mapping its logical positions onto pool pages.
# A page is lane-dense — a position's heads lie side by side in the last
# axis — and the pool is only ever touched in that at-rest layout: writes
# are row scatters over its flattened (L*n_pages*page_size, H*D) view (a
# bitcast; `_put_rows`), and the S=1 decode step on a TPU reads it page by
# page from HBM (`ops.paged_attention`). Two reads exist:
#
# * the REFERENCE read, `gather_paged_kv`: a slot's pages gathered into the
#   SAME dense (rows, T, H, D) view the bitwise-pinned decode attention
#   consumes, so fp32 paged decode inherits the dense path's exactness
#   proof verbatim: trailing/garbage positions are masked to the fp32 min,
#   their softmax weight underflows to exactly 0.0, and adding 0.0 in the
#   fp32 contraction is exact. Windows (resume, speculative verify), int8
#   pools, meshes of more than one device and every CPU run take it.
# * the KERNEL read, `PagedRead` -> `ops.paged_attention`: no dense view;
#   equal to the reference within a tolerance, not bitwise (PARITY.md).
#
# int8 pages quantize each (position, head) row over D through the
# gradient-wire codec grid (``grad_sync._quantize_int8_rows`` — codes + one
# fp32 scale per row), a bounded, deterministic, replica-identical
# perturbation (PARITY.md).
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class PagedKV:
    """The model's paged KV pool, stacked across ALL blocks.

    ``k``/``v`` are (L, n_pages, page_size, H*D) in the model dtype — one
    leading layer axis over every transformer block, a position's
    ``num_heads`` heads side by side in the lanes — or int8 codes when
    quantized, in which case ``k_scale``/``v_scale`` hold one fp32 scale
    per (layer, page, position, head) row (the wire codec's per-row grid
    over D). The shape is private to this module (`init_paged_kv`, the
    gather, the scatters) and to the kernel that reads it.

    Why this shape, on the chip (v5e, GPT-2 124M, 64 rows, a 2.42 GB
    pool): at rest as (L, n_pages, page_size, H, D) XLA relaid the whole
    pool out and back around the scatter and built the dense view twice,
    145 ms of a 197.7 ms decode step (ledger, PR 24); in this shape the
    scatter is in place and no pool-sized copy is left (PERF.md, PR 25).
    The stack over layers stays: every block's pages share one page table,
    so the write half of a step is ONE scatter.

    Page 0 is the SCRATCH page by convention (serving/paged.py): freed or
    unallocated table entries point at it, so a gather is always in-bounds
    and masked positions stay finite (0.0 x finite = 0.0 exactly; a NaN
    would poison the masked softmax row)."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    num_heads: int = flax.struct.field(pytree_node=False, default=1)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    # what a token's cache row is made of, for the functions below that
    # work over any pool: its leaves as (store, scales or None) pairs, how a
    # leaf's gathered pages become the dense view a model reads, and how the
    # rows a model hands back become what a leaf stores
    def leaves(self):
        return ((self.k, self.k_scale), (self.v, self.v_scale))

    def view_of(self, leaf: int, pages: jnp.ndarray) -> jnp.ndarray:
        """(L, rows, T, H*D) -> (L, rows, T, H, D)."""
        return pages.reshape(pages.shape[:-1] + (
            self.num_heads, pages.shape[-1] // self.num_heads))

    def rows_of(self, fresh):
        return fresh

    def with_leaves(self, stores) -> "PagedKV":
        (k, k_scale), (v, v_scale) = stores
        return self.replace(k=k, v=v, k_scale=k_scale, v_scale=v_scale)


@flax.struct.dataclass
class PagedLatent:
    """A paged pool whose row is ONE vector a token a layer, shared by every
    head: latent attention's compressed key-value ``c`` (``rank`` numbers)
    and the one rotary key ``k_pe`` beside it (models/deepseek_v2.py: 512 +
    64). Two leaves, both lane-dense in whole 128-lane tiles, which is what
    lets `ops.mla_paged_attention` copy a page as it lies (Mosaic refuses a
    576- or a 64-lane slice of an HBM array): ``c`` is (L, n_pages,
    page_size, rank); ``pe`` holds the rotary keys of layers 2i and 2i + 1
    side by side, (ceil(L / 2), n_pages, page_size, 2 * rope) — every write
    lands in all layers at the same (page, offset), so a pair's row is
    written whole. At rest a token costs ``rank + rope`` numbers a layer
    (an odd ``depth`` pads one layer's ``rope``). Page 0 is the scratch page
    as in `PagedKV`. There is no int8 form."""

    c: jnp.ndarray
    pe: jnp.ndarray
    depth: int = flax.struct.field(pytree_node=False, default=1)
    quantized = False

    def leaves(self):
        return ((self.c, None), (self.pe, None))

    def view_of(self, leaf: int, pages: jnp.ndarray) -> jnp.ndarray:
        """``c`` as it is; ``pe`` (L/2, rows, T, 2 * rope) -> (L, rows, T,
        rope), a pair's two layers taken apart."""
        if leaf == 0:
            return pages
        pairs, rows, t, width = pages.shape
        apart = pages.reshape(pairs, rows, t, 2, width // 2)
        return jnp.moveaxis(apart, 3, 1).reshape(
            2 * pairs, rows, t, width // 2)[:self.depth]

    def rows_of(self, fresh):
        """(c (L, *idx, rank), k_pe (L, *idx, rope)) -> what the two leaves
        store: ``c``, and the rotary keys of each pair of layers side by
        side, (ceil(L / 2), *idx, 2 * rope)."""
        c, pe = fresh
        if self.depth % 2:
            pe = jnp.concatenate([pe, jnp.zeros_like(pe[:1])])
        paired = pe.reshape((pe.shape[0] // 2, 2) + pe.shape[1:])
        paired = jnp.moveaxis(paired, 1, -2)
        return c, paired.reshape(paired.shape[:-2] + (-1,))

    def with_leaves(self, stores) -> "PagedLatent":
        return self.replace(c=stores[0][0], pe=stores[1][0])


@flax.struct.dataclass
class PagedRead:
    """What the S=1 decode step hands the model in place of dense cache
    views when attention reads the pool in place (`ops.paged_attention`):
    the pool, the page table (rows, P) and, per slot row, how many
    positions to read from its pages — the row's position, 0 for a dead
    row (the fresh token's own k/v never come from the pool). ``layer`` is
    the block the read is for; the model sets it per block."""

    pool: Any            # PagedKV or PagedLatent
    page_table: jnp.ndarray
    live: jnp.ndarray
    layer: int = flax.struct.field(pytree_node=False, default=0)


def init_paged_kv(depth: int, n_pages: int, page_size: int, num_heads: int,
                  head_dim: int, dtype: Dtype = jnp.float32,
                  quantized: bool = False) -> PagedKV:
    """Zero-filled paged pool for ALL ``depth`` blocks (stacked axis 0)."""
    shape = (depth, n_pages, page_size, num_heads * head_dim)
    if quantized:
        scales = (depth, n_pages, page_size, num_heads)
        return PagedKV(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(scales, jnp.float32),
            v_scale=jnp.zeros(scales, jnp.float32), num_heads=num_heads)
    # k and v must be DISTINCT buffers: the serving step donates the whole
    # pool, and XLA rejects donating one buffer twice
    return PagedKV(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   num_heads=num_heads)


def init_paged_latent(depth: int, n_pages: int, page_size: int, rank: int,
                      rope: int, dtype: Dtype = jnp.float32) -> PagedLatent:
    """Zero-filled latent pool for ALL ``depth`` layers (stacked axis 0)."""
    return PagedLatent(
        c=jnp.zeros((depth, n_pages, page_size, rank), dtype),
        pe=jnp.zeros((-(-depth // 2), n_pages, page_size, 2 * rope), dtype),
        depth=depth)


def _dequant_pages(codes: jnp.ndarray, scales: jnp.ndarray,
                   dtype: Dtype) -> jnp.ndarray:
    return (codes.astype(jnp.float32) * scales[..., None]).astype(dtype)


def _quant_rows(x: jnp.ndarray, fused: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8-quantize (..., D) through the gradient-wire codec grid: one
    scale per leading row over the trailing D axis — THE same absmax /
    ``max(amax, 1e-30) * (1/127)`` / round/clip grid the wire uses, so the
    KV-page error model is the wire codec's one-shot bound. ``fused``
    threads the PR 6 tri-state (None = auto, True = Pallas fused kernel,
    False = XLA-composed reference) exactly like the wire's
    ``_quantize_int8_rows`` — the fused kernel is bit-identical by the
    PR 6 exactness model, so the page bytes do not depend on the flag."""
    from ..parallel.grad_sync import _quantize_int8_rows

    lead = x.shape[:-1]
    q, scales = _quantize_int8_rows(
        x.astype(jnp.float32).reshape(-1, x.shape[-1]), fused=fused)
    return q.reshape(x.shape), scales.reshape(lead)


@jax.named_scope("kv_gather")
def gather_paged_kv(pkv, page_table: jnp.ndarray,
                    dtype: Dtype = jnp.float32) -> Tuple[jnp.ndarray, ...]:
    """Per-slot dense view of the whole pool, the REFERENCE read:
    ``page_table`` (rows, P) int32 -> one (L, rows, P * page_size, *tail)
    view a leaf of the pool in ``dtype`` (dequantized when the pool is
    int8): k and v as (.., H, D) of a `PagedKV`, c and k_pe as (.., rank)
    and (.., rope) of a `PagedLatent` — one gather a leaf covering every
    layer. Per-layer slices of the result feed the bitwise-pinned
    `decode_dot_product_attention` unchanged; positions beyond a slot's
    write frontier carry scratch/stale (finite) values the caller's mask
    zeroes exactly."""
    rows, pages = page_table.shape

    def dense(leaf, codes, scales):
        ps = codes.shape[2]
        g = codes[:, page_table]                 # (L, rows, P, ps, W)
        g = pkv.view_of(leaf, g.reshape(
            g.shape[0], rows, pages * ps, g.shape[-1]))
        if scales is not None:
            s = scales[:, page_table].reshape(g.shape[:-1])
            return _dequant_pages(g, s, dtype)
        return g.astype(dtype)

    return tuple(dense(i, codes, scales)
                 for i, (codes, scales) in enumerate(pkv.leaves()))


def _put_rows(pkv, page: jnp.ndarray, off: jnp.ndarray, fresh,
              fused: Optional[bool]):
    """The one write all three scatters share: ``fresh``, one (L, *idx,
    *tail) array a leaf of the pool (k and v of a `PagedKV`), lands at
    (page, off), both of shape ``idx``, in every layer, as ONE row scatter a
    leaf over the pool's flattened (L * n_pages * page_size, width) view —
    row ``(l * n_pages + page) * page_size + off``. A write to drop arrives
    as ``page == n_pages`` and leaves through the far end of the view
    (``mode="drop"``), so it can never land in the next layer's page 0. The
    reshape is a bitcast and the scatter in place: indexed as
    ``store.at[:, page, off]`` XLA copied the whole pool into another
    layout and back (PERF.md, PR 25)."""
    leaves = pkv.leaves()
    if len(fresh) != len(leaves):
        raise ValueError(f"a row of this pool has {len(leaves)} leaves, "
                         f"got {len(fresh)}")
    fresh = pkv.rows_of(tuple(fresh))

    def put(store, new):
        depth, n_pages, ps, width = store.shape
        flat = depth * n_pages * ps
        row = (jnp.arange(depth).reshape((depth,) + (1,) * page.ndim)
               * n_pages + page[None]) * ps + off[None]
        row = jnp.where(page[None] < n_pages, row, flat).reshape(-1)
        return store.reshape(flat, width).at[row].set(
            new.reshape(-1, width).astype(store.dtype),
            mode="drop").reshape(store.shape)

    if not pkv.quantized:
        return pkv.with_leaves([(put(store, new), None)
                                for (store, _), new in zip(leaves, fresh)])
    coded = [_quant_rows(new, fused=fused) for new in fresh]
    return pkv.with_leaves([(put(store, q), put(scales, s)) for
                            (store, scales), (q, s) in zip(leaves, coded)])


@jax.named_scope("kv_scatter")
def scatter_paged_rows(pkv, page_table: jnp.ndarray, positions: jnp.ndarray,
                       *rows_then_active, fused: Optional[bool] = None):
    """Write ONE fresh row per slot per layer at that slot's own position:
    the paged decode step's write half, ONE scatter a leaf covering every
    layer. The rows come one array a leaf of the pool, then ``active``:
    ``(pool, table, positions, k_rows, v_rows, active)`` for a `PagedKV`
    — (L, rows, H, D) each, or (L, rows, H*D) as the kernel read returns
    them — and ``(pool, table, positions, c_rows, pe_rows, active)`` for a
    `PagedLatent`. ``positions`` (rows,) int32, ``active`` (rows,) bool:
    inactive rows are dropped by pointing their write at an out-of-range
    page, so finished/free slots never touch the pool (the token-granular
    join/leave substrate). ``fused`` is the int8 codec's PR 6 tri-state
    (`_quant_rows`)."""
    *fresh, active = rows_then_active
    _, n_pages, ps, _ = pkv.leaves()[0][0].shape
    rows = positions.shape[0]
    page = page_table[jnp.arange(rows), positions // ps]
    page = jnp.where(active, page, n_pages)         # drop inactive writes
    return _put_rows(pkv, page, positions % ps, fresh, fused)


@jax.named_scope("kv_scatter")
def scatter_paged_window(pkv, page_table: jnp.ndarray,
                         positions: jnp.ndarray, *rows_then_active,
                         fused: Optional[bool] = None):
    """`scatter_paged_rows` generalized to an S-position window per slot:
    ``positions`` / ``active`` are (rows, S) and the rows (L, rows, S,
    *tail) a leaf — the speculative VERIFY step's write half (target
    k/v for the whole K+1 window) and the draft engine's propose-round
    commit, still ONE scatter covering every layer. Inactive (row, offset)
    pairs — dead slots, positions past the slot's page span — are dropped
    exactly like the one-row form; the caller masks out-of-range window
    positions BEFORE the page lookup here clips them, so a clipped index
    can never alias a live page."""
    *fresh, active = rows_then_active
    _, n_pages, ps, _ = pkv.leaves()[0][0].shape
    rows = positions.shape[0]
    page = page_table[jnp.arange(rows)[:, None], positions // ps]  # (rows, S)
    page = jnp.where(active, page, n_pages)         # drop inactive writes
    return _put_rows(pkv, page, positions % ps, fresh, fused)


@jax.named_scope("kv_scatter")
def scatter_paged_prefill(pkv, page_row: jnp.ndarray, *seqs_then_length,
                          fused: Optional[bool] = None):
    """Write one slot's prompt rows — (L, S, *tail) a leaf of the pool,
    every layer at once, then ``length``: ``(pool, page_row, k_seqs,
    v_seqs, length)`` for a `PagedKV` — into its pages, positions [0,
    length) only: the paged prefill's write half. ``page_row`` (P,) is the
    slot's page-table row; positions past ``length`` (bucket padding) are
    dropped, so a shared prefix page is only ever rewritten with its own
    bytes (identical params + identical tokens -> identical rows, bitwise —
    the prefix-sharing safety argument)."""
    *fresh, length = seqs_then_length
    _, n_pages, ps, _ = pkv.leaves()[0][0].shape
    idx = jnp.arange(fresh[0].shape[1])
    page = jnp.where(idx < length, page_row[idx // ps], n_pages)
    return _put_rows(pkv, page, idx % ps, fresh, fused)


def paged_kv_bytes(pool) -> int:
    """At-rest bytes of a paged pool (every block's codes + scales for
    int8 pools, raw elements otherwise) — the serving analogue of
    grad_sync's wire accounting, compared against a dense cache
    (`SlotEngine.dense_baseline_bytes`)."""
    import jax

    return int(sum(arr.size * arr.dtype.itemsize
                   for arr in jax.tree_util.tree_leaves(pool)))


class MultiHeadAttention(nn.Module):
    """Self-attention with fused qkv projection.

    `attention_fn(q, k, v, mask, dtype)` defaults to the XLA einsum path;
    swap in `ops.flash_attention` / `ops.ring_attention` for long context.

    Explicit TP (``tp_size`` > 1, inside a shard_map binding ``tp_axis``):
    megatron column/row split — the qkv projection holds this shard's
    ``num_heads / tp_size`` heads (column-parallel, `copy_to_tp` at its
    input so the backward sums the per-shard cotangents), attention runs on
    the local heads, and the out projection is `RowParallelDense` (one
    forward psum per residual join, bias added once after it). Param tree
    paths are unchanged; kernel/bias SHAPES hold the local slice, exactly
    the `tp_fsdp_rules()` model-axis dims — the passive GSPMD constraints
    read as the explicit layout contract.

    KV cache (serving/): ``cache=(k, v)`` of shape (B, T, H, D) engages the
    incremental-decoding path and the call returns ``(out, new_cache)``.
    Three cache forms exist:

    * prefill (``cache_positions=None``, S > 1 legal): the fresh k/v land
      in slots [0, S) and attention runs over the FRESH k/v with the
      caller's (causal) mask — exactly the no-cache computation, so
      prefill logits are the eval forward's logits bit-for-bit, with the
      cache fill as a side output.
    * decode (``cache_positions`` = per-row write index, S == 1): the new
      token's k/v land at each row's own position (a where-scatter, so
      rows at different prompt lengths advance independently with no
      recompile) and attention runs over the UPDATED cache under the
      caller's per-row validity mask.

    * decode over the pool in place (``cache`` a `PagedRead`, S == 1): no
      cache write and no view; `_attend_pool` returns the fresh k/v rows
      as the new cache and the caller scatters them (the kernel read).

    With ``cache=None`` the path is byte-identical to the pre-cache module
    (pinned by tests/test_serving.py's lowering test).
    """

    num_heads: int
    head_dim: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    dropout_rate: float = 0.0
    use_bias: bool = True
    attention_fn: Callable = dot_product_attention
    tp_size: int = 1
    tp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True,
                 cache=None, cache_positions=None):
        features = self.num_heads * self.head_dim
        dense = functools.partial(nn.DenseGeneral, dtype=self.dtype,
                                  param_dtype=self.param_dtype,
                                  use_bias=self.use_bias)
        if self.tp_size > 1:
            return self._tp_call(x, mask, deterministic, cache, dense)
        qkv = dense(features=(3, self.num_heads, self.head_dim), name="qkv")(x)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        new_cache = None
        y = None
        if cache is not None:
            if self.attention_fn is not dot_product_attention:
                raise ValueError(
                    "KV-cache decoding needs the XLA attention path — the "
                    "kernel attention_fns own their causal structure and "
                    "take no cache (serve with --attention xla)")
            if isinstance(cache, PagedRead):
                y, new_cache = self._attend_pool(q, k, v, cache)
            elif cache_positions is None:
                ck, cv = cache
                # prefill: the S fresh rows fill slots [0, S); attention
                # runs over the FRESH k/v below (the eval computation)
                new_cache = (
                    jax.lax.dynamic_update_slice(
                        ck, k.astype(ck.dtype), (0, 0, 0, 0)),
                    jax.lax.dynamic_update_slice(
                        cv, v.astype(cv.dtype), (0, 0, 0, 0)))
            else:
                ck, cv = cache
                # decode: per-row scatter at each row's own position, then
                # attend over the updated cache. S == 1 is the classic
                # one-token step; S > 1 is the speculative VERIFY window
                # (serving/speculative.py) — window token j lands at
                # position + j BEFORE attention, and the caller's per-row
                # causal mask hides the not-yet-committed later rows, so
                # window row j is bitwise the s=1 step at that position.
                s_q = q.shape[1]
                if s_q == 1:
                    hit = (jnp.arange(ck.shape[1])[None, :]
                           == cache_positions[:, None])[:, :, None, None]
                    ck = jnp.where(hit, k.astype(ck.dtype), ck)
                    cv = jnp.where(hit, v.astype(cv.dtype), cv)
                else:
                    for j in range(s_q):
                        hit = (jnp.arange(ck.shape[1])[None, :]
                               == (cache_positions + j)[:, None]
                               )[:, :, None, None]
                        ck = jnp.where(hit, k[:, j:j + 1].astype(ck.dtype),
                                       ck)
                        cv = jnp.where(hit, v[:, j:j + 1].astype(cv.dtype),
                                       cv)
                new_cache = (ck, cv)
                y = decode_dot_product_attention(q, ck, cv, mask=mask,
                                                 dtype=self.dtype)
        if y is None:
            y = self.attention_fn(q, k, v, mask=mask, dtype=self.dtype)
        if self.dropout_rate and not deterministic:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=False)
        out = nn.DenseGeneral(features=x.shape[-1], axis=(-2, -1),
                              dtype=self.dtype, param_dtype=self.param_dtype,
                              use_bias=self.use_bias, name="out")(y)
        return out if cache is None else (out, new_cache)

    def _attend_pool(self, q, k, v, cache: PagedRead):
        """Decode at S == 1 with the pool read in place (the kernel read):
        no dense view and no row write. `ops.paged_attention` folds the
        fresh k/v row in at the row's own position; the rows returned as
        the new cache, (rows, H*D) in the pool's dtype, are what the caller
        scatters into the pool, once, after the last block. The kernel
        sits under ``kv_gather``: that region is "reading the cache,
        attention over it included"."""
        from ..ops.paged_attention import paged_attention

        rows, features = q.shape[0], self.num_heads * self.head_dim
        pool = cache.pool
        k_row = k.reshape(rows, features).astype(pool.k.dtype)
        v_row = v.reshape(rows, features).astype(pool.v.dtype)
        with jax.named_scope("kv_gather"):
            y = paged_attention(
                q.reshape(rows, features), k_row, v_row, pool.k, pool.v,
                cache.page_table, cache.live, layer=cache.layer,
                num_heads=self.num_heads)
        return y.reshape(q.shape).astype(self.dtype), (k_row, v_row)

    def _tp_call(self, x, mask, deterministic, cache, dense):
        """The explicit-TP attention body (tp_size > 1): local head slice,
        one forward psum at the out projection."""
        if cache is not None:
            raise ValueError(
                "explicit TP attention has no KV-cache path — serve TP "
                "checkpoints via the GSPMD rules (--mesh model=N without "
                "--fsdp-explicit on the serving side)")
        if self.dropout_rate and not deterministic:
            raise ValueError(
                "explicit TP runs the dropout RNG stream replicated over "
                "the model axis; per-shard head slices would draw "
                "correlated masks — train explicit TP with dropout 0")
        if self.num_heads % self.tp_size:
            raise ValueError(
                f"num_heads={self.num_heads} not divisible by "
                f"tp_size={self.tp_size}")
        heads_local = self.num_heads // self.tp_size
        x = copy_to_tp(x, self.tp_axis)
        qkv = dense(features=(3, heads_local, self.head_dim),
                    name="qkv")(x)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        y = self.attention_fn(q, k, v, mask=mask, dtype=self.dtype)
        return RowParallelDense(
            features=x.shape[-1], tp_axis=self.tp_axis, n_contract_dims=2,
            use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype, name="out")(y)


class MlpBlock(nn.Module):
    """Transformer MLP. Explicit TP (``tp_size`` > 1): fc1 is
    column-parallel (this shard's ``hidden_dim / tp_size`` neurons, with
    its bias slice), fc2 is `RowParallelDense` — one forward psum per
    residual join, full bias added once after it."""

    hidden_dim: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    dropout_rate: float = 0.0
    activation: Callable = nn.gelu
    tp_size: int = 1
    tp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        d = x.shape[-1]
        if self.tp_size > 1:
            if self.dropout_rate and not deterministic:
                raise ValueError(
                    "explicit TP runs the dropout RNG stream replicated "
                    "over the model axis; per-shard neuron slices would "
                    "draw correlated masks — train explicit TP with "
                    "dropout 0")
            if self.hidden_dim % self.tp_size:
                raise ValueError(
                    f"hidden_dim={self.hidden_dim} not divisible by "
                    f"tp_size={self.tp_size}")
            x = copy_to_tp(x, self.tp_axis)
            h = nn.Dense(self.hidden_dim // self.tp_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="fc1")(x)
            h = self.activation(h)
            return RowParallelDense(
                features=d, tp_axis=self.tp_axis, dtype=self.dtype,
                param_dtype=self.param_dtype, name="fc2")(h)
        h = nn.Dense(self.hidden_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="fc1")(x)
        h = self.activation(h)
        if self.dropout_rate and not deterministic:
            h = nn.Dropout(self.dropout_rate)(h, deterministic=False)
        out = nn.Dense(d, dtype=self.dtype, param_dtype=self.param_dtype,
                       name="fc2")(h)
        return out


class TransformerBlock(nn.Module):
    """Pre-LN transformer block (ViT/GPT-2 style; BERT overrides to post-LN)."""

    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    dropout_rate: float = 0.0
    layernorm_epsilon: float = 1e-5
    attention_fn: Callable = dot_product_attention
    tp_size: int = 1
    tp_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True,
                 cache=None, cache_positions=None):
        ln = functools.partial(nn.LayerNorm, epsilon=self.layernorm_epsilon,
                               dtype=self.dtype, param_dtype=self.param_dtype)
        y = ln(name="ln1")(x)
        y = MultiHeadAttention(
            num_heads=self.num_heads, head_dim=self.head_dim, dtype=self.dtype,
            param_dtype=self.param_dtype, dropout_rate=self.dropout_rate,
            attention_fn=self.attention_fn, name="attn",
            tp_size=self.tp_size, tp_axis=self.tp_axis,
        )(y, mask=mask, deterministic=deterministic, cache=cache,
          cache_positions=cache_positions)
        new_cache = None
        if cache is not None:
            y, new_cache = y
        x = x + y
        y = ln(name="ln2")(x)
        y = MlpBlock(hidden_dim=self.mlp_dim, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     dropout_rate=self.dropout_rate, name="mlp",
                     tp_size=self.tp_size, tp_axis=self.tp_axis,
                     )(y, deterministic=deterministic)
        return x + y if cache is None else (x + y, new_cache)


def padded_vocab_size(vocab_size: int, multiple: int) -> int:
    """Megatron-style vocab padding: the smallest multiple of `multiple`
    >= vocab_size. GPT-2's 50257 is indivisible by any TP degree, so the
    (vocab, d) embedding — the model's largest tensor — could never shard
    over the `model` axis without this (it would silently replicate, see
    parallel/sharding.feasible_spec). 0 or 1 disables padding."""
    if multiple <= 1:
        return vocab_size
    return -(-vocab_size // multiple) * multiple


class VocabPaddingMixin:
    """Shared accessors for Megatron-style vocab padding. Models declare the
    ``pad_vocab_to_multiple_of: int = 0`` field themselves (flax's dataclass
    transform requires fields on the Module subclass); this mixin supplies
    the derived quantities so the padding formula lives in one place."""

    @property
    def padded_vocab(self) -> int:
        return padded_vocab_size(self.vocab_size, self.pad_vocab_to_multiple_of)

    @property
    def vocab_pad_params(self) -> int:
        """Extra params introduced by vocab padding (for HF-exact reporting)."""
        return (self.padded_vocab - self.vocab_size) * self.hidden_dim


def mask_vocab_padding(logits: jnp.ndarray, vocab_size: int) -> jnp.ndarray:
    """Neutralize padded vocab columns: set their logits to the dtype min so
    softmax assigns them exactly zero probability (exp underflows to 0.0)
    and argmax never selects them. With that, CE loss / token accuracy over
    a padded head are bit-identical to the unpadded head."""
    padded = logits.shape[-1]
    if padded == vocab_size:
        return logits
    keep = jnp.arange(padded) < vocab_size
    return jnp.where(keep, logits, jnp.finfo(logits.dtype).min)


def causal_mask(seq_len: int) -> jnp.ndarray:
    """(1, 1, S, S) lower-triangular True=attend mask."""
    return jnp.tril(jnp.ones((seq_len, seq_len), bool))[None, None]


def padding_mask(attention_mask: jnp.ndarray) -> jnp.ndarray:
    """(B, T) 1=real token -> (B, 1, 1, T) attend mask."""
    return attention_mask[:, None, None, :].astype(bool)


def tp_fsdp_rules() -> PartitionRules:
    """The combined layout table every transformer here ships: megatron TP
    over ``model`` on the head/neuron dim + ZeRO-style FSDP over ``fsdp`` on
    the complementary (d_model) dim of the same kernels (SURVEY.md §2c; the
    promise at parallel/mesh.py `fsdp` axis).

    One table serves every mesh: an axis of size 1 contributes nothing, so
    pure DP (both axes 1) reproduces the DDP replicated layout, ``--mesh
    model=N`` is pure TP, ``--mesh fsdp=N`` is pure FSDP, and ``--mesh
    fsdp=M,model=N`` is 2-D parameter sharding.

    The EXPLICIT TP x FSDP step (ISSUE 13) reads this same table as its
    layout contract: `parallel.sharding.tp_split_dims` takes each leaf's
    model-axis dim from these specs, and the tp_size>1 module forms above
    compute with exactly those slices — the passive GSPMD constraints and
    the explicit layout cannot disagree.

    Because `shard_pytree` applies the same table to the optimizer state,
    the AdamW/SGD moments are sharded identically — the ZeRO-2/3 memory win.
    The batch is sharded over (data, fsdp) jointly (sharding.batch_spec), so
    fsdp devices also do data-parallel work; XLA inserts the per-layer
    all-gather (params) and reduce-scatter (grads) that a hand-written FSDP
    wrapper would schedule manually.
    """
    return PartitionRules([
        (r"attn/qkv/kernel", P(FSDP, None, MODEL, None)),
        (r"attn/qkv/bias", P(None, MODEL, None)),
        (r"attn/out/kernel", P(MODEL, None, FSDP)),
        (r"mlp/fc1/kernel", P(FSDP, MODEL)),
        (r"mlp/fc1/bias", P(MODEL)),
        (r"mlp/fc2/kernel", P(MODEL, FSDP)),
        (r"(token_embedding|wte)/embedding", P(MODEL, FSDP)),
        (r"(position_embedding|wpe)/embedding", P(None, FSDP)),
        (r"patch_embed/kernel", P(None, None, None, FSDP)),
        (r"(head|fc|mlm_dense)/kernel", P(FSDP, None)),
    ])

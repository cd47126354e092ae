"""Collectives — the visible-API parity surface for the reference's NCCL usage.

The reference touches NCCL in four ways (/root/reference/train_ddp.py):
(a) rendezvous (:65)        -> runtime.dist.setup_distributed
(b) dist.barrier (:112)     -> `barrier()` here (host-level sync)
(c) DDP bucketed gradient all-reduce (:305-310, implicit C++ reducer)
                            -> NOT an API here at all: gradients sync because
                               the batch is sharded over the mesh and the loss
                               mean contracts over the global batch — XLA
                               inserts (and overlaps) the all-reduce.
(d) scalar metric all-reduce via `reduce_tensor` (:159-167, :251-253, :290-292)
                            -> `psum`/`pmean` (in-jit) and `reduce_scalar`
                               (host-level), both with the reference's
                               "identity when single-device" convention
                               (ref :164-165).

Two distinct layers, never to be confused:

* **In-program collectives** (`psum`, `pmean`, `pmax`, `psum_scatter`,
  `all_gather`, `ppermute_ring`, `all_to_all`): used inside `shard_map`-ped
  functions where mesh axis names are bound. These lower to XLA collectives
  riding ICI. `psum_scatter`/`all_gather` are the two halves of an
  all-reduce, split so the ZeRO-1 weight update (training/loop.py) can do
  per-replica work between them.
* **Host-level collectives** (`barrier`, `broadcast_from_main`,
  `host_all_gather`, `reduce_scalar`): process-level synchronization across
  hosts, used for data-download gating (ref :111-112) and metric fan-in.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

AxisName = Union[str, Sequence[str]]

def shard_map(f: Callable, mesh: Mesh, in_specs: Any, out_specs: Any):
    """`jax.shard_map` with replication checking off — the one entry point
    for every shard_map in the repo (the ``shard-map-shim-only`` rule).
    Checking is disabled because the bodies here use collectives whose
    replication the checker cannot always prove (psum_scatter / all_gather
    chains)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _axes_present(axis_name: AxisName, mesh: Optional[Mesh]) -> bool:
    """Static (trace-time) check: does `axis_name` have size > 1?

    Implements the reference's single-process passthrough
    (train_ddp.py:164-165) as a *compile-time* no-op rather than a runtime
    branch — XLA never even sees a collective on trivial axes.
    """
    if mesh is None:
        return True  # caller is inside shard_map and asserts the axis exists
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    unknown = [n for n in names if n not in mesh.shape]
    if unknown:
        # A typo'd axis must not silently become a no-op — that would
        # silently disable gradient sync.
        raise KeyError(f"axis {unknown} not in mesh axes {tuple(mesh.shape)}")
    return any(mesh.shape[n] > 1 for n in names)


def psum(x: Any, axis_name: AxisName, *, mesh: Optional[Mesh] = None) -> Any:
    """SUM all-reduce over mesh axes (maps reduce_tensor, train_ddp.py:159-167).

    Identity when the axes are trivial, mirroring ref :164-165.
    """
    if not _axes_present(axis_name, mesh):
        return x
    return lax.psum(x, axis_name)


def pmean(x: Any, axis_name: AxisName, *, mesh: Optional[Mesh] = None) -> Any:
    """MEAN all-reduce (the gradient-sync op DDP performs implicitly)."""
    if not _axes_present(axis_name, mesh):
        return x
    return lax.pmean(x, axis_name)


def pmax(x: Any, axis_name: AxisName, *, mesh: Optional[Mesh] = None) -> Any:
    if not _axes_present(axis_name, mesh):
        return x
    return lax.pmax(x, axis_name)


def psum_scatter(x: Any, axis_name: AxisName, *, scatter_dimension: int = 0,
                 tiled: bool = True, mesh: Optional[Mesh] = None) -> Any:
    """SUM-reduce across the axes, each replica keeping only ITS chunk of the
    result — the first half of an all-reduce (all-reduce = reduce-scatter +
    all-gather), and the gradient-sync primitive of the ZeRO-1 sharded
    weight update (Xu et al., PAPERS.md): every replica receives 1/N of the
    synchronized gradient instead of all of it.

    Identity when the axes are trivial (reducing over one replica and
    keeping its single chunk is the value itself) — the same single-device
    passthrough convention as `psum` (ref train_ddp.py:164-165).
    """
    if not _axes_present(axis_name, mesh):
        return x
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=tiled)


def all_gather(x: Any, axis_name: AxisName, *, axis: int = 0,
               tiled: bool = True, mesh: Optional[Mesh] = None) -> Any:
    """Concatenate every replica's chunk along `axis` — the second half of an
    all-reduce, and the ZeRO-1 weight-update epilogue (each replica gathers
    the 1/N of the new parameters every other replica just updated).

    Identity when the axes are trivial, like `psum`/`psum_scatter`.
    """
    if not _axes_present(axis_name, mesh):
        return x
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute_ring(x: Any, axis_name: str, *, shift: int = 1) -> Any:
    """Rotate `x` around the ring of `axis_name` — the building block of ring
    attention (KV blocks circulate over the ICI ring). No NCCL analogue in the
    reference (max sequence there is a 32x32 image); this is the long-context
    primitive SURVEY.md §5 requires."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x: Any, axis_name: str, split_axis: int, concat_axis: int) -> Any:
    """All-to-all over a mesh axis — the Ulysses (head-sharding) primitive."""
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)


# ---------------------------------------------------------------------------
# Explicit tensor-parallel region operators (Megatron's f / g).
#
# Inside a shard_map'd train step the TP layers consume a replicated
# activation with per-shard weight slices; autodiff must then produce
# (a) a full (cross-shard-summed) cotangent flowing UPSTREAM of each
# parallel region — each shard's slice contributes an independent partial —
# and (b) an identity backward through the output psum (the cotangent of a
# replicated value consumed replicatedly is itself). jax's built-in
# transpose rules for psum/all_gather encode a different cotangent
# convention under check-free shard_map (per-device cotangents SUM across
# replicas), which would scale gradients by the TP degree here. These
# custom_vjp wrappers pin the exact collective structure of both passes BY
# CONSTRUCTION, independent of jax-version transpose conventions — one
# model-axis psum per residual join in the forward, its mirror at the
# region input in the backward (models/layers.py uses them; the
# `tp-psum-signature` analysis rule counts them in HLO).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp(x: jnp.ndarray, axis_name: AxisName) -> jnp.ndarray:
    """Megatron's ``f``: identity forward into a tensor-parallel region,
    SUM over the TP axis in the backward. Placed at each parallel region's
    input (the qkv / fc1 projection input, the tied-head matmul input), so
    every upstream consumer — layernorms, embeddings, the residual stream —
    receives the full cotangent instead of one shard's partial."""
    return x


def _copy_to_tp_fwd(x, axis_name):
    return x, None


def _copy_to_tp_bwd(axis_name, _res, ct):
    return (lax.psum(ct, axis_name),)


copy_to_tp.defvjp(_copy_to_tp_fwd, _copy_to_tp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tp(x: jnp.ndarray, axis_name: AxisName) -> jnp.ndarray:
    """Megatron's ``g``: SUM the row-parallel partial outputs over the TP
    axis in the forward (THE one psum per residual join), identity in the
    backward (the summed output is replicated; each shard's partial gets
    the replicated cotangent unchanged)."""
    return lax.psum(x, axis_name)


def _reduce_from_tp_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _reduce_from_tp_bwd(axis_name, _res, ct):
    return (ct,)


reduce_from_tp.defvjp(_reduce_from_tp_fwd, _reduce_from_tp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_all_gather(x: jnp.ndarray, axis_name: AxisName,
                  dim: int) -> jnp.ndarray:
    """Concatenate per-shard slices along ``dim`` over the TP axis
    (the vocab-parallel logits gather), with the exact backward: each
    shard takes ITS slice of the (replicated) cotangent — a dynamic
    slice, no collective. jax's built-in all_gather transpose is a
    psum_scatter, which under the check-free shard_map convention would
    scale the cotangent by the TP degree (see `copy_to_tp`)."""
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)


def _tp_all_gather_fwd(x, axis_name, dim):
    return lax.all_gather(x, axis_name, axis=dim, tiled=True), x.shape[dim]


def _tp_all_gather_bwd(axis_name, dim, size, ct):
    idx = lax.axis_index(axis_name)
    return (lax.dynamic_slice_in_dim(ct, idx * size, size, axis=dim),)


tp_all_gather.defvjp(_tp_all_gather_fwd, _tp_all_gather_bwd)


# ---------------------------------------------------------------------------
# Megatron parallel-vocab cross-entropy (Shoeybi et al., arXiv:1909.08053
# §3): the loss over vocab-SHARDED logit columns, without ever gathering
# the (B, S, vocab) logits over the model axis. The softmax denominator
# and the target-column logit are the only cross-shard facts CE needs —
# two (B, S)-sized stats instead of a vocab-sized gather, shrinking the
# head's model-axis wire by ~padded_vocab/4 per token.
# ---------------------------------------------------------------------------


class TpShardedLogits:
    """This shard's logit COLUMNS ``local`` = full_logits[..., lo:hi) with
    ``lo = axis_index(axis_name) * vocab_rows`` — what the vocab-parallel
    LM head returns instead of gathered logits (models/gpt2.py). The task
    layer branches on this type (training/tasks.py) and computes CE via
    `tp_parallel_cross_entropy`. Registered as a pytree so it can cross
    transform boundaries like the plain logits array it replaces."""

    def __init__(self, local: jnp.ndarray, axis_name: AxisName,
                 vocab_rows: int, vocab_size: int):
        self.local = local
        self.axis_name = axis_name
        self.vocab_rows = int(vocab_rows)
        self.vocab_size = int(vocab_size)

    def map_local(self, fn: Callable) -> "TpShardedLogits":
        """Same shards, ``fn`` applied to the local columns (the task's
        next-token shift: ``lg = logits.map_local(lambda x: x[:, :-1])``)."""
        return TpShardedLogits(fn(self.local), self.axis_name,
                               self.vocab_rows, self.vocab_size)


jax.tree_util.register_pytree_node(
    TpShardedLogits,
    lambda s: ((s.local,), (s.axis_name, s.vocab_rows, s.vocab_size)),
    lambda aux, children: TpShardedLogits(children[0], *aux))


def tp_parallel_cross_entropy(
        logits: TpShardedLogits,
        targets: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(per-position CE, predicted-correct) from vocab-sharded logit
    columns, exactly equal (at fp32 reassociation tolerance) to softmax CE
    over the gathered logits.

    Two model-axis collectives total, both (targets.shape, 2)-sized fp32:
    a stop-gradient pmax for the safe-softmax max, and ONE stacked psum
    carrying [sum_j exp(l_j - m), l_target-partial] (`reduce_from_tp`, so
    the backward is identity — the gradient of CE w.r.t. the local
    columns is softmax - onehot with no further collective, each shard
    producing exactly its own columns' cotangents). The pmax operand is
    deliberately stacked to width 2 as well: both stats then share ONE
    census size class, so the `tp-psum-signature` budget's floor logic is
    a single threshold instead of a straddle window (analysis/hlo_rules).

    ``correct`` is target-logit == global max — argmax-up-to-ties, which
    matches ``argmax(gathered) == target`` everywhere the max is unique.
    """
    local = logits.local.astype(jnp.float32)
    axis, rows = logits.axis_name, logits.vocab_rows
    shard = lax.axis_index(axis)
    # stop_gradient on the OPERAND (not the result): the tangent is then
    # a symbolic zero and the pmax — which has no differentiation rule —
    # is never linearized; the max is a shift, so it carries no gradient
    local_max = lax.stop_gradient(jnp.max(local, axis=-1))
    m = lax.pmax(jnp.stack([local_max, local_max], -1), axis)[..., 0]
    sumexp = jnp.sum(jnp.exp(local - m[..., None]), axis=-1)
    local_ids = targets - shard * rows
    valid = (local_ids >= 0) & (local_ids < rows)
    picked = jnp.take_along_axis(
        local, jnp.clip(local_ids, 0, rows - 1)[..., None], axis=-1)[..., 0]
    tgt_partial = jnp.where(valid, picked, 0.0)
    stats = reduce_from_tp(jnp.stack([sumexp, tgt_partial], -1), axis)
    total, tgt_logit = stats[..., 0], stats[..., 1]
    ce = jnp.log(total) + m - tgt_logit
    return ce, tgt_logit >= m


# ---------------------------------------------------------------------------
# Host-level (cross-process) collectives.
# ---------------------------------------------------------------------------


def barrier(name: str = "barrier") -> None:
    """Block until every process arrives (maps dist.barrier, train_ddp.py:112).

    Single-process: immediate return (ref is_distributed() gate, :111).
    """
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def broadcast_from_main(x: Any) -> Any:
    """Process-0 value to every process (DDP broadcasts params rank0->all at
    wrap time, train_ddp.py:305-310; we broadcast explicitly at init)."""
    if jax.process_count() == 1:
        return x
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(x)


def host_all_gather(x: Any) -> Any:
    """Gather a host value from every process -> stacked numpy array."""
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(lambda a: np.asarray(a)[None], x)
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x)


def reduce_scalar(x: Union[float, int, jnp.ndarray], op: str = "sum") -> float:
    """Host-level scalar reduction across processes — the literal parity API
    for `reduce_tensor` (train_ddp.py:159-167): SUM all-reduce, identity when
    single-process. Used for end-of-epoch metric fan-in (ref :251-253)."""
    val = float(np.asarray(x))
    if jax.process_count() == 1:
        return val
    gathered = np.asarray(host_all_gather(val))
    if op == "sum":
        return float(gathered.sum())
    if op == "max":
        return float(gathered.max())
    if op == "mean":
        return float(gathered.mean())
    raise ValueError(f"unknown op {op!r}")

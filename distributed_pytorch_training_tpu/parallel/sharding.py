"""Sharding rules: param-path regex -> PartitionSpec.

The DDP wrapper (/root/reference/train_ddp.py:303-311) has exactly one layout:
every parameter replicated on every device. Here layout is first-class: each
model ships `PartitionRules` — an ordered list of (path-regex, PartitionSpec)
— and `shard_pytree` places params/optimizer state on the mesh accordingly.
Pure DP reproduces DDP (all params replicated); TP/FSDP are just different
rule tables over the same machinery (SURVEY.md §2c).
"""

from __future__ import annotations

import logging
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import BATCH_AXES

logger = logging.getLogger(__name__)

# Degraded layouts warned about already (one warning per unique shape/spec —
# rule tables hit the same shapes for params+optimizer state repeatedly).
_degraded_warned: set = set()


def reset_degradation_warnings() -> None:
    """Clear the warn-once state so a new mesh/model setup warns afresh
    (long-lived processes and tests would otherwise inherit stale state)."""
    _degraded_warned.clear()


class PartitionRules:
    """Ordered (regex, PartitionSpec) table; first match on the '/'-joined
    param path wins; no match -> fully replicated (the DDP default layout)."""

    def __init__(self, rules: Sequence[Tuple[str, P]] = ()):  # noqa: D401
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, path: str, ndim: Optional[int] = None) -> P:
        for pat, spec in self._rules:
            if pat.search(path):
                if ndim is not None and len(spec) > ndim:
                    raise ValueError(
                        f"rule {pat.pattern!r} spec {spec} has more axes than "
                        f"param {path!r} with ndim={ndim}"
                    )
                return spec
        return P()  # replicated

    def __add__(self, other: "PartitionRules") -> "PartitionRules":
        out = PartitionRules()
        out._rules = self._rules + other._rules
        return out

    def axes_used(self) -> set:
        """Mesh axis names any rule in the table can place a dim on (used by
        mesh validation: an axis no rule mentions cannot shard a param)."""
        axes = set()
        for _, spec in self._rules:
            for entry in spec:
                if entry is None:
                    continue
                names = (entry,) if isinstance(entry, str) else tuple(entry)
                axes.update(names)
        return axes


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_path(rules: Optional[PartitionRules], path: str, ndim: int) -> P:
    if rules is None:
        return P()
    return rules.spec_for(path, ndim)


def tree_specs(tree: Any, rules: Optional[PartitionRules]) -> Any:
    """PartitionSpec pytree matching `tree` (for jit in_shardings / orbax)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_path(rules, _path_str(path), np.ndim(leaf)),
        tree,
    )


def feasible_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec entries whose mesh axes do not divide the dimension.

    Rules describe the *intended* layout; some tensors cannot honor it (e.g.
    a (50257, d) GPT-2 vocab embedding is not divisible by a model axis of
    2 — Megatron pads the vocab; we keep exact parity shapes and replicate
    that dim instead). Infeasible dims degrade to replication, per-dim."""
    if not len(spec):
        return spec
    if len(spec) > len(shape):
        # A rule matching a tensor of smaller rank is a bug in the rule
        # table, not a layout infeasibility — keep the loud failure.
        raise ValueError(
            f"PartitionSpec {spec} has more entries than tensor rank "
            f"{len(shape)} (shape {shape})")
    entries = []
    changed = False
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            entries.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        size = int(np.prod([mesh.shape[n] for n in names]))
        if dim % size:
            entries.append(None)
            changed = True
        else:
            entries.append(entry)
    if changed:
        # Warn (once per shape/spec) — a silently-replicated tensor the rules
        # meant to split multiplies per-device memory and hides rule bugs.
        key = (tuple(spec), shape, tuple(sorted(mesh.shape.items())))
        if key not in _degraded_warned:
            _degraded_warned.add(key)
            logger.warning(
                "sharding %s infeasible for shape %s (indivisible dims) — "
                "degraded to %s (replicating those dims)",
                spec, shape, P(*entries))
    return P(*entries)


def shard_pytree(tree: Any, mesh: Mesh, rules: Optional[PartitionRules] = None) -> Any:
    """Place a pytree on the mesh per the rules (replicated by default).

    This is the moment DDP performs its rank0->all param broadcast
    (train_ddp.py:305-310); here placement and layout are one operation.
    Dims the rules would split unevenly are replicated instead (see
    `feasible_spec`).
    """
    specs = tree_specs(tree, rules)
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(
            leaf,
            NamedSharding(mesh, feasible_spec(spec, np.shape(leaf), mesh))),
        tree,
        specs,
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# ZeRO-1 flat-shard layout (Xu et al., "Automatic Cross-Replica Sharding of
# Weight Update in Data-Parallel Training", PAPERS.md).
#
# The sharded weight update partitions every parameter's *flattened* value
# over the data-parallel axes: tensor shapes never constrain divisibility
# (a (1000,) bias on 8 replicas pads 1000 -> 1008 and shards 126 elements
# per replica), and the optimizer update becomes shape-agnostic elementwise
# work on (padded_size / N,) chunks. Padding elements carry zero gradient,
# so they stay zero through any elementwise optimizer chain.
# ---------------------------------------------------------------------------


def flat_padded_size(size: int, n_shards: int) -> int:
    """`size` rounded up to a multiple of `n_shards` (0-padding at the end)."""
    return size + (-size % n_shards)


def flatten_pad(x, n_shards: int):
    """1-D view of `x`, zero-padded so it splits evenly into `n_shards`."""
    import jax.numpy as jnp

    flat = jnp.ravel(x)
    pad = -flat.size % n_shards
    return jnp.pad(flat, (0, pad)) if pad else flat


def dp_flat_specs(tree: Any, axes: Sequence[str] = BATCH_AXES) -> Any:
    """Spec tree for a ZeRO-1 flat-sharded pytree: every array leaf is 1-D
    and sharded over the data-parallel axes; scalars (optimizer step counts)
    stay replicated."""
    return jax.tree_util.tree_map(
        lambda leaf: P(tuple(axes)) if np.ndim(leaf) else P(), tree)


def fsdp_flat_params(params: Any, mesh: Mesh, n_shards: int) -> Any:
    """Rewrite a (replicated, model-shaped) parameter tree into the
    explicit-FSDP at-rest layout: every leaf flat-padded to a multiple of
    ``n_shards`` and sharded 1/N over the batch axes — the zero1 moment
    layout (`optim.zero1_opt_state`) applied to the PARAMETERS themselves.

    Built under jit with ``out_shardings`` so XLA writes each replica's
    chunk in place (the `_born_sharded_zeros` idiom: no full-tree flat
    transient on one device). The original shapes/dtypes live on the
    caller (Trainer keeps a ShapeDtypeStruct template for the per-layer
    gather's unflatten)."""
    specs = dp_flat_specs(jax.eval_shape(
        lambda p: jax.tree_util.tree_map(
            lambda x: flatten_pad(x, n_shards), p), params))
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    make = jax.jit(
        lambda p: jax.tree_util.tree_map(
            lambda x: flatten_pad(x, n_shards), p),
        out_shardings=shardings)
    return make(params)


# ---------------------------------------------------------------------------
# Explicit TP x FSDP layout (ISSUE 13): the tp_fsdp_rules() table read as an
# EXPLICIT layout contract. Each leaf gets a model-axis split dim from the
# rules (None = model-replicated); the at-rest layout is then model-major
# flat-padded: (M * flat_padded(local_size, N),) where "local" is the leaf's
# contiguous TP slice (split leaves) or a full per-model-shard copy
# (replicated leaves — same per-device bytes as plain model-axis
# replication, but a UNIFORM one-spec layout so the moments/EF machinery of
# explicit FSDP applies verbatim). Sharded P((model, data, fsdp)) on dim 0,
# so inside the step's shard_map each device holds exactly its (padded/N,)
# chunk of its model shard's slice.
# ---------------------------------------------------------------------------


def tp_split_dims(template: Any, rules: Optional[PartitionRules],
                  model_n: int) -> Any:
    """Per-leaf model-axis split dim (or None) — the tp_fsdp_rules() table
    read as the explicit-TP layout contract.

    A leaf splits on the first spec dim whose entry names the ``model``
    axis, IF that dim divides by ``model_n``; indivisible dims degrade to
    model-replication with the same warn-once `feasible_spec` issues (the
    GPT-2 vocab embedding without Megatron padding is the canonical case).
    The EXPLICIT TP forward (models/layers.py tp_size>1) derives its local
    shapes from the same divisibility conditions, so plan and computation
    cannot disagree."""
    from .mesh import MODEL

    def one(path, leaf):
        spec = spec_for_path(rules, _path_str(path), np.ndim(leaf))
        shape = np.shape(leaf)
        for dim, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            if MODEL not in names:
                continue
            if shape[dim] % model_n:
                key = (("tp", tuple(spec)), shape, model_n)
                if key not in _degraded_warned:
                    _degraded_warned.add(key)
                    logger.warning(
                        "explicit TP: %s dim %d (size %d) not divisible by "
                        "model=%d — leaf stays model-replicated (Megatron "
                        "vocab padding un-degrades embeddings)",
                        _path_str(path), dim, shape[dim], model_n)
                return None
            return dim
        return None

    return jax.tree_util.tree_map_with_path(one, template)


def tp_local_struct(template: Any, split_dims: Any, model_n: int) -> Any:
    """ShapeDtypeStruct tree of the per-model-shard LOCAL shapes: split
    leaves shrink their split dim by 1/M, replicated leaves keep their full
    shape (each model shard holds a copy)."""

    import jax.numpy as jnp

    def one(leaf, dim):
        shape = list(np.shape(leaf))
        if dim is not None:
            shape[dim] //= model_n
        return jax.ShapeDtypeStruct(tuple(shape), jnp.result_type(leaf))

    return jax.tree_util.tree_map(one, template, split_dims)


def _tp_slice(x, dim: Optional[int], model_n: int, shard: int):
    """Model shard ``shard``'s contiguous local slice of one leaf (the full
    leaf when dim is None)."""
    import jax.numpy as jnp  # noqa: F401

    if dim is None:
        return x
    c = x.shape[dim] // model_n
    return jax.lax.slice_in_dim(x, shard * c, (shard + 1) * c, axis=dim)


def tp_flat_leaf(x, dim: Optional[int], model_n: int, n_shards: int):
    """One leaf's model-major flat-padded at-rest vector: the concatenation
    over model shards of flat_padded(ravel(local slice), N). Trace-time
    Python loop over M (small); C-order ravel of each LOCAL slice, so the
    in-step per-layer gather's reshape-to-local-shape is pure arithmetic."""
    import jax.numpy as jnp

    rows = [flatten_pad(_tp_slice(x, dim, model_n, s), n_shards)
            for s in range(model_n)]
    return jnp.concatenate(rows) if model_n > 1 else rows[0]


def fsdp_tp_flat_params(params: Any, mesh: Mesh, n_shards: int,
                        model_n: int, split_dims: Any,
                        axes: Sequence[str]) -> Any:
    """`fsdp_flat_params` for the 2-D (TP x FSDP) layout: every leaf lands
    in the model-major flat-padded form (`tp_flat_leaf`), born sharded over
    ``axes`` so each device writes only its chunk in place."""
    structs = jax.eval_shape(
        lambda p: jax.tree_util.tree_map(
            lambda x, d: tp_flat_leaf(x, d, model_n, n_shards),
            p, split_dims), params)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(tuple(axes)) if np.ndim(s) else P()),
        structs)
    make = jax.jit(
        lambda p: jax.tree_util.tree_map(
            lambda x, d: tp_flat_leaf(x, d, model_n, n_shards),
            p, split_dims),
        out_shardings=shardings)
    return make(params)


def tp_unflatten_leaf(flat, full_shape: Tuple[int, ...], dtype,
                      dim: Optional[int], model_n: int):
    """Model-shaped leaf from its model-major flat-padded at-rest vector
    (outside shard_map — eval/diagnostics; GSPMD inserts the movement).
    Split leaves re-concatenate their M local slices along the split dim;
    replicated leaves take copy 0 (all copies are bit-identical — each
    model group runs the same data-axis scatter on the same grads)."""
    import jax.numpy as jnp

    full_shape = tuple(full_shape)
    local_shape = list(full_shape)
    if dim is not None:
        local_shape[dim] //= model_n
    size = int(np.prod(local_shape) or 1)
    mat = flat.reshape(model_n, -1)[:, :size]
    if dim is None:
        return mat[0].reshape(full_shape).astype(dtype)
    rows = [mat[s].reshape(local_shape) for s in range(model_n)]
    return jnp.concatenate(rows, axis=dim).astype(dtype)


def tp_clip_weights_for_model(model, rules: Optional[PartitionRules],
                              model_n: int, sample_input) -> dict:
    """`tp_clip_weights` derived straight from a model + its rules — THE
    one derivation both train.py and the experiments harness use (a weighting
    rule living in two hand-rolled copies would silently diverge between
    the CLI and the experiment arms). One abstract trace of ``model.init`` on
    ``sample_input`` recovers the leaf paths/shapes the divisibility
    decisions need."""
    import functools

    import jax.numpy as jnp

    template = jax.eval_shape(
        functools.partial(model.init, train=False), jax.random.PRNGKey(0),
        jnp.asarray(sample_input))["params"]
    split_dims = tp_split_dims(template, rules, model_n)
    return tp_clip_weights(template, split_dims, model_n)


def tp_clip_weights(template: Any, split_dims: Any, model_n: int) -> dict:
    """{'/'.joined leaf path: squared-norm weight} for the TP-aware global
    norm clip (optim.clip_by_global_norm_dp): a psum over
    (model,) + batch axes counts model-replicated leaves M times (each
    model shard holds a copy), so their squared contribution weighs 1/M;
    TP-split leaves' disjoint slices weigh 1. Exact in fp32 for
    power-of-two M (the usual TP degrees); otherwise a reassociation-level
    perturbation PARITY.md documents."""
    out = {}
    flat = jax.tree_util.tree_leaves_with_path(
        jax.tree_util.tree_map(lambda l, d: (d is not None), template,
                               split_dims))
    for path, is_split in flat:
        out[_path_str(path)] = 1.0 if is_split else 1.0 / model_n
    return out


def reshard_flat_padded(x, new_padded_len: int, name: str = "") -> "np.ndarray":
    """Re-slice one flat-padded leaf from old-N chunking to new-M chunking.

    A valid flat-padded vector holds its true content in ``[0, true_size)``
    and zeros beyond (``flatten_pad`` pads with zeros; gradients/updates on
    pad elements are zero through any elementwise optimizer chain, and the
    int8 codecs' residuals stay zero there too — the carried value at a pad
    slot is always 0). Since ``true_size <= flat_padded_size(true_size, M)``
    for ANY shard count M, re-chunking reduces to truncate-or-zero-extend
    to the new padded length — no true size needed. Host-side numpy (this
    runs at restore time, one leaf at a time — never on the step path).

    Shrinking asserts the dropped tail really is zero: a nonzero tail means
    the input was NOT a flat-padded layout (or carried real content into
    the pad region) and silently dropping it would corrupt the trajectory.
    """
    x = np.asarray(x)
    if x.ndim != 1:
        raise ValueError(
            f"reshard_flat_padded expects a 1-D flat-padded vector, got "
            f"shape {x.shape}" + (f" for {name}" if name else ""))
    old_len = x.shape[0]
    if new_padded_len < old_len:
        tail = x[new_padded_len:]
        if np.any(tail):
            raise ValueError(
                f"re-chunking {old_len} -> {new_padded_len} elements would "
                f"drop {int(np.count_nonzero(tail))} NONZERO tail "
                "element(s) — the input is not a zero-padded flat layout"
                + (f" ({name})" if name else ""))
        return np.array(x[:new_padded_len])
    if new_padded_len > old_len:
        return np.pad(x, (0, new_padded_len - old_len))
    return np.array(x)


def reshard_flat_leaf(value, new_shape: Tuple[int, ...],
                      name: str = "") -> "np.ndarray":
    """The ONE per-leaf reshard dispatch (both the whole-tree helper below
    and the elastic restore path route through it, so the invariant cannot
    fork): same shape -> passthrough, 1-D length change -> flat-padded
    re-chunk, anything else -> loud structure error naming the leaf."""
    v = np.asarray(value)
    t = tuple(new_shape)
    if v.shape == t:
        return v
    if v.ndim == 1 and len(t) == 1:
        return reshard_flat_padded(v, t[0], name=name)
    raise ValueError(
        f"cannot reshard leaf {name!r} from shape {v.shape} to {t} — "
        "only flat-padded 1-D leaves change shape across world sizes")


def reshard_flat_tree(old_tree: Any, template_tree: Any) -> Any:
    """Re-slice every flat-padded leaf of ``old_tree`` into the shapes of
    ``template_tree`` (the new-world layout) via `reshard_flat_leaf`.
    Values are host numpy — the caller places them on the new mesh.
    (The elastic restore uses the leaf-at-a-time placing variant,
    `resilience.elastic._reshard_and_place`, to keep host memory bounded
    by one leaf; both share `reshard_flat_leaf`.)"""
    return jax.tree_util.tree_map_with_path(
        lambda path, old, tmpl: reshard_flat_leaf(
            old, np.shape(tmpl), name=_path_str(path)),
        old_tree, template_tree)


def batch_spec(ndim: int = 1) -> P:
    """Leading dim sharded over the batch axes (data, fsdp); rest replicated.

    This single annotation replaces DistributedSampler + DDP: the global batch
    is one array split over the mesh (ref :122-127 does this with per-rank
    index slicing; here it is a layout fact XLA reasons about). Scalars
    (ndim=0) have no batch dimension and are replicated.
    """
    if ndim == 0:
        return P()
    return P(BATCH_AXES, *([None] * (ndim - 1)))


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(ndim))


def shard_batch(batch: Any, mesh: Mesh) -> Any:
    """Make each process-local batch shard into one global device array.

    Single-host: a plain device_put with the batch sharding. Multi-host: each
    process contributes its local slice (the generalization of the reference's
    per-rank DistributedSampler shard, train_ddp.py:122-127) via
    `make_array_from_process_local_data`.
    """
    def _one(x):
        x = np.asarray(x)
        sharding = batch_sharding(mesh, x.ndim)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(_one, batch)

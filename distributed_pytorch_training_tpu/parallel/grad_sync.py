"""Gradient synchronization as an explicit, configurable, profiled stage —
the TPU-native rebuild of DDP's C++ reducer (bucketed, backward-overlapped,
optionally compressed all-reduce; /root/reference/train_ddp.py:305-310 wraps
it, README.md:35 promises to profile it).

The repo's default data-parallel path leaves gradient sync to XLA: the batch
is sharded over the mesh, the loss mean contracts over the global batch, and
the compiler inserts one all-reduce per gradient leaf wherever its scheduler
likes. That is correct but opaque — O(leaves) small collectives, no knob for
wire precision, nothing to profile against. This module makes the reducer
explicit, with the three levers DDP exposes (and two it doesn't):

* **Bucketing** (`BucketPlan`): gradients are flattened into ONE fp32 vector
  (leaf order = `jax.tree_util.tree_leaves` order, the documented
  reassociation order) and cut into contiguous size-capped buckets — the
  `bucket_cap_mb` analog. The compiled step then carries
  ``ceil(total_grad_bytes / cap)`` large collectives instead of one per
  leaf. Unlike DDP, bucket boundaries may split a leaf: the plan chunks the
  concatenated vector, so the bucket count meets the ceil bound exactly
  (DDP's greedy per-tensor packing can only promise 2x it).
* **Wire compression** (`reduce_flat`, `compressed_psum_scatter`): the
  collective operand dtype is a choice, not a given. ``bf16`` halves wire
  bytes (sum accumulates in bf16 on TPU — bounded error, no state);
  ``int8`` uses per-bucket max-abs scales plus **error feedback**
  (Karimireddy et al.; the DynamiQ lever, PAPERS.md): the quantization
  residual is carried to the next reduction so the bias telescopes instead
  of accumulating. Master accumulation is always fp32 — compression
  touches only the wire. Honest accounting for the int8 BUCKETED form
  (gather-based, see below): per-replica ring traffic is ~(n-1)·S bytes
  vs ~8·S for an uncompressed fp32 all-reduce, so the byte saving is real
  only for small DP degrees (break-even near n=9); the zero1 int8 scatter
  (s8 all-to-all, ~1 B/element regardless of n) does not have this
  scaling. ``int8_multihop`` is the n-independent fix for the bucketed
  path (DynamiQ's multi-hop scheme, arxiv 2602.08923): each bucket is
  padded to the shard count, quantized PER DESTINATION CHUNK (one scale
  per chunk, so each receiver dequantizes exactly the chunks it sums),
  reduce-scattered as s8 over an all-to-all (hop 1, error feedback on
  this first quantization), dequant-summed locally in fp32, then the
  partial sum is REQUANTIZED and all-gathered as s8 (hop 2) — exactly
  two gradient-sized collectives per bucket and ~2 wire bytes/element
  regardless of n (`wire_bytes_per_replica` is the accounting). Hop 2
  is a broadcast of identical data, so its quantization error is the
  SAME perturbation on every replica — a bounded per-step bias (no
  divergence), not covered by EF (the hop-1 residual is). On zero1,
  ``int8_multihop`` means the FULLY compressed wire: the scatter half is
  the s8 all-to-all of ``int8`` (error-fed-back), and the param
  all-gather compresses as s8 UPDATE codes + per-chunk fp32 scales
  (`quantized_delta_all_gather` — the hop-2 error model applied to the
  parameter delta).
* **Topology awareness** (``int8_hier``): the two-tier hierarchical wire
  for multi-slice fleets (ICI islands joined by DCN — the mesh's ``slice``
  axis). Per bucket: (1) an EXACT fp32 reduce-scatter inside the slice over
  the fast tier, (2) the DynamiQ multi-hop s8 codec (per-chunk scales +
  error feedback, `_int8_multihop_sum` reused verbatim) ACROSS slices on
  the 1/n_inner partial — the only tier that quantizes, and the only EF
  site — then (3) an exact intra-slice all-gather back. Slow-link traffic
  per slice is ~2 bytes/element regardless of the slice count
  (`hier_wire_bytes` is the accounting); intra-slice arithmetic is exact,
  so the error model is EXACTLY the flat multihop wire's, at slice
  granularity (PARITY.md "Exactness model: two-tier sync").
* **Overlap** is the caller's third lever: `training/loop.py` reduces
  microbatch *i*'s buckets INSIDE the grad-accum scan body, so the
  collective for step *i* has no data dependency on step *i+1*'s compute
  and XLA's latency-hiding scheduler can run them concurrently — exposed
  comm time becomes hidden time (measured by
  `telemetry.trace_analysis.comm_overlap_split`).

Everything here is shard_map-body code: collectives take bound mesh axis
names, never a Mesh. The int8 wire uses all-gather / all-to-all (each
replica's quantized contribution travels with its own scale and is summed
AFTER dequantization) because a SUM all-reduce of int8 operands would
overflow at 2 replicas — the gather form is what keeps s8 on the wire.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

WIRE_DTYPES = ("fp32", "bf16", "int8", "int8_multihop", "int8_hier")

# Wire modes whose codec carries an error-feedback residual (built by
# Trainer.init_state into TrainState.grad_sync).
EF_WIRE_DTYPES = ("int8", "int8_multihop", "int8_hier")

# Quantization grid half-width: int8 values in [-127, 127] (symmetric; -128
# unused so the grid is zero-centered and dequantization is a pure scale).
_QMAX = 127.0


# ---------------------------------------------------------------------------
# Hierarchy spec (the int8_hier wire's static topology)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HierSpec:
    """Static two-tier topology of the ``int8_hier`` wire.

    ``slice_axis`` is the slow (DCN) mesh axis, ``fast_axes`` the intra-
    slice (ICI) batch axes the exact tier reduces over; ``n_slices`` /
    ``n_inner`` are their sizes (world = n_slices * n_inner). Chunk
    ownership under the two-stage scatter is FAST-MAJOR: the fast-tier
    reduce-scatter hands fast-rank j contiguous chunk j, the slow-tier
    all-to-all then hands slice s sub-chunk s of it — so replica (s, j)
    owns global chunk ``j * n_slices + s``, which is exactly
    ``lax.axis_index(fast_axes + (slice_axis,))``. Every hier gather
    therefore runs slice-axis FIRST, then fast axes, to reassemble chunks
    in order (``hier_axes`` is the index/PartitionSpec order)."""

    slice_axis: str
    fast_axes: Tuple[str, ...]
    n_slices: int
    n_inner: int

    def __post_init__(self):
        if self.n_slices < 2:
            raise ValueError(
                f"HierSpec needs >= 2 slices (got {self.n_slices}); a "
                "1-slice mesh has no slow tier — the trainer resolves "
                "int8_hier to the flat fp32 path there")
        if self.n_inner < 1:
            raise ValueError(f"n_inner must be >= 1, got {self.n_inner}")

    @property
    def world(self) -> int:
        return self.n_slices * self.n_inner

    @property
    def hier_axes(self) -> Tuple[str, ...]:
        """Fast-major ownership order (axis_index / PartitionSpec order)."""
        return tuple(self.fast_axes) + (self.slice_axis,)


# ---------------------------------------------------------------------------
# Bucket plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static layout of the flattened gradient vector.

    ``bounds`` are cumulative element offsets cutting the concatenated fp32
    gradient vector into buckets: bucket k is ``flat[bounds[k]:bounds[k+1]]``.
    Built from parameter SHAPES only, so it is identical at trace time and
    across processes (no data-dependent layout).
    """

    total_size: int           # elements in the concatenated gradient vector
    bounds: Tuple[int, ...]   # len == n_buckets + 1; bounds[0] == 0

    @property
    def n_buckets(self) -> int:
        return len(self.bounds) - 1

    @property
    def total_bytes(self) -> int:
        """fp32 master bytes of one full gradient (the bucket-cap currency)."""
        return self.total_size * 4

    def bucket_sizes(self) -> Tuple[int, ...]:
        return tuple(b - a for a, b in zip(self.bounds, self.bounds[1:]))


def build_bucket_plan(params: Any, bucket_cap_mb: float) -> BucketPlan:
    """Cut the flattened gradient of ``params`` into size-capped buckets.

    ``bucket_cap_mb`` caps each bucket at that many MB of fp32 elements
    (DDP's ``bucket_cap_mb``, default 25 there). ``<= 0`` means one bucket —
    a single fused collective, the fully-flat extreme. The bucket count is
    exactly ``ceil(total_fp32_bytes / cap_bytes)``: boundaries cut the
    concatenated vector, not the leaf list, so no greedy-packing slack.
    """
    total = int(sum(np.prod(np.shape(leaf)) or 1
                    for leaf in jax.tree_util.tree_leaves(params)))
    if total == 0:
        return BucketPlan(total_size=0, bounds=(0,) * 2)
    cap_elems = int(bucket_cap_mb * (1024 ** 2) // 4)
    if bucket_cap_mb <= 0 or cap_elems >= total:
        return BucketPlan(total_size=total, bounds=(0, total))
    cap_elems = max(1, cap_elems)
    bounds = tuple(range(0, total, cap_elems)) + (total,)
    plan = BucketPlan(total_size=total, bounds=bounds)
    assert plan.n_buckets == math.ceil(total / cap_elems)
    return plan


def padded_bucket_bounds(plan: BucketPlan, n_shards: int) -> Tuple[int, ...]:
    """Cumulative offsets of the multihop wire layout: each bucket padded up
    to a multiple of ``n_shards`` (the all-to-all needs equal destination
    chunks). This is the layout of the hop-1 error-feedback residual — one
    padded slot per bucket element INCLUDING the pad tail, so the residual
    slices align with the codec's padded view of each bucket."""
    bounds = [0]
    for size in plan.bucket_sizes():
        bounds.append(bounds[-1] + -(-size // n_shards) * n_shards)
    return tuple(bounds)


def padded_total_size(plan: BucketPlan, n_shards: int) -> int:
    """Total elements of the multihop (padded-to-n) flat layout — the hop-1
    residual length `ef_state_bucketed` allocates per replica."""
    return padded_bucket_bounds(plan, n_shards)[-1]


def wire_bytes_per_replica(plan: BucketPlan, wire_dtype: str,
                           n_shards: int, n_slices: int = 1) -> int:
    """Per-replica wire bytes of ONE full gradient sync under `wire_dtype` —
    the accounting behind the mode table (README) as a measured/recorded
    number in scaling rows, not a docstring claim.

    Conventions (payload only — the fp32 scale sideband, O(n) bytes per
    bucket, is excluded as noise):

    * ``fp32``/``bf16`` ride a ring all-reduce: ~2 hops x dtype bytes x S
      (the large-n ring volume 2·(n-1)/n·S rounds up to 2·S) — 8·S and 4·S.
    * ``int8`` (gather form): every replica RECEIVES each peer's full-size
      s8 codes — (n-1)·S bytes, growing with the DP degree (break-even vs
      fp32 near n=9).
    * ``int8_multihop``: hop 1 all-to-all moves ~S_padded s8 bytes, hop 2
      all-gather moves ~S_padded s8 bytes — 2·S_padded, independent of n
      (padding adds < n elements per bucket).
    * ``int8_hier`` (pass ``n_slices``): the fast tier is a flat fp32
      half+half all-reduce inside the slice — 8·S, exactly the flat fp32
      formula at the per-slice degree — plus the multihop wire on the
      1/n_inner partial across slices: 2·S_padded/n_inner slow-tier bytes
      per replica, i.e. ~2·S DCN bytes PER SLICE independent of the slice
      count (`hier_wire_bytes` returns the split).
    """
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(f"unknown wire dtype {wire_dtype!r} "
                         f"(choose from {WIRE_DTYPES})")
    if n_shards <= 1:
        return 0  # passthrough: nothing rides the wire
    s = plan.total_size
    if wire_dtype == "int8_hier":
        split = hier_wire_bytes(plan, n_shards, n_slices)
        return split["ici"] + split["dcn"]
    if wire_dtype == "fp32":
        return 8 * s
    if wire_dtype == "bf16":
        return 4 * s
    if wire_dtype == "int8":
        return (n_shards - 1) * s
    return 2 * padded_total_size(plan, n_shards)


def hier_wire_bytes(plan: BucketPlan, n_shards: int,
                    n_slices: int) -> dict:
    """Per-replica bytes of one ``int8_hier`` sync, split by tier:
    ``{"ici": fast-tier bytes, "dcn": slow-tier bytes}``.

    Fast tier: exact fp32 reduce-scatter + all-gather inside the slice —
    together one ring all-reduce's volume, 8·S (identical to the flat fp32
    formula at the per-slice degree; per-bucket padding, < world elements,
    is excluded like every formula here excludes sideband noise). Slow
    tier: the multihop codec on this replica's 1/n_inner partial —
    2·S_padded/n_inner s8 bytes. Summed over a slice's n_inner replicas
    that is 2·S_padded DCN bytes per slice, INDEPENDENT of the slice count
    — the whole point of the hierarchy, and the property tests pin it.

    Raises loudly on infeasible factorizations (world not divisible by
    the slice count) — the same guard the trainer applies."""
    if n_slices < 1:
        raise ValueError(f"n_slices must be >= 1, got {n_slices}")
    if n_shards % n_slices:
        raise ValueError(
            f"int8_hier: {n_shards} batch shards do not factor into "
            f"{n_slices} slices (world % slices != 0)")
    s = plan.total_size
    if n_shards <= 1:
        return {"ici": 0, "dcn": 0}
    if n_slices == 1:
        # slices=1 passthrough: the trainer resolves to the flat fp32 path.
        return {"ici": 8 * s, "dcn": 0}
    n_inner = n_shards // n_slices
    return {"ici": 8 * s if n_inner > 1 else 0,
            "dcn": 2 * padded_total_size(plan, n_shards) // n_inner}


def _flat_padded_total(params: Any, n_shards: int) -> int:
    """Sum of every leaf's flat-padded size — the element count that rides
    the explicit-FSDP wire (gathers and scatters both operate on the
    padded-to-n per-leaf layout)."""
    from .sharding import flat_padded_size

    return int(sum(
        flat_padded_size(int(np.prod(np.shape(leaf)) or 1), n_shards)
        for leaf in jax.tree_util.tree_leaves(params)))


def fsdp_gather_bytes(params: Any, wire_dtype: str, n_shards: int,
                      n_slices: int = 1) -> int:
    """Per-replica wire bytes of ONE full per-layer parameter gather pass
    under explicit FSDP (`fsdp_explicit`) — the gather-traffic term
    `wire_bytes_for_config` adds for that mode, recorded in scaling
    rows (satellite of ISSUE 7).

    Conventions (payload only, scale sidebands excluded as noise): the
    fp32/bf16/int8 wires gather parameters EXACTLY (fp32 on the wire,
    mirroring zero1's exact param gather) — ~4 bytes x padded elements per
    replica. ``int8_multihop`` gathers s8 codes + per-chunk fp32 scales
    (`quantized_shard_all_gather`) — ~1 byte/element, independent of the
    shard count (the delta-gather n-independence argument, applied to the
    absolute shard values). ``int8_hier`` gathers s8 across slices first
    (~total/n_inner slow bytes per replica) then exact fp32 inside the
    slice (~4·total fast bytes) — the slow-tier term is what the mode
    exists to shrink."""
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(f"unknown wire dtype {wire_dtype!r} "
                         f"(choose from {WIRE_DTYPES})")
    if n_shards <= 1:
        return 0  # passthrough: nothing rides the wire
    total = _flat_padded_total(params, n_shards)
    if wire_dtype == "int8_hier":
        if n_slices <= 1:
            return 4 * total  # passthrough: the flat exact fp32 gather
        n_inner = n_shards // n_slices
        return (4 * total if n_inner > 1 else 0) + total // n_inner
    return total if wire_dtype == "int8_multihop" else 4 * total


def tp_psum_bytes_per_step(hidden: int, depth: int, local_batch: int,
                           seq: int, model_n: int, tp_vocab: bool = False,
                           padded_vocab: int = 0) -> int:
    """Per-replica MODEL-axis wire bytes of ONE explicit-TP train step
    (ISSUE 13) — the TP term `wire_bytes_for_config` grows and
    `emit_wire_accounting` tags with its own tier row.

    Conventions (payload only, matching `wire_bytes_per_replica`): each
    megatron psum is an fp32 ring all-reduce of one (local_batch, seq,
    hidden) activation — ~8 bytes/element; the step carries 4 per block
    (forward g + backward f mirrors) plus 2 with the vocab-parallel
    embedding (`Trainer.tp_expected_model_collectives` is the same
    arithmetic read off the trainer). The vocab-parallel head adds the
    parallel-vocab cross-entropy's two (local_batch, seq, 2)-sized stat
    all-reduces (~32 bytes x local_batch x seq total) — the vocab-scale
    logits gather it replaced cost ~4 bytes x (local_batch, seq,
    padded_vocab), i.e. the head's wire shrank by ~padded_vocab/8 per
    token (collectives.tp_parallel_cross_entropy). ``padded_vocab`` is
    kept in the signature for callers recording the replaced-gather
    delta."""
    del padded_vocab  # the gather this sized is gone; see docstring
    if model_n <= 1:
        return 0
    act = local_batch * seq * hidden
    n_psums = 4 * depth + (2 if tp_vocab else 0)
    total = 8 * act * n_psums
    if tp_vocab:
        total += 32 * local_batch * seq
    return total


def wire_bytes_for_config(params: Any, grad_sync_cfg: Optional[dict],
                          n_shards: int) -> int:
    """`wire_bytes_per_replica` from a TrainConfig-style override dict
    (``bucket_cap_mb`` / ``wire_dtype`` / ``fsdp_explicit``, with the
    TrainConfig defaults) — the ONE accounting call that
    `emit_wire_accounting` (train.py's stream) and scaling (`run_grad_sync`
    / `run_fsdp` / `run_tp`) record, so their rows cannot drift apart.

    For ``fsdp_explicit`` configs the number is scatter + gather: the
    gradient reduce-scatter at the wire dtype (4/2/1/1 bytes per padded
    element for fp32/bf16/int8/int8_multihop — a reduce-scatter is half an
    all-reduce) plus the `fsdp_gather_bytes` per-layer gather term. Only
    ``int8_multihop`` compresses both directions (~2 B/element total,
    independent of n — asserted by tests, like the multihop gradient
    wire's).

    Explicit TP x FSDP: pass the TP-LOCAL parameter template as
    ``params`` (the trainer's `_fsdp_local_template` — gathers/scatters
    move each model shard's local slice only, the 1/M reduction) and the
    model-axis activation term via ``cfg["tp_psum_bytes"]``
    (`tp_psum_bytes_per_step`); the result is the TOTAL data-axis +
    model-axis per-replica bytes.

    ``int8_hier`` configs carry ``cfg["slices"]`` (the slice-axis size);
    `wire_bytes_split_for_config` returns the same number split by tier."""
    split = wire_bytes_split_for_config(params, grad_sync_cfg, n_shards)
    return split["ici"] + split["dcn"]


def wire_bytes_split_for_config(params: Any, grad_sync_cfg: Optional[dict],
                                n_shards: int) -> dict:
    """`wire_bytes_for_config`, split by interconnect tier:
    ``{"ici": fast-tier bytes, "dcn": slow-tier bytes}``. Every flat wire
    mode is all-ICI (dcn = 0); ``int8_hier`` puts the cross-slice s8
    traffic in "dcn" (the `hier_wire_bytes` split, extended with the
    fsdp gather/scatter terms). Raises loudly when ``cfg["slices"]`` does
    not divide the batch-shard world."""
    cfg = dict(grad_sync_cfg or {})
    wire = cfg.get("wire_dtype", "fp32")
    if wire not in WIRE_DTYPES:
        raise ValueError(f"unknown wire dtype {wire!r} "
                         f"(choose from {WIRE_DTYPES})")
    n_slices = int(cfg.get("slices", 1))
    if n_slices >= 1 and n_shards > 1 and n_shards % n_slices:
        raise ValueError(
            f"int8_hier: {n_shards} batch shards do not factor into "
            f"{n_slices} slices (world % slices != 0)")
    tp_bytes = int(cfg.get("tp_psum_bytes", 0))
    hier = wire == "int8_hier" and n_slices > 1 and n_shards > 1
    if cfg.get("fsdp_explicit"):
        if n_shards <= 1:
            return {"ici": tp_bytes, "dcn": 0}
        total = _flat_padded_total(params, n_shards)
        if hier:
            n_inner = n_shards // n_slices
            # scatter: fast fp32 reduce-scatter (4 B/elem) + slow s8
            # all-to-all on the 1/n_inner partial; gather: the mirror
            # (fsdp_gather_bytes) — slow-tier total 2·total/n_inner.
            fast = 8 * total if n_inner > 1 else 0
            return {"ici": fast + tp_bytes,
                    "dcn": 2 * (total // n_inner)}
        scatter = {"fp32": 4, "bf16": 2, "int8": 1, "int8_multihop": 1,
                   "int8_hier": 4}[wire] * total
        return {"ici": scatter + fsdp_gather_bytes(params, wire, n_shards)
                + tp_bytes, "dcn": 0}
    plan = build_bucket_plan(params, float(cfg.get("bucket_cap_mb", 0.0)))
    if hier:
        split = hier_wire_bytes(plan, n_shards, n_slices)
        return {"ici": split["ici"], "dcn": split["dcn"]}
    return {"ici": wire_bytes_per_replica(plan, wire, n_shards), "dcn": 0}


def emit_wire_accounting(params: Any, grad_sync_cfg: Optional[dict],
                         n_shards: int, tier: str = "ici",
                         **attrs: Any) -> dict:
    """Record the configured sync mode's per-replica wire accounting as
    telemetry counters (host-side, setup-time — called once by train.py,
    NEVER from traced code) and return the numbers — THE one emission
    site.

    ``tier`` names the interconnect the bytes ride — "ici" is the only
    tier today; the ROADMAP's two-tier (ICI + DCN) hierarchical sync will
    emit one counter set per tier through this same call, which is why
    the attribute exists now (per-tier byte/time telemetry is the
    substrate that item presumes). Extra ``attrs`` (e.g. a
    ``model=...``) ride every emitted counter.

    Explicit TP x FSDP (``cfg["model_shards"]`` > 1 with
    ``cfg["tp_psum_bytes"]``): the model-axis activation bytes land in
    their OWN counter row (``tp_psum_bytes_per_replica``, axis="model")
    so ``telemetry summary`` splits TP psum traffic from the data-axis
    gradient sync, and ``wire_bytes_per_replica`` stays the data-axis
    number (tagged axis="data"). With no model axis the emission is
    byte-identical to before.

    ``int8_hier`` configs (``cfg["slices"]`` > 1): TWO
    ``wire_bytes_per_replica`` rows, one per interconnect tier —
    (tier="ici", axis="data") for the exact intra-slice half and
    (tier="dcn", axis="slice") for the compressed cross-slice half. The
    rows flow through `telemetry aggregate` and /metrics with zero schema
    change — (name, tier, axis) was already the rollup key."""
    from .. import telemetry

    cfg = dict(grad_sync_cfg or {})
    wire = cfg.get("wire_dtype", "fp32")
    model_shards = int(cfg.get("model_shards", 1))
    n_slices = int(cfg.get("slices", 1))
    tp_bytes = int(cfg.get("tp_psum_bytes", 0)) if model_shards > 1 else 0
    data_cfg = {k: v for k, v in cfg.items() if k != "tp_psum_bytes"}
    hier = (wire == "int8_hier" and n_slices > 1 and n_shards > 1)
    split = wire_bytes_split_for_config(params, data_cfg, n_shards)
    out = {"tier": tier, "wire_dtype": wire, "n_shards": n_shards,
           "wire_bytes_per_replica": split["ici"] + split["dcn"]}
    axis_attr = {"axis": "data"} if model_shards > 1 else {}
    if hier:
        out["wire_bytes_ici"] = split["ici"]
        out["wire_bytes_dcn"] = split["dcn"]
        out["n_slices"] = n_slices
        telemetry.counter("wire_bytes_per_replica", split["ici"],
                          tier="ici", axis="data", wire_dtype=wire,
                          n_shards=n_shards, n_slices=n_slices, **attrs)
        telemetry.counter("wire_bytes_per_replica", split["dcn"],
                          tier="dcn", axis="slice", wire_dtype=wire,
                          n_shards=n_shards, n_slices=n_slices, **attrs)
    else:
        telemetry.counter("wire_bytes_per_replica",
                          out["wire_bytes_per_replica"], tier=tier,
                          wire_dtype=wire, n_shards=n_shards, **axis_attr,
                          **attrs)
    if cfg.get("fsdp_explicit"):
        out["fsdp_gather_bytes"] = fsdp_gather_bytes(params, wire, n_shards,
                                                     n_slices)
        telemetry.counter("fsdp_gather_bytes", out["fsdp_gather_bytes"],
                          tier=tier, wire_dtype=wire, n_shards=n_shards,
                          **axis_attr, **attrs)
    if tp_bytes:
        out["tp_psum_bytes_per_replica"] = tp_bytes
        telemetry.counter("tp_psum_bytes_per_replica", tp_bytes, tier=tier,
                          axis="model", model_shards=model_shards, **attrs)
    return out


# ---------------------------------------------------------------------------
# Layer plan (explicit FSDP): the per-layer cut of the parameter tree
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """One per-layer gather/scatter unit of the explicit-FSDP wire layout.

    ``leaf_slots`` index into the params tree's ``tree_leaves`` order;
    ``chunk_sizes[i]`` is leaf ``leaf_slots[i]``'s per-replica chunk
    (flat-padded size / n_shards). The group's WIRE LAYOUT is
    destination-major: row j = the concatenation of every member leaf's
    chunk j — so ONE tiled all-gather of this replica's row rebuilds every
    member leaf's flat-padded vector, and ONE reduce-scatter of the
    row-stacked gradient lands each leaf's chunk back on its owner.
    """

    name: str
    leaf_slots: Tuple[int, ...]
    chunk_sizes: Tuple[int, ...]

    @property
    def row_size(self) -> int:
        """Per-replica elements of this group (one gather/scatter row)."""
        return int(sum(self.chunk_sizes))


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Static per-layer layout of a parameter tree for explicit FSDP —
    the BucketPlan idea applied to the MODEL's structure instead of a byte
    cap: one group per top-level module (`wte`, `block0`, ..., `ln_f`), so
    the step carries one just-in-time param gather and one gradient
    reduce-scatter per layer. Built from SHAPES only (host-side, identical
    at trace time and across processes)."""

    groups: Tuple[LayerGroup, ...]
    n_shards: int

    @property
    def total_padded(self) -> int:
        return self.n_shards * sum(g.row_size for g in self.groups)

    @property
    def padded_group_sizes(self) -> Tuple[int, ...]:
        """Full padded elements per group (n_shards x row_size) — the ONE
        budget the analysis/ fsdp rules read (the contract evaluator
        snapshots this)."""
        return tuple(self.n_shards * g.row_size for g in self.groups)


def _top_level_key(path) -> str:
    if not path:
        return "params"
    p = path[0]
    for attr in ("key", "name", "idx"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def build_layer_plan(params: Any, n_shards: int) -> LayerPlan:
    """Group ``params`` into per-layer gather units by top-level key.

    Grouping by the first path component makes each transformer block (and
    each standalone module: embeddings, final layernorm) one gather — the
    per-layer granularity SimpleFSDP gathers at. Leaves keep their
    ``tree_leaves`` order inside a group, so slicing a gathered row back
    into leaves is pure static arithmetic."""
    from .sharding import flat_padded_size

    by_key: dict = {}
    order: List[str] = []
    leaves = jax.tree_util.tree_leaves_with_path(params)
    for slot, (path, leaf) in enumerate(leaves):
        key = _top_level_key(path)
        if key not in by_key:
            by_key[key] = []
            order.append(key)
        size = int(np.prod(np.shape(leaf)) or 1)
        by_key[key].append((slot, flat_padded_size(size, n_shards)
                            // n_shards))
    groups = tuple(
        LayerGroup(name=k,
                   leaf_slots=tuple(s for s, _ in by_key[k]),
                   chunk_sizes=tuple(c for _, c in by_key[k]))
        for k in order)
    return LayerPlan(groups=groups, n_shards=n_shards)


def flatten_tree(tree: Any) -> jnp.ndarray:
    """Concatenate every leaf (ravelled, cast fp32) in tree-leaves order —
    the master flat gradient the buckets slice. This fixed order IS the
    documented reassociation order of the bucketed reducer."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate(
        [jnp.ravel(leaf).astype(jnp.float32) for leaf in leaves])


def unflatten_tree(flat: jnp.ndarray, like: Any) -> Any:
    """Rebuild a pytree shaped like ``like`` from the flat vector, casting
    each leaf back to its template's dtype (fp32 master -> param dtype)."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out = []
    offset = 0
    for leaf in leaves:
        size = int(np.prod(np.shape(leaf)) or 1)
        out.append(
            lax.slice_in_dim(flat, offset, offset + size)
            .reshape(np.shape(leaf)).astype(jnp.result_type(leaf)))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Compressed collectives (shard_map-body code: axis names must be bound)
# ---------------------------------------------------------------------------


def _quantize_int8_rows(rows: jnp.ndarray, fused: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise symmetric quantization of a (n, chunk) matrix: one fp32
    max-abs scale PER ROW (= per destination chunk), int8 codes. The single
    quantization-grid definition every int8 wire shares.

    ``fused=None`` resolves via ``ops.quantize.resolve_fused`` (TPU-gated,
    ``DPT_FUSED_QUANTIZE`` override); True routes through the Pallas fused
    kernel — BIT-IDENTICAL by contract (PARITY.md), a scheduling change
    only. The scale is an explicit multiply by 1/127 (not a division):
    XLA's simplifier rewrites division-by-constant to exactly that inside
    compiled steps, so writing the multiply keeps this function
    bit-reproducible across eager/jit/kernel contexts instead of depending
    on whether the rewrite fired."""
    from ..ops.quantize import quantize_int8_rows_fused, resolve_fused

    if resolve_fused(fused):
        return quantize_int8_rows_fused(rows)
    scales = jnp.maximum(jnp.max(jnp.abs(rows), axis=1), 1e-30) \
        * (1.0 / _QMAX)
    q = jnp.clip(jnp.round(rows / scales[:, None]),
                 -_QMAX, _QMAX).astype(jnp.int8)
    return q, scales


def _quantize_int8(v: jnp.ndarray, fused: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(int8 codes, fp32 scale): symmetric per-bucket max-abs scaling —
    the one-row case of `_quantize_int8_rows`."""
    q, scales = _quantize_int8_rows(v[None], fused=fused)
    return q[0], scales[0]


def _dequant_sum_rows(q: jnp.ndarray, scales: jnp.ndarray,
                      fused: Optional[bool] = None) -> jnp.ndarray:
    """SUM of dequantized rows — (n, chunk) s8 x (n,) fp32 scales ->
    (chunk,) fp32: the receive-side accumulate every int8 wire shares
    (hop-1 partial sums, the zero1 s8 scatter, the gather-form sum).
    ``fused`` routes through the Pallas kernel (bit-identical contract,
    ops/quantize.py)."""
    from ..ops.quantize import dequant_sum_rows_fused, resolve_fused

    if resolve_fused(fused):
        return dequant_sum_rows_fused(q, scales)
    return jnp.sum(q.astype(jnp.float32) * scales[:, None], axis=0)


def _int8_gather_sum(q: jnp.ndarray, scale: jnp.ndarray,
                     axis_names: Sequence[str], n_shards: int,
                     fused: Optional[bool] = None) -> jnp.ndarray:
    """SUM-of-dequantized across replicas via an s8 all-gather.

    Each replica contributes (codes, scale); codes ride the wire as s8
    (the compression), scales as one fp32 scalar per replica (noise). The
    sum happens AFTER dequantization, locally and in the same axis order on
    every replica — so the result is exactly replicated, and no int8
    overflow is possible. Wire scaling caveat: an all-gather moves every
    replica's codes to every replica (~(n-1)·S bytes each), so the saving
    over a fp32 all-reduce (~8·S) erodes as n grows — see the module
    docstring.
    """
    gathered = lax.all_gather(q, axis_names, axis=0, tiled=True)
    scales = lax.all_gather(scale[None], axis_names, axis=0, tiled=True)
    return _dequant_sum_rows(gathered.reshape(n_shards, -1), scales,
                             fused=fused)


def _int8_multihop_sum(v: jnp.ndarray, residual: jnp.ndarray,
                       axis_names: Sequence[str], n_shards: int,
                       fused: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DynamiQ-style two-hop compressed SUM of one bucket: s8 all-to-all
    reduce-scatter, local fp32 dequant-sum, requantize, s8 all-gather.

    ``v``: this replica's (S,) fp32 bucket contribution. ``residual``: the
    (S_padded,) hop-1 error-feedback residual (S_padded = S rounded up to a
    multiple of ``n_shards``). Returns ``(fp32 (S,) global sum, new
    residual)``.

    Hop 1 quantizes PER DESTINATION CHUNK — one scale per (n_shards,)-row
    of the padded bucket — so replica j dequantizes each received chunk
    with exactly the scale its sender used for chunk j (a per-bucket scale
    would make the receiver's dequant depend on elements it never sees).
    The s8 all-to-all moves each chunk to its owner (~S_padded wire bytes);
    the scales ride a tiny fp32 all-to-all (n scalars, under any census
    floor). Error feedback covers THIS quantization: the residual is what
    this replica's codes dropped, re-injected at its next reduction, so the
    hop-1 bias telescopes across steps.

    Hop 2 requantizes the fp32 partial sum of the n received chunks (one
    scale for this replica's chunk) and all-gathers the codes
    (~S_padded wire bytes) + scales (n fp32 scalars). Every replica
    dequantizes the same (codes, scales), so the result is exactly
    replicated. Hop-2 error is NOT error-fed-back — the partial sum is
    owned by one replica but consumed by all, so a residual would have to
    ride the wire to help; instead the error is bounded (<= scale2/2 per
    element, scale2 = maxabs(partial)/127) and identical everywhere,
    a per-step perturbation like the bf16 wire's (PARITY.md documents it).

    Total: exactly TWO gradient-sized collectives per bucket and ~2 wire
    bytes/element regardless of n — the census bound
    `analysis.contracts.collectives_per_bucket("int8_multihop") == 2`.
    """
    names = tuple(axis_names)
    size = v.shape[0]
    padded = residual.shape[0]
    chunk = padded // n_shards
    carried = jnp.pad(v, (0, padded - size)) + residual
    rows = carried.reshape(n_shards, chunk)
    q, scales = _quantize_int8_rows(rows, fused=fused)
    new_residual = carried - (q.astype(jnp.float32)
                              * scales[:, None]).reshape(-1)
    # hop 1: replica j receives every peer's chunk j (+ the scale each
    # peer used for chunk j) — an s8 reduce-scatter, sum deferred to fp32
    recv_q = lax.all_to_all(q.reshape(-1), names, split_axis=0,
                            concat_axis=0, tiled=True)  # (padded,) s8
    recv_scales = lax.all_to_all(scales, names, split_axis=0,
                                 concat_axis=0, tiled=True)  # (n,) fp32
    partial = _dequant_sum_rows(recv_q.reshape(n_shards, chunk),
                                recv_scales, fused=fused)  # (chunk,) fp32
    # hop 2: requantize the partial sum, gather codes + scales, dequant
    out = _s8_all_gather_dequant(partial, names, fused=fused)
    return out[:size], new_residual


def _int8_hier_sum(v: jnp.ndarray, residual: jnp.ndarray,
                   spec: HierSpec, fused: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two-tier topology-aware SUM of one bucket (the ``int8_hier`` wire):
    exact fp32 reduce-scatter inside the slice, the multihop s8 codec
    across slices, exact all-gather back.

    ``v``: this replica's (S,) fp32 bucket contribution. ``residual``: the
    (S_padded / n_inner,) slow-tier error-feedback residual — S_padded is
    the bucket rounded up to a multiple of the WORLD (`padded_bucket_bounds`
    at world), so the fast-tier chunk S_padded/n_inner is itself divisible
    by n_slices and the reused multihop codec pads nothing further. Returns
    ``(fp32 (S,) global sum, new residual)``.

    Stage 1 — fast tier, EXACT: a tiled fp32 ``psum_scatter`` over the
    intra-slice batch axes. Fast-rank j now holds chunk j of the
    within-slice sum; no quantization, no residual — intra-slice
    arithmetic is bitwise the same reassociation class as the flat
    reducer's.

    Stage 2 — slow tier, COMPRESSED: `_int8_multihop_sum` over the slice
    axis on the 1/n_inner partial, verbatim — per-destination-chunk s8
    quantization with error feedback (the ONE EF site of the hier wire;
    the residual telescopes across steps exactly as in the flat multihop
    wire), s8 all-to-all + requantized s8 all-gather. Its output is
    replica-identical ACROSS slices at each fast rank, so stage 3's
    reassembly never mixes divergent values.

    Stage 3 — fast tier, EXACT: a tiled all-gather over the intra-slice
    axes rebuilds the full bucket (chunks are fast-indexed, so order is
    restored by construction).

    Four gradient-sized collectives per bucket — two exact f32 on ICI,
    two s8 on DCN (`analysis.contracts.collectives_per_bucket` == 4; the
    `hier-tier-signature` HLO rule pins dtype-per-tier). Slow-tier wire
    bytes: ~2·S per SLICE, independent of the slice count."""
    size = v.shape[0]
    padded = residual.shape[0] * spec.n_inner
    carried = jnp.pad(v, (0, padded - size))
    if spec.fast_axes:
        part = lax.psum_scatter(carried, spec.fast_axes,
                                scatter_dimension=0, tiled=True)
    else:  # pure cross-slice mesh (n_inner == 1): no fast tier
        part = carried
    summed, new_residual = _int8_multihop_sum(
        part, residual, (spec.slice_axis,), spec.n_slices, fused=fused)
    if spec.fast_axes:
        summed = lax.all_gather(summed, spec.fast_axes, axis=0, tiled=True)
    return summed[:size], new_residual


def _compressed_psum(v: jnp.ndarray, axis_names: Sequence[str],
                     n_shards: int, wire_dtype: str,
                     residual: Optional[jnp.ndarray],
                     fused: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """One bucket's SUM all-reduce at the chosen wire dtype.

    Returns ``(fp32 global sum, new residual)``; the residual is None unless
    ``wire_dtype == 'int8'`` (error feedback: what this replica's
    quantization dropped, to be re-injected at its next reduction).
    """
    names = tuple(axis_names)
    if wire_dtype == "fp32":
        return lax.psum(v, names), residual
    if wire_dtype == "bf16":
        # wire + accumulation in bf16 (that is the point: half the bytes);
        # the caller keeps the fp32 master copy
        return lax.psum(v.astype(jnp.bfloat16), names).astype(jnp.float32), \
            residual
    if wire_dtype == "int8_multihop":
        raise ValueError("int8_multihop buckets reduce via "
                         "_int8_multihop_sum (reduce_flat routes them — "
                         "the residual layout is padded-to-n, not flat)")
    if wire_dtype != "int8":
        raise ValueError(f"unknown wire dtype {wire_dtype!r} "
                         f"(choose from {WIRE_DTYPES})")
    if residual is None:
        raise ValueError("int8 wire needs an error-feedback residual "
                         "(Trainer.init_state builds it)")
    carried = v + residual
    q, scale = _quantize_int8(carried, fused=fused)
    new_residual = carried - q.astype(jnp.float32) * scale
    return _int8_gather_sum(q, scale, names, n_shards, fused=fused), \
        new_residual


def reduce_flat(flat: jnp.ndarray, plan: BucketPlan,
                axis_names: Sequence[str], n_shards: int, wire_dtype: str,
                residual: Optional[jnp.ndarray] = None,
                fused: Optional[bool] = None,
                hier: Optional[HierSpec] = None
                ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Reduce the flat local gradient vector bucket-by-bucket.

    ``flat``: this replica's (total_size,) fp32 contribution (weight-scaled
    gradient sums). Returns the globally-summed fp32 vector and the updated
    error-feedback residual (int8 wires only; same shape for ``int8``, the
    `padded_bucket_bounds` layout for ``int8_multihop``, that layout's
    1/n_inner slow-tier view for ``int8_hier`` — which also requires the
    ``hier`` spec). One collective per bucket (TWO for the multi-hop wire,
    FOUR for the hierarchical wire: 2 exact f32 on ICI + 2 s8 on DCN) —
    the O(buckets) contract `grad_sync_census` verifies in HLO.
    """
    multihop = wire_dtype == "int8_multihop"
    if wire_dtype == "int8_hier":
        if hier is None:
            raise ValueError("int8_hier wire needs a HierSpec (the trainer "
                             "builds it from the mesh's slice axis)")
        if residual is None:
            raise ValueError("int8_hier wire needs a slow-tier error-"
                             "feedback residual (Trainer.init_state "
                             "builds it)")
    elif multihop and residual is None:
        raise ValueError("int8_multihop wire needs a hop-1 error-feedback "
                         "residual (Trainer.init_state builds it)")
    pbounds = (padded_bucket_bounds(plan, n_shards)
               if (multihop or wire_dtype == "int8_hier") else None)
    outs: List[jnp.ndarray] = []
    res_outs: List[jnp.ndarray] = []
    for k, (a, b) in enumerate(zip(plan.bounds, plan.bounds[1:])):
        v = lax.slice_in_dim(flat, a, b)
        if wire_dtype == "int8_hier":
            r = lax.slice_in_dim(residual, pbounds[k] // hier.n_inner,
                                 pbounds[k + 1] // hier.n_inner)
            summed, new_r = _int8_hier_sum(v, r, hier, fused=fused)
        elif multihop:
            r = lax.slice_in_dim(residual, pbounds[k], pbounds[k + 1])
            summed, new_r = _int8_multihop_sum(v, r, axis_names, n_shards,
                                               fused=fused)
        else:
            r = (lax.slice_in_dim(residual, a, b)
                 if residual is not None else None)
            summed, new_r = _compressed_psum(v, axis_names, n_shards,
                                             wire_dtype, r, fused=fused)
        outs.append(summed)
        if new_r is not None:
            res_outs.append(new_r)
    synced = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    new_residual = (jnp.concatenate(res_outs) if len(res_outs) > 1
                    else res_outs[0]) if res_outs else None
    return synced, new_residual


def _s8_all_gather_dequant(chunk: jnp.ndarray, names: Tuple[str, ...],
                           fused: Optional[bool] = None) -> jnp.ndarray:
    """The shared s8 gather wire: quantize this replica's (chunk,) fp32
    vector with ONE max-abs scale, all-gather codes (s8 on the wire) +
    scales (n fp32 scalars, noise), dequantize identically everywhere.
    Returns the full (n x chunk,) fp32 reconstruction — exactly
    replica-identical because every replica dequantizes the same
    (codes, scales). One convention, three wires: multihop's hop 2,
    zero1's delta gather, and the explicit-FSDP shard gather."""
    q, scale = _quantize_int8(chunk, fused=fused)
    gathered = lax.all_gather(q, names, axis=0, tiled=True)
    scales = lax.all_gather(scale[None], names, axis=0, tiled=True)
    n = scales.shape[0]
    return (gathered.reshape(n, -1).astype(jnp.float32)
            * scales[:, None]).reshape(-1)


def quantized_delta_all_gather(new_shard: jnp.ndarray,
                               old_shard: jnp.ndarray,
                               old_flat: jnp.ndarray,
                               axis_names: Sequence[str],
                               fused: Optional[bool] = None) -> jnp.ndarray:
    """Compressed zero1 PARAM all-gather (the `int8_multihop` composition):
    gather s8 codes of each replica's UPDATE, not fp32 new params.

    ``new_shard``/``old_shard``: this replica's (padded/n,) fp32 chunk of
    one leaf's flat-padded parameters, after/before the optimizer update.
    ``old_flat``: the full (padded,) flat-padded OLD parameters — replicated
    in zero1 (the layout the mode shards is the update, not the model), so
    every replica already holds them exactly. Each replica quantizes its
    chunk's delta with one fp32 max-abs scale (the per-destination-chunk
    rule of the multihop gradient wire, reused: the scale travels with the
    codes it scales), all-gathers codes (s8 on the wire, ~1 B per fp32
    param byte saved x4) + scales (n fp32 scalars, noise), and adds the
    dequantized full delta to ``old_flat``.

    Error model (the hop-2 story, verbatim): every replica dequantizes the
    SAME (codes, scales), so the reconstructed parameters are exactly
    replicated — quantization perturbs the trajectory by a bounded,
    replica-identical amount per step (<= scale/2 per element, scale =
    maxabs(update)/127 per chunk; the UPDATE is lr-sized, so the absolute
    param error is ~lr * grad-scale / 254 per step). NOT error-fed-back:
    the delta is owned by one replica but consumed by all, so a residual
    would have to ride the wire to help; tests pin the 20-step fp32-parity
    instead (tests/test_grad_sync.py).
    """
    names = tuple(axis_names)
    full_delta = _s8_all_gather_dequant(new_shard - old_shard, names,
                                        fused=fused)
    return old_flat + full_delta


def quantized_shard_all_gather(shard: jnp.ndarray,
                               axis_names: Sequence[str],
                               fused: Optional[bool] = None) -> jnp.ndarray:
    """Compressed explicit-FSDP PARAM all-gather: s8 codes of each
    replica's shard (absolute values, one fp32 max-abs scale per chunk —
    the per-destination-chunk rule again), gathered and dequantized
    identically everywhere.

    ``shard``: this replica's (chunk,) fp32 row of one layer group's
    flat-padded parameters (at rest — explicit FSDP never holds a
    replicated copy, so unlike zero1's `quantized_delta_all_gather` there
    is no old_flat base to delta against; the codes carry the values
    themselves). Returns the full (n x chunk,) fp32 reconstruction.

    Error model (the hop-2 story applied to parameter VALUES, stated
    honestly): every replica dequantizes the SAME (codes, scales), so the
    gathered working parameters are exactly replica-identical; the at-rest
    shards stay exact fp32 (only the per-step gathered copy is perturbed,
    by <= scale/2 per element with scale = maxabs(chunk)/127 — coarser
    than the delta gather's lr-sized error because it scales with the
    PARAMETER magnitude, not the update). NOT error-fed-back (the same
    one-owner/all-consumers argument); pinned by convergence tests, not
    fp32 parity (tests/test_fsdp_explicit.py)."""
    return _s8_all_gather_dequant(shard, tuple(axis_names), fused=fused)


def compressed_psum_scatter(v: jnp.ndarray, axis_names: Sequence[str],
                            n_shards: int, wire_dtype: str,
                            residual: Optional[jnp.ndarray] = None,
                            fused: Optional[bool] = None
                            ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Reduce-scatter one flat-padded leaf at the chosen wire dtype — the
    compressed half-all-reduce of the ZeRO-1 update (training/loop.py).

    ``v``: (padded,) local fp32, padded size divisible by ``n_shards``.
    Returns this replica's (padded/n,) fp32 chunk of the cross-replica sum
    plus the updated error-feedback residual (int8 only, full padded size —
    EF must remember what was dropped from EVERY chunk, not just the kept
    one). int8 rides an s8 all-to-all: replica j receives every peer's
    chunk j (2 wire bytes per 8 fp32 bytes, scatter-half included), then
    dequantizes with the peers' gathered scales and sums in fp32.
    """
    names = tuple(axis_names)
    if wire_dtype == "fp32":
        return lax.psum_scatter(v, names, scatter_dimension=0, tiled=True), \
            residual
    if wire_dtype == "bf16":
        return lax.psum_scatter(v.astype(jnp.bfloat16), names,
                                scatter_dimension=0,
                                tiled=True).astype(jnp.float32), residual
    if wire_dtype == "int8_multihop":
        raise ValueError(
            "the zero1 scatter half is ALREADY the n-independent s8 "
            "all-to-all: the zero1 step maps wire_dtype='int8_multihop' "
            "to the 'int8' scatter codec before calling here (what "
            "multihop adds on zero1 is the compressed param gather — "
            "quantized_delta_all_gather)")
    if wire_dtype != "int8":
        raise ValueError(f"unknown wire dtype {wire_dtype!r} "
                         f"(choose from {WIRE_DTYPES})")
    if residual is None:
        raise ValueError("int8 wire needs an error-feedback residual "
                         "(Trainer.init_state builds it)")
    carried = v + residual
    q, scale = _quantize_int8(carried, fused=fused)
    new_residual = carried - q.astype(jnp.float32) * scale
    received = lax.all_to_all(q, names, split_axis=0, concat_axis=0,
                              tiled=True)  # (padded,) s8: peers' chunk j
    scales = lax.all_gather(scale[None], names, axis=0, tiled=True)
    return _dequant_sum_rows(received.reshape(n_shards, -1), scales,
                             fused=fused), new_residual


def hier_psum_scatter(v: jnp.ndarray, spec: HierSpec,
                      residual: Optional[jnp.ndarray],
                      fused: Optional[bool] = None
                      ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Two-tier reduce-scatter of one flat-padded leaf (zero1) or layer-
    group row stack (explicit FSDP) under the ``int8_hier`` wire.

    ``v``: (padded,) local fp32, padded divisible by the WORLD. Stage 1 is
    the exact fp32 ``psum_scatter`` over the intra-slice axes (fast-rank j
    takes chunk j); stage 2 is the s8 all-to-all scatter of `int8` mode
    over the slice axis on that 1/n_inner partial — the one quantization,
    with error feedback (``residual`` spans the FULL partial,
    padded/n_inner elements — EF remembers what was dropped from every
    destination chunk, the `compressed_psum_scatter` convention). Returns
    this replica's (padded/world,) chunk of the global sum: chunk index
    ``j * n_slices + s`` — the FAST-MAJOR ownership `HierSpec.hier_axes`
    names — plus the updated residual."""
    if spec.fast_axes:
        part = lax.psum_scatter(v, spec.fast_axes, scatter_dimension=0,
                                tiled=True)
    else:
        part = v
    return compressed_psum_scatter(part, (spec.slice_axis,), spec.n_slices,
                                   "int8", residual, fused=fused)


def hier_delta_all_gather(new_shard: jnp.ndarray, old_shard: jnp.ndarray,
                          old_flat: jnp.ndarray, spec: HierSpec,
                          fused: Optional[bool] = None) -> jnp.ndarray:
    """`quantized_delta_all_gather` on the two-tier wire (zero1 x hier
    param gather): s8 UPDATE codes cross slices, exact fp32 crosses ICI.

    Gather order is slice-axis FIRST: under fast-major ownership replica
    (s, j) holds chunk ``j * n_slices + s``, so the slice gather rebuilds
    fast-rank j's contiguous stage-1 chunk, and the fast gather then
    concatenates those in order. The slow hop carries ~1 byte/element of
    the 1/n_inner partial; the fast hop is exact (the intra-slice tier
    never quantizes). Error model: identical to the flat delta gather —
    every replica dequantizes the same (codes, scales) per slow hop, then
    gathers exactly, so the reconstruction is replica-identical."""
    delta = new_shard - old_shard
    part = _s8_all_gather_dequant(delta, (spec.slice_axis,), fused=fused)
    if spec.fast_axes:
        full = lax.all_gather(part, spec.fast_axes, axis=0, tiled=True)
    else:
        full = part
    return old_flat + full


def hier_shard_all_gather(shard: jnp.ndarray, spec: HierSpec,
                          fused: Optional[bool] = None) -> jnp.ndarray:
    """`quantized_shard_all_gather` on the two-tier wire (explicit FSDP x
    hier param gather): s8 codes of this replica's at-rest row cross
    slices (~1 B/element of the partial), then an exact fp32 intra-slice
    gather rebuilds the full layer group. Same slice-first order as
    `hier_delta_all_gather` (fast-major ownership); at-rest shards stay
    exact fp32 — only the per-step gathered working copy carries the
    bounded slow-hop perturbation."""
    part = _s8_all_gather_dequant(shard, (spec.slice_axis,), fused=fused)
    if spec.fast_axes:
        return lax.all_gather(part, spec.fast_axes, axis=0, tiled=True)
    return part


# ---------------------------------------------------------------------------
# Error-feedback state constructors (host-side; Trainer.init_state calls)
# ---------------------------------------------------------------------------


def _born_sharded_zeros(structs: Any, mesh, axes=None):
    """Zeros pytree (of jax.ShapeDtypeStruct leaves) created ALREADY
    sharded over ``axes`` (default: the batch axes — the
    optim.zero1_opt_state idiom; explicit TP passes (model,) + batch):
    jit with out_shardings makes XLA allocate each replica's rows in
    place — no full-array transient on device 0 (for gpt2-scale params,
    n_shards x param bytes would be a multi-GB spike at init_state)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh import BATCH_AXES

    axes = tuple(axes) if axes is not None else BATCH_AXES
    shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(axes)), structs)
    make = jax.jit(
        lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), structs),
        out_shardings=shardings)
    return make()


def ef_state_bucketed(params: Any, mesh, n_shards: int,
                      bucket_cap_mb: float = 0.0,
                      wire_dtype: str = "int8", n_slices: int = 1):
    """Per-replica error-feedback residual for the bucketed reducer: one
    (n_shards, R) fp32 array, row r = replica r's residual, sharded over
    the batch axes so each replica materializes only its row. R is the
    flat gradient size for the ``int8`` gather wire; for ``int8_multihop``
    it is the `padded_bucket_bounds` layout (each bucket padded to a
    multiple of n_shards — the hop-1 residual lives in the codec's padded
    view, so the bucket cap and wire dtype size the buffer); for
    ``int8_hier`` it is 1/n_inner of that padded layout (each replica's
    residual covers only its fast-tier partial — the slow tier is the one
    quantization site, and it only ever sees the partial). Consequence:
    a multihop/hier residual is only meaningful under the bucket plan it
    was built for — resuming such a checkpoint with a different
    ``bucket_cap_mb`` is unsupported (the step rejects mismatched residual
    lengths; keep the cap or rebuild the state and let EF restart from
    zero residuals).
    """
    plan = build_bucket_plan(params, bucket_cap_mb)
    if wire_dtype == "int8_multihop":
        total = padded_total_size(plan, n_shards)
    elif wire_dtype == "int8_hier":
        if n_slices < 2 or n_shards % n_slices:
            raise ValueError(
                f"int8_hier EF state needs a feasible factorization; got "
                f"{n_shards} shards over {n_slices} slices")
        total = padded_total_size(plan, n_shards) // (n_shards // n_slices)
    else:
        total = plan.total_size
    struct = jax.ShapeDtypeStruct((n_shards, total), jnp.float32)
    return {"ef": _born_sharded_zeros(struct, mesh)}


def ef_state_fsdp(params: Any, mesh, n_shards: int, model_n: int = 1,
                  n_inner: int = 1):
    """Per-replica residuals for the explicit-FSDP int8 gradient scatter:
    one (n_shards, n_shards * row_size) fp32 array PER LAYER GROUP (the
    scatter is per layer there — `build_layer_plan`), keyed by group name,
    sharded over the batch axes so each replica materializes only its row.
    The residual length is the group's full padded size: EF must remember
    what was dropped from EVERY destination chunk, not just the kept one
    (the `compressed_psum_scatter` convention).

    Explicit TP x FSDP (``model_n`` > 1): ``params`` is the TP-LOCAL
    template — each (model shard, data replica) pair runs its own
    data-axis scatter over its local row, so the row dim grows to
    ``model_n * n_shards`` (model-major, matching the at-rest layout) and
    the rows shard over (model,) + batch axes.

    Under the ``int8_hier`` wire pass ``n_inner``: the slow-tier scatter
    quantizes only the 1/n_inner fast-tier partial of each group, so each
    residual row shrinks by that factor (n_shards * row_size is a multiple
    of the world, hence of n_inner; TP x hier is rejected upstream, so
    model_n and n_inner never both exceed 1)."""
    from .mesh import BATCH_AXES, MODEL

    plan = build_layer_plan(params, n_shards)
    structs = {
        g.name: jax.ShapeDtypeStruct(
            (model_n * n_shards,
             n_shards * g.row_size // max(1, n_inner)), jnp.float32)
        for g in plan.groups}
    axes = ((MODEL,) + BATCH_AXES) if model_n > 1 else BATCH_AXES
    return {"ef": _born_sharded_zeros(structs, mesh, axes=axes)}


def fold_ef_rows(rows, new_n: int):
    """Re-chunk per-replica error-feedback ROWS from old-N to new-M
    replicas: new row m is the sum of old rows ``{m, m + M, m + 2M, ...}``
    (growing, M > N: the extra rows are zero).

    The invariant this preserves EXACTLY (element-wise, in order-fixed fp
    summation) is the column-wise TOTAL — the telescoping sum of carried
    quantization error across replicas, which is what re-enters the next
    reduction (each replica adds its row to its contribution before
    quantizing, and the collective sums all rows). The per-row DISTRIBUTION
    changes, so post-resize quantization scales differ from either
    fixed-world run — a bounded, deterministic re-association the elastic
    exactness model documents (PARITY.md). Host-side numpy, restore time.
    """
    import numpy as np

    rows = np.asarray(rows)
    if rows.ndim != 2:
        raise ValueError(f"fold_ef_rows expects (n, R) rows, got shape "
                         f"{rows.shape}")
    old_n, r = rows.shape
    out = np.zeros((new_n, r), rows.dtype)
    for i in range(old_n):
        out[i % new_n] += rows[i]
    return out


def reshard_multihop_ef_row(row, plan: BucketPlan, old_n: int,
                            new_n: int):
    """Re-chunk ONE multihop hop-1 residual row from the old-N
    `padded_bucket_bounds` layout to the new-M one: each bucket's padded
    region is truncated-or-zero-extended independently (the pad tail of
    every bucket is exactly zero — the carried value at a pad slot is
    always 0, so the hop-1 residual never accumulates there)."""
    import numpy as np

    from .sharding import reshard_flat_padded

    old_b = padded_bucket_bounds(plan, old_n)
    new_b = padded_bucket_bounds(plan, new_n)
    parts = [
        reshard_flat_padded(row[a:b], nb - na, name=f"bucket {k}")
        for k, (a, b, na, nb) in enumerate(
            zip(old_b, old_b[1:], new_b, new_b[1:]))]
    return np.concatenate(parts) if len(parts) > 1 else parts[0]


def reshard_fsdp_ef_row(row, old_group: LayerGroup, new_group: LayerGroup,
                        old_n: int, new_n: int):
    """Re-chunk ONE explicit-FSDP group residual row from the old-N
    destination-major stacking to the new-M one, leaf by leaf (never
    materializing more than this one layer group): column block i of the
    (n, row_size) view is leaf i's flat-padded vector reshaped (n, chunk),
    so per leaf the re-chunk is exactly `reshard_flat_padded` on the
    unstacked flat vector, restacked at the new chunking."""
    import numpy as np

    from .sharding import reshard_flat_padded

    row = np.asarray(row)
    mat = row.reshape(old_n, old_group.row_size)
    parts = []
    off = 0
    for (slot, c_old), c_new in zip(
            zip(old_group.leaf_slots, old_group.chunk_sizes),
            new_group.chunk_sizes):
        leaf_flat = np.ascontiguousarray(
            mat[:, off:off + c_old]).reshape(-1)
        leaf_new = reshard_flat_padded(leaf_flat, new_n * c_new,
                                       name=f"{old_group.name}[{slot}]")
        parts.append(leaf_new.reshape(new_n, c_new))
        off += c_old
    out = np.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return out.reshape(-1)


def ef_state_zero1(params: Any, mesh, n_shards: int, n_inner: int = 1):
    """Per-replica residuals for the zero1 int8 scatter: one
    (n_shards, flat_padded_size) fp32 array PER LEAF (the scatter is
    per-leaf there), sharded over the batch axes. Under the ``int8_hier``
    wire pass ``n_inner``: the slow-tier scatter quantizes only the
    1/n_inner fast-tier partial, so each residual row shrinks by the same
    factor (flat_padded_size is a multiple of the world, hence of
    n_inner)."""
    from .sharding import flat_padded_size

    structs = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(
            (n_shards,
             flat_padded_size(int(np.prod(np.shape(p)) or 1), n_shards)
             // max(1, n_inner)),
            jnp.float32),
        params)
    return {"ef": _born_sharded_zeros(structs, mesh)}

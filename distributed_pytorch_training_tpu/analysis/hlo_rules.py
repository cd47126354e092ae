"""HLO contract checker: census parsers + declarative rules over compiled
train steps.

The parsers (`hlo_result_elements`, `collective_census`,
`weight_update_census`, `grad_sync_census`) moved here from
the trace reader (now `telemetry/trace_analysis.py`: that half is
runtime analysis; this is the compile-time half, a checked contract
instead of scattered helpers).

Rules consume a `StepArtifacts` snapshot of one lowered config — the
optimized HLO text, the pre-optimization text (the wire-dtype read on CPU,
whose float-normalization pass promotes bf16 collectives to f32 in the
optimized text), the config knobs, and the sharding facts the evaluator
read off the live state. Each rule returns `Finding`s instead of raising,
so one run reports every violation; the `verify_*` wrappers below keep the
historical raise-on-violation API for acceptance-gate callers.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

from .contracts import (
    Contract, Finding, WIRE_HLO_DTYPE, WIRE_MODES, collectives_per_bucket,
    rule,
)

# ---------------------------------------------------------------------------
# HLO text parsers (the census)
# ---------------------------------------------------------------------------

# HLO text: `%name = shape op-name(...)`. On TPU the latency-hiding scheduler
# splits collectives into async `-start`/`-done` pairs; count the `-start`
# half (and bare sync forms), never `-done`, so each collective counts once.
# `ragged-all-to-all` (MoE dispatch at uneven expert loads) precedes
# `all-to-all` in the alternation so the longer name wins.
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|ragged-all-to-all|all-to-all)"
    r"(-start|-done)?[.\w]*\(")

# The collective's device grouping, printed on the same HLO line: the
# explicit form `replica_groups={{0,1},{2,3}}` or the iota form
# `replica_groups=[G,S]<=[dims...]` with an optional transpose suffix
# `T(perm)` (XLA's strided-group print form — the data-axis groups of a
# (data, model) mesh). The capture must accept every shape
# `parse_replica_groups` can decode, or classifiable groups silently
# arrive as "" and the TP rules misfire.
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups="
    r"(\{\{[\d,{} ]*\}\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")

# One array shape inside an HLO result: "f32[1000,512]{1,0}" (possibly inside
# a tuple). Captures the bracketed dims; "f32[]" is a scalar.
_HLO_SHAPE_RE = re.compile(r"\w+\[([\d,]*)\]")

# Same shape token with the DTYPE captured instead ("f32", "bf16", "s8") —
# the wire-dtype read of `grad_sync_census`. Context/token dtypes (u32 ids
# in async tuples) ride along; the census reports all of them.
_HLO_TYPED_SHAPE_RE = re.compile(r"(\w+)\[[\d,]*\]")


def hlo_result_elements(shape_str: str) -> int:
    """Total elements across every array in an HLO result shape string
    (async collectives return tuples; sum the parts so `-start` forms
    compare like their sync equivalents)."""
    total = 0
    for m in _HLO_SHAPE_RE.finditer(shape_str):
        dims = m.group(1)
        if not dims:
            total += 1  # scalar
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        total += n
    return total


def collective_census(compiled_text: str) -> List[dict]:
    """Census of collective ops in optimized HLO text: op kind + result
    shape + the replica grouping (which mesh axis the collective rides —
    the 2-D TP x FSDP rules classify it via `replica_group_axis`).

    The static half of the grad-sync analysis: what the compiler actually
    scheduled (names/shapes straight from the executable), standing in for
    the reference's promised profiler-timeline read-off (README.md:35)."""
    rows = {}
    for line in compiled_text.splitlines():
        m = _HLO_COLLECTIVE_RE.search(line)
        if not m:
            continue
        shape, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue  # the paired completion of an async -start
        g = _REPLICA_GROUPS_RE.search(line)
        groups = g.group(1) if g else ""
        key = (kind, shape, groups)
        if key not in rows:
            rows[key] = {"op": kind, "result_shape": shape,
                         "replica_groups": groups, "count": 0}
        rows[key]["count"] += 1
    return sorted(rows.values(),
                  key=lambda r: (r["op"], r["result_shape"],
                                 r["replica_groups"]))


def parse_replica_groups(groups: str):
    """Explicit `{{0,1},{2,3}}` or iota `[G,S]<=[dims...]` replica groups
    (with an optional transpose suffix `T(perm)` — XLA's strided-group
    print form, e.g. the data-axis groups of a (data, model) mesh) as a
    tuple of tuples; None when absent/unparseable."""
    if not groups:
        return None
    if groups.startswith("{{"):
        try:
            return tuple(
                tuple(int(x) for x in part.split(",") if x.strip())
                for part in groups.strip("{}").split("},{"))
        except ValueError:
            return None
    m = re.fullmatch(r"\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                     groups)
    if m:
        import numpy as _np

        n_groups, size = int(m.group(1)), int(m.group(2))
        dims = tuple(int(d) for d in m.group(3).split(","))
        total = int(_np.prod(dims))
        if n_groups * size != total:
            return None
        devices = _np.arange(total).reshape(dims)
        if m.group(4) is not None:
            perm = tuple(int(p) for p in m.group(4).split(","))
            if sorted(perm) != list(range(len(dims))):
                return None
            devices = devices.transpose(perm)
        flat = devices.reshape(-1)
        return tuple(tuple(int(x) for x in flat[g * size:(g + 1) * size])
                     for g in range(n_groups))
    return None


def replica_group_axis(groups: str, n_batch: int, n_model: int) -> str:
    """Which logical axis a collective's replica groups ride, on a 2-D
    (batch-shards x model) device layout with the model axis MINOR
    (parallel/mesh.AXIS_ORDER puts `model` last): "model" (consecutive-id
    groups of size M), "data" (stride-M groups of size N), "all" (one
    group spanning every device), or "other"/"unknown". The TP x FSDP
    rules use this to tell megatron activation psums from gradient
    traffic; artifacts without a model axis never consult it."""
    parsed = parse_replica_groups(groups)
    if parsed is None:
        return "unknown"
    got = {frozenset(g) for g in parsed}
    total = n_batch * n_model
    if got == {frozenset(range(b * n_model, (b + 1) * n_model))
               for b in range(n_batch)}:
        return "model"
    if got == {frozenset(range(m, total, n_model)) for m in range(n_model)}:
        return "data"
    if got == {frozenset(range(total))}:
        return "all"
    return "other"


def replica_group_tier(groups: str, n_slices: int, n_inner: int) -> str:
    """Which TIER a collective's replica groups ride on the two-tier
    (slice x intra-slice) layout: "ici" (groups stay inside one slice —
    the fast interconnect), "dcn" (groups cross slices — the slow
    inter-slice links), "all" (one group spanning the mesh), or
    "other"/"unknown". The slice axis is OUTERMOST in AXIS_ORDER
    (parallel/mesh.py), so device ids are slice-major: intra-slice groups
    are consecutive-id runs of size n_inner and cross-slice groups are
    stride-n_inner combs — exactly the geometry `replica_group_axis`
    already classifies with (n_batch, n_model) = (n_slices, n_inner);
    this wrapper renames its verdicts into tier vocabulary. With
    n_inner=1 (no intra-slice width) every hier collective spans all
    slices and classifies "dcn" — there is no fast tier to ride."""
    axis = replica_group_axis(groups, max(n_slices, 1), max(n_inner, 1))
    return {"model": "ici", "data": "dcn"}.get(axis, axis)


def weight_update_census(compiled_text: str, min_elements: int = 8192) -> dict:
    """The gradient-sync subset of the census: collectives whose result
    carries at least `min_elements` elements — gradient- and parameter-sized
    transfers. Scalar psums (metric fan-in, global-norm clipping, BatchNorm
    channel stats) fall under the floor, so the returned counts isolate the
    ops that move the model: the DDP-style grad all-reduce on the replicated
    path, reduce-scatter + all-gather on the zero1 path.

    Returns {"all-reduce": n, "reduce-scatter": n, "all-gather": n,
    "rows": [...]} (other collective kinds appear only if present)."""
    counts: Dict[str, int] = {"all-reduce": 0, "reduce-scatter": 0,
                              "all-gather": 0}
    rows = []
    for c in collective_census(compiled_text):
        if hlo_result_elements(c["result_shape"]) < min_elements:
            continue
        counts[c["op"]] = counts.get(c["op"], 0) + c["count"]
        rows.append(c)
    counts["rows"] = rows
    return counts


def grad_sync_census(hlo_text: str, min_elements: int = 8192) -> dict:
    """Census of the gradient-sync stage in HLO text: how many gradient-
    sized collectives the step carries, and what dtype rides the wire.

    The instrument for the bucketed reducer (parallel/grad_sync.py): with
    ``bucket_cap_mb`` set, the compiled step must show
    ``ceil(total_grad_bytes / cap)`` large collectives (one per bucket)
    instead of one per leaf, and with a compressed ``wire_dtype`` their
    operands must be bf16/s8, not f32. Accepts optimized HLO
    (``compiled.as_text()``) or pre-optimization HLO (`preopt_hlo_text`):
    CPU's float-normalization pass promotes bf16 collectives to f32 in the
    OPTIMIZED text, so wire-dtype checks on the test backend read the
    pre-optimization module (TPU keeps bf16 end-to-end).

    Returns {"n_collectives", "by_op": {op: n}, "wire_dtypes": {dtype: n},
    "rows": [...]} counting only collectives whose result carries at least
    `min_elements` elements (scalar metric psums and int8 scale gathers
    fall under the floor).
    """
    by_op: Dict[str, int] = {}
    wire: Dict[str, int] = {}
    rows = []
    total = 0
    for c in collective_census(hlo_text):
        if hlo_result_elements(c["result_shape"]) < min_elements:
            continue
        total += c["count"]
        by_op[c["op"]] = by_op.get(c["op"], 0) + c["count"]
        dtypes = sorted(set(
            m.group(1)
            for m in _HLO_TYPED_SHAPE_RE.finditer(c["result_shape"])))
        for d in dtypes:
            wire[d] = wire.get(d, 0) + c["count"]
        rows.append({**c, "dtypes": dtypes})
    return {"n_collectives": total, "by_op": by_op, "wire_dtypes": wire,
            "rows": rows}


def preopt_hlo_text(lowered) -> str:
    """Pre-optimization HLO text of a ``jax.jit(...).lower(...)`` result —
    the wire-dtype read for `grad_sync_census` (see its docstring: the CPU
    backend's float-normalization rewrites bf16 collectives to f32 before
    the optimized text is printed)."""
    return lowered.compiler_ir(dialect="hlo").as_hlo_text()


def expected_buckets(total_grad_bytes: int, bucket_cap_mb: float) -> int:
    """ceil(bytes/cap) with build_bucket_plan's EXACT floor-to-elements
    arithmetic — re-deriving it as ceil(bytes/cap_bytes) would under-count
    buckets whenever the cap is not element-aligned and flag a correctly
    engaged reducer."""
    total_elems = int(total_grad_bytes) // 4
    cap_elems = int(bucket_cap_mb * (1024 ** 2) // 4)
    if bucket_cap_mb <= 0 or cap_elems >= total_elems:
        return 1  # no/huge cap = one fused bucket
    return -(-total_elems // max(cap_elems, 1))


# ---------------------------------------------------------------------------
# Step artifacts: everything the rules need, snapshotted once per config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepArtifacts:
    """One lowered/compiled train-step config, as the rules see it.

    Built by `evaluate_contract` (the matrix); tests build them directly
    to feed rules synthetic violations (the mutation tests).
    """

    name: str
    optimized_text: str
    preopt_text: Optional[str] = None
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    n_shards: int = 1
    total_grad_bytes: int = 0
    min_elements: int = 8192
    # (path, n_elements) of optimizer-state leaves >= min_elements whose
    # sharding the evaluator found fully replicated (zero1 promises none).
    replicated_state_buffers: Tuple[Tuple[str, int], ...] = ()
    # Same read over the PARAMETER leaves (explicit FSDP promises none:
    # params live flat-sharded 1/N at rest — a replicated param buffer
    # means the mode is paying replicated memory while claiming the
    # division). Filled only for fsdp configs.
    replicated_param_buffers: Tuple[Tuple[str, int], ...] = ()
    # Per-group full padded element counts (n_shards x row_size, one per
    # LayerGroup of the trainer's grad_sync.build_layer_plan) — the
    # fsdp-layer-gather-bound / scatter-signature budget. The SIZES ride
    # along (not just the count) because the census floor hides sub-floor
    # groups (a tiny final layernorm's gather is metric noise by design):
    # the rules compute floor-aware expected counts from these. Empty when
    # the config is not explicit-FSDP.
    layer_group_padded_sizes: Tuple[int, ...] = ()
    # the backend the config was lowered FOR ("tpu"/"cpu"/...): rules whose
    # promise only exists in one backend's lowering (fused-quantize-kernel-
    # present: Pallas emits a custom-call on TPU but inlines as plain HLO
    # in CPU interpreter mode) abstain rather than guess when it is "".
    backend: str = ""
    # Explicit TP x FSDP (ISSUE 13): the mesh's model-axis size (1 = no
    # TP — every pre-existing artifact), and the trainer-derived model-axis
    # collective budget: `tp_expected_psums` counts the megatron psums of
    # one fwd+bwd step (one per residual join forward + its backward
    # mirror at each parallel-region input: 4/block, +2 with the
    # vocab-parallel embedding), `tp_expected_model_gathers` the
    # vocab-parallel logits gathers (1 when engaged). Snapshotted from the
    # trainer (Trainer.tp_expected_model_collectives), never hard-coded in
    # a rule.
    model_shards: int = 1
    tp_expected_psums: int = 0
    tp_expected_model_gathers: int = 0
    # Per-shard element count of EACH of the parallel-vocab CE's two
    # model-axis stat collectives (both (rows, seq-1, 2)-shaped by
    # construction — collectives.tp_parallel_cross_entropy). Batch-shaped,
    # so unlike the hidden-sized structural psums their census visibility
    # depends on batch x floor: `tp-psum-signature` adds 2 to the psum
    # budget iff this clears min_elements. Snapshotted from
    # Trainer.tp_expected_ce_stat_elements; 0 when the vocab-parallel
    # head is not engaged.
    tp_ce_stat_elements: int = 0
    # Two-tier hierarchical sync (int8_hier): the mesh's slice-axis size
    # (1 = single-slice — every pre-existing artifact). Snapshotted from
    # the trainer's resolved HierSpec, never re-derived in a rule: the
    # tier classification of every hier census row keys on it.
    slice_shards: int = 1

    @property
    def wire_mode(self) -> str:
        return self.config.get("wire_dtype", "fp32")

    @property
    def tp_engaged(self) -> bool:
        """Mirrors Trainer's engagement condition for explicit TP x FSDP."""
        return bool(self.config.get("fsdp_explicit")) and self.model_shards > 1

    def collective_axis(self, row: dict) -> str:
        """`replica_group_axis` of one census row under this artifact's
        (batch, model) shard counts."""
        return replica_group_axis(row.get("replica_groups", ""),
                                  max(self.n_shards, 1),
                                  max(self.model_shards, 1))

    @property
    def hier_engaged(self) -> bool:
        """Mirrors Trainer's engagement condition for the two-tier wire:
        int8_hier on a mesh with a real slice axis (on slices=1 the
        trainer resolves to the flat fp32 path BEFORE tracing, so no hier
        collective exists to classify)."""
        return (self.wire_mode == "int8_hier" and self.slice_shards > 1
                and self.n_shards > 1)

    def collective_tier(self, row: dict) -> str:
        """`replica_group_tier` of one census row under this artifact's
        (slice, intra-slice) factorization: n_inner is the intra-slice
        batch-shard count n_shards / slice_shards."""
        n_slices = max(self.slice_shards, 1)
        return replica_group_tier(row.get("replica_groups", ""), n_slices,
                                  max(self.n_shards // n_slices, 1))

    @property
    def zero1_engaged(self) -> bool:
        return bool(self.config.get("zero1")) and self.n_shards > 1

    @property
    def fsdp_engaged(self) -> bool:
        """Mirrors Trainer's engagement condition for explicit FSDP."""
        return bool(self.config.get("fsdp_explicit")) and self.n_shards > 1

    @property
    def grad_sync_engaged(self) -> bool:
        """Mirrors Trainer's engagement condition for the explicit reducer
        (fsdp_explicit owns its own wire layout — the per-layer cut — so a
        compressed wire under fsdp is NOT the bucketed reducer)."""
        return (not self.config.get("zero1")
                and not self.config.get("fsdp_explicit")
                and self.n_shards > 1
                and (float(self.config.get("bucket_cap_mb", 0.0)) > 0
                     or self.wire_mode != "fp32"))

    @property
    def wire_text(self) -> str:
        """The text wire-dtype reads use: pre-optimization when available
        (bf16 survives only there on CPU), optimized otherwise."""
        return self.preopt_text or self.optimized_text


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

# Collective kinds that REDUCE gradients (may legally compress). all-gather
# is excluded: the zero1 parameter gather is exact by design — fp32 there is
# the contract, not a violation. (The int8 code gather rides s8 anyway.)
_REDUCTION_KINDS = ("all-reduce", "reduce-scatter", "all-to-all",
                    "ragged-all-to-all")


def _multihop_hop_problems(census: dict) -> List[str]:
    """Problems with a census that CLAIMS the multi-hop int8 wire.

    The 2/bucket budget is an upper bound, so a single-collective-per-
    bucket impostor (e.g. the gather-form codec mislabeled as multihop)
    sails under it — the hop SIGNATURE is what catches it: hop 1 must
    appear as a scatter-kind collective (all-to-all or reduce-scatter) and
    hop 2 as an all-gather, both gradient-sized.
    """
    by_op = census["by_op"]
    problems = []
    if not (by_op.get("all-to-all", 0) + by_op.get("reduce-scatter", 0)):
        problems.append(
            "multihop wire shows no gradient-sized all-to-all/reduce-"
            "scatter — hop 1 (the s8 reduce-scatter) is missing")
    if not by_op.get("all-gather", 0):
        problems.append(
            "multihop wire shows no gradient-sized all-gather — hop 2 "
            "(the requantized s8 gather) is missing")
    return problems


@rule("grad-sync-bucket-bound", "hlo",
      "bucketed reducer emits <= buckets x per-bucket-cost + slack "
      "gradient-sized collectives",
      "O(buckets) large transfers instead of O(leaves) small ones is the "
      "reducer's whole win; an unbounded census means bucketing silently "
      "disengaged (parallel/grad_sync.py).")
def check_bucket_bound(a: StepArtifacts, slack: int = 2) -> List[Finding]:
    if not a.grad_sync_engaged:
        return []
    census = grad_sync_census(a.optimized_text, a.min_elements)
    n_buckets = expected_buckets(a.total_grad_bytes,
                                 float(a.config.get("bucket_cap_mb", 0.0)))
    bound = n_buckets * collectives_per_bucket(a.wire_mode) + slack
    out = []
    if census["n_collectives"] > bound:
        out.append(Finding(
            "grad-sync-bucket-bound",
            f"step carries {census['n_collectives']} gradient-sized "
            f"collectives, more than {n_buckets} bucket(s) x "
            f"{collectives_per_bucket(a.wire_mode)} ({a.wire_mode}) + "
            f"{slack} = {bound}: {census['by_op']}", a.name))
    if census["n_collectives"] == 0:
        out.append(Finding(
            "grad-sync-bucket-bound",
            f"no gradient-sized collectives found — the census floor "
            f"(min_elements={a.min_elements}) is above the model's gradient "
            "transfers, or the reducer never ran", a.name))
    elif a.wire_mode == "int8_multihop":
        out.extend(Finding("grad-sync-bucket-bound", p, a.name)
                   for p in _multihop_hop_problems(census))
    return out


@rule("compressed-wire", "hlo",
      "a compressed wire_dtype really puts bf16/s8 on the wire",
      "a silent fallback to fp32 operands erases the wire-byte win while "
      "the flag still claims it (the ISSUE-2 acceptance check).")
def check_compressed_wire(a: StepArtifacts) -> List[Finding]:
    if a.wire_mode == "fp32" or not (a.grad_sync_engaged or a.zero1_engaged
                                     or a.fsdp_engaged):
        return []
    if a.wire_mode == "int8_hier" and not a.hier_engaged:
        # slices=1 passthrough: the trainer resolved int8_hier to the flat
        # fp32 path before tracing — there is no s8 wire to demand
        return []
    if a.preopt_text is None:
        # No reliable wire read: CPU's float-normalization promotes bf16
        # collectives to f32 in the OPTIMIZED text, so checking it would
        # turn a pre-opt extraction failure into a false violation. The
        # wire rules abstain rather than guess (the evaluator always
        # attempts the pre-opt read).
        return []
    expect = WIRE_HLO_DTYPE[a.wire_mode]
    wire = grad_sync_census(a.wire_text, a.min_elements)["wire_dtypes"]
    if not wire.get(expect):
        return [Finding(
            "compressed-wire",
            f"wire_dtype={a.wire_mode!r} promises {expect} collective "
            f"operands on the wire, but the HLO shows {wire}", a.name)]
    return []


@rule("no-fp32-wire", "hlo",
      "no fp32 bytes ride a compressed wire's gradient reductions",
      "compressed-wire proves bf16/s8 is present; this proves fp32 is "
      "ABSENT from the reducing collectives — both can hold at once only "
      "if every gradient byte is compressed. The zero1 parameter "
      "all-gather is exempt: it is exact by design.")
def check_no_fp32_wire(a: StepArtifacts) -> List[Finding]:
    if a.wire_mode == "fp32" or not (a.grad_sync_engaged or a.zero1_engaged
                                     or a.fsdp_engaged):
        return []
    if a.wire_mode == "int8_hier" and not a.hier_engaged:
        return []  # slices=1 passthrough — see check_compressed_wire
    if a.preopt_text is None:
        return []  # no reliable wire read — see check_compressed_wire
    census = grad_sync_census(a.wire_text, a.min_elements)
    # Explicit TP: megatron activation psums ride the MODEL axis in exact
    # fp32 BY DESIGN (they are forward/backward activations, not gradient
    # sync — the zero1 param-gather exemption's argument); only collectives
    # off the model axis must keep the compressed-wire promise.
    rows = census["rows"]
    if a.tp_engaged:
        rows = [r for r in rows if a.collective_axis(r) != "model"]
    if a.hier_engaged:
        # Two-tier wire: the INTRA-slice stage reduces in exact fp32 BY
        # DESIGN (that tier rides the fast interconnect; s8 is the
        # SLOW-tier promise — contracts.WIRE_HLO_DTYPE). Only the ici
        # tier is exempt: cross-slice rows (and anything the classifier
        # can't place) must still keep every gradient byte compressed.
        rows = [r for r in rows if a.collective_tier(r) != "ici"]
    bad = [r for r in rows
           if r["op"] in _REDUCTION_KINDS and "f32" in r["dtypes"]]
    if bad:
        return [Finding(
            "no-fp32-wire",
            f"wire_dtype={a.wire_mode!r} but {len(bad)} gradient-sized "
            f"reducing collective(s) carry f32 operands: "
            f"{[(r['op'], r['result_shape']) for r in bad]}", a.name)]
    return []


@rule("hier-tier-signature", "hlo",
      "the two-tier wire rides each tier with the right signature: exact "
      "reduce-scatter/all-gather INSIDE a slice, an s8 scatter+gather "
      "hop pair ACROSS slices, nothing spanning both",
      "the 4/bucket budget alone is a ceiling a flat codec sails under — "
      "the TIER-classified signature is what pins the hierarchy: a flat "
      "multihop mislabeled int8_hier shows no cross-slice-only hop (its "
      "groups span the whole mesh), a hierarchy that lost its fast stage "
      "shows no intra-slice reduce-scatter, and an fp32 byte on a "
      "cross-slice collective is paying exact-width traffic on the slow "
      "links the mode exists to compress (parallel/grad_sync.py "
      "_int8_hier_sum; slice-major device ids make the tiers readable "
      "straight off replica_groups — parallel/mesh.py AXIS_ORDER).")
def check_hier_tier_signature(a: StepArtifacts) -> List[Finding]:
    if not a.hier_engaged or not (a.grad_sync_engaged or a.zero1_engaged
                                  or a.fsdp_engaged):
        return []
    n_slices = max(a.slice_shards, 1)
    n_inner = max(a.n_shards // n_slices, 1)
    census = grad_sync_census(a.optimized_text, a.min_elements)
    by_tier_op: Dict[Tuple[str, str], int] = {}
    for r in census["rows"]:
        key = (a.collective_tier(r), r["op"])
        by_tier_op[key] = by_tier_op.get(key, 0) + r["count"]

    def n(tier: str, *ops: str) -> int:
        return sum(by_tier_op.get((tier, op), 0) for op in ops)

    out = []
    spanning = [(t, op, c) for (t, op), c in sorted(by_tier_op.items())
                if t not in ("ici", "dcn")]
    if spanning:
        out.append(Finding(
            "hier-tier-signature",
            f"{sum(c for _, _, c in spanning)} gradient-sized "
            f"collective(s) ride groups that are neither intra-slice nor "
            f"cross-slice: {spanning[:5]} — a hier collective grouped "
            "over the whole mesh (or off-pattern) is flat traffic wearing "
            "the two-tier flag", a.name))
    dcn_scatter = n("dcn", "all-to-all", "reduce-scatter")
    dcn_gather = n("dcn", "all-gather")
    if not dcn_scatter:
        out.append(Finding(
            "hier-tier-signature",
            "no gradient-sized CROSS-SLICE all-to-all/reduce-scatter — "
            "hop 1 of the slow-tier s8 exchange is missing", a.name))
    if not dcn_gather:
        out.append(Finding(
            "hier-tier-signature",
            "no gradient-sized CROSS-SLICE all-gather — hop 2 (the "
            "requantized s8 gather) is missing", a.name))
    if n_inner > 1:
        if not n("ici", "reduce-scatter"):
            out.append(Finding(
                "hier-tier-signature",
                "no gradient-sized INTRA-SLICE reduce-scatter — the "
                "exact fast-tier reduce is missing (every byte is riding "
                "the slow links)", a.name))
        if not n("ici", "all-gather"):
            out.append(Finding(
                "hier-tier-signature",
                "no gradient-sized INTRA-SLICE all-gather — the reduced "
                "buckets are never rebuilt across the slice", a.name))
    if a.grad_sync_engaged and a.total_grad_bytes:
        # The bucketed-reducer arm pins EXACT per-bucket counts per tier
        # (zero1/fsdp cut per shard-group/layer instead — presence-only
        # above). Every hop's census result clears the floor whenever the
        # smallest (the 1/n_inner slow-tier part) does, so one floor
        # check guards the whole expectation from tiny-bucket noise.
        n_buckets = expected_buckets(
            a.total_grad_bytes, float(a.config.get("bucket_cap_mb", 0.0)))
        part = (a.total_grad_bytes // 4) // max(n_buckets, 1) // n_inner
        if part >= a.min_elements:
            expect = [(dcn_scatter, "cross-slice scatter (hop 1)"),
                      (dcn_gather, "cross-slice all-gather (hop 2)")]
            if n_inner > 1:
                expect += [(n("ici", "reduce-scatter"),
                            "intra-slice reduce-scatter"),
                           (n("ici", "all-gather"), "intra-slice all-gather")]
            for got, label in expect:
                if got != n_buckets:
                    out.append(Finding(
                        "hier-tier-signature",
                        f"step carries {got} {label} collective(s), "
                        f"expected exactly {n_buckets} (one per bucket; "
                        f"census by (tier, op): "
                        f"{dict(sorted(by_tier_op.items()))})", a.name))
    if a.preopt_text is not None:
        # the dtype read (pre-opt text — see check_compressed_wire): no
        # fp32 byte may CROSS slices, on any collective kind. Stricter
        # than no-fp32-wire, which exempts gathers mode-wide: the hier
        # slow-tier gather is s8 by construction, so fp32 there is a
        # decompressed hop-2 paying 4x on the slow links.
        wrows = grad_sync_census(a.wire_text, a.min_elements)["rows"]
        bad = [(r["op"], r["result_shape"]) for r in wrows
               if a.collective_tier(r) == "dcn" and "f32" in r["dtypes"]]
        if bad:
            out.append(Finding(
                "hier-tier-signature",
                f"{len(bad)} CROSS-SLICE collective(s) carry f32 "
                f"operands: {bad[:5]} — the slow tier must ride s8 codes "
                "(+ sub-floor scale rows) only", a.name))
    return out


@rule("zero1-collectives", "hlo",
      "zero1 replaces gradient all-reduces with reduce-scatter + all-gather",
      "the collective signature of cross-replica weight-update sharding "
      "(Xu et al., arXiv:2004.13336): a surviving gradient-sized "
      "all-reduce means the sharded update silently fell back to the "
      "replicated one.")
def check_zero1_collectives(a: StepArtifacts) -> List[Finding]:
    if not a.zero1_engaged:
        return []
    census = weight_update_census(a.optimized_text, a.min_elements)
    out = []
    if census["all-reduce"]:
        out.append(Finding(
            "zero1-collectives",
            f"zero1 step still contains {census['all-reduce']} gradient-"
            f"sized all-reduce(s): "
            f"{[r for r in census['rows'] if r['op'] == 'all-reduce']}",
            a.name))
    # the int8 scatter rides an s8 all-to-all instead of reduce-scatter
    scatter_ops = census["reduce-scatter"] + census.get("all-to-all", 0)
    if not scatter_ops:
        out.append(Finding("zero1-collectives",
                           "zero1 step contains no reduce-scatter (or s8 "
                           "all-to-all) — gradients are not being scattered",
                           a.name))
    if not census["all-gather"]:
        out.append(Finding("zero1-collectives",
                           "zero1 step contains no all-gather — updated "
                           "parameter shards are never rebuilt", a.name))
    return out


@rule("zero1-sharded-state", "hlo",
      "no gradient-sized optimizer-state buffer stays replicated under zero1",
      "dividing moment memory by the DP degree IS the zero1 win; a "
      "replicated moment buffer means the sharded update is paying "
      "replicated memory (the arXiv:2004.13336 contract).")
def check_zero1_sharded_state(a: StepArtifacts) -> List[Finding]:
    if not a.zero1_engaged:
        return []
    if a.replicated_state_buffers:
        rows = ", ".join(f"{p} ({n} elements)"
                         for p, n in a.replicated_state_buffers[:5])
        more = len(a.replicated_state_buffers) - 5
        return [Finding(
            "zero1-sharded-state",
            f"{len(a.replicated_state_buffers)} optimizer-state buffer(s) "
            f">= {a.min_elements} elements are fully replicated under "
            f"zero1: {rows}" + (f" (+{more} more)" if more > 0 else ""),
            a.name)]
    return []


@rule("fsdp-layer-gather-bound", "hlo",
      "explicit FSDP gathers params exactly once per layer group",
      "the just-in-time per-layer gather IS the mode (SimpleFSDP, "
      "PAPERS.md): fewer gathers than layer groups means some layer reads "
      "stale or GSPMD-materialized full params; more means the per-layer "
      "plan degenerated into per-leaf traffic (the O(leaves) failure the "
      "LayerPlan exists to prevent). The budget comes from the trainer's "
      "build_layer_plan, never hard-coded.")
def check_fsdp_gather_bound(a: StepArtifacts) -> List[Finding]:
    if not a.fsdp_engaged:
        return []
    sizes = a.layer_group_padded_sizes
    if not sizes:
        return [Finding(
            "fsdp-layer-gather-bound",
            "fsdp config evaluated without a layer-plan budget "
            "(layer_group_padded_sizes empty) — the evaluator must "
            "snapshot the trainer's LayerPlan group sizes", a.name)]
    # A group's gather result carries its FULL padded size (fp32 f32 or
    # multihop s8 codes — same element count); groups under the census
    # floor are invisible by design, so the expectation is floor-aware.
    expected = sum(1 for s in sizes if s >= a.min_elements)
    census = grad_sync_census(a.optimized_text, a.min_elements)
    if a.tp_engaged:
        # 2-D mesh: count only the DATA-axis gathers — the vocab-parallel
        # logits gather rides the model axis and is tp-psum-signature's
        # budget, not a param gather
        gathers = sum(r["count"] for r in census["rows"]
                      if r["op"] == "all-gather"
                      and a.collective_axis(r) == "data")
    else:
        gathers = census["by_op"].get("all-gather", 0)
    if gathers != expected:
        return [Finding(
            "fsdp-layer-gather-bound",
            f"fsdp step carries {gathers} gradient/param-sized "
            + ("data-axis " if a.tp_engaged else "")
            + f"all-gather(s), expected exactly {expected} (one per layer "
            f"group over the census floor; {len(sizes)} group(s), "
            f"{len(sizes) - expected} under min_elements="
            f"{a.min_elements}): {census['by_op']}", a.name)]
    return []


@rule("fsdp-scatter-into-shard", "hlo",
      "explicit FSDP reduce-scatters each layer's gradient into the shard "
      "layout, with no gradient-sized all-reduce",
      "the scatter-into-shard signature: gradients must land as 1/N "
      "chunks (reduce-scatter, or the s8 all-to-all under the int8 "
      "codec), one per layer group. A surviving gradient-sized all-reduce "
      "means the step synced replicated gradients and the at-rest "
      "sharding is cosmetic.")
def check_fsdp_scatter_signature(a: StepArtifacts) -> List[Finding]:
    if not a.fsdp_engaged:
        return []
    census = grad_sync_census(a.optimized_text, a.min_elements)
    by_op = census["by_op"]
    out = []
    if a.tp_engaged:
        # 2-D mesh: the scatter census counts data-axis collectives; the
        # model-axis megatron psums are all-reduces by op kind and are
        # budgeted by tp-psum-signature instead — a gradient-sized
        # all-reduce on the DATA axes is still the violation here.
        rows = census["rows"]
        scatters = sum(r["count"] for r in rows
                       if r["op"] in ("reduce-scatter", "all-to-all")
                       and a.collective_axis(r) == "data")
        data_all_reduce = sum(r["count"] for r in rows
                              if r["op"] == "all-reduce"
                              and a.collective_axis(r) != "model")
    else:
        scatters = by_op.get("reduce-scatter", 0) + by_op.get("all-to-all", 0)
        data_all_reduce = by_op.get("all-reduce", 0)
    sizes = a.layer_group_padded_sizes
    if sizes:
        # Floor-aware expectation, per wire: the s8 codec's all-to-all
        # result carries the group's FULL padded size, a plain
        # reduce-scatter's result is the 1/N destination chunk — the same
        # group can be census-visible under one wire and not the other.
        if a.wire_mode in ("int8", "int8_multihop"):
            expected = sum(1 for s in sizes if s >= a.min_elements)
        else:
            expected = sum(1 for s in sizes
                           if s // max(a.n_shards, 1) >= a.min_elements)
        if scatters != expected:
            out.append(Finding(
                "fsdp-scatter-into-shard",
                f"fsdp step carries {scatters} gradient-sized "
                f"reduce-scatter/all-to-all(s), expected exactly "
                f"{expected} (one per layer group whose scatter result "
                f"clears the census floor; {len(sizes)} group(s), "
                f"min_elements={a.min_elements}, wire={a.wire_mode}): "
                f"{by_op}", a.name))
    if data_all_reduce:
        out.append(Finding(
            "fsdp-scatter-into-shard",
            f"fsdp step still contains {data_all_reduce} gradient-"
            "sized all-reduce(s)"
            + (" off the model axis" if a.tp_engaged else "")
            + " — gradients are being synced replicated "
            "instead of scattered into the shard layout", a.name))
    return out


@rule("tp-psum-signature", "hlo",
      "explicit TP carries exactly the megatron model-axis collective "
      "budget: one psum per residual join (+ backward mirror), the "
      "parallel-vocab CE's two stat collectives, and ZERO model-axis "
      "gathers",
      "the model-axis psums ARE the TP wire: fewer than the budget means "
      "a parallel region lost its f/g operator (silently wrong gradients "
      "or a dead region); more means extra model-axis traffic smuggled "
      "into every step — and ANY model-axis all-gather means the "
      "vocab-scale logits gather the parallel-vocab cross-entropy "
      "removed crept back. The budget comes from the trainer's TP model "
      "(4/block + 2 with the vocab-parallel embedding; the batch-shaped "
      "CE stats counted iff they clear the census floor), never "
      "hard-coded (parallel/collectives.py copy_to_tp / reduce_from_tp / "
      "tp_parallel_cross_entropy; ISSUEs 13 + 16).")
def check_tp_psum_signature(a: StepArtifacts) -> List[Finding]:
    if not a.tp_engaged:
        return []
    if not a.tp_expected_psums:
        return [Finding(
            "tp-psum-signature",
            "explicit-TP config evaluated without a model-axis collective "
            "budget (tp_expected_psums=0) — the evaluator must snapshot "
            "Trainer.tp_expected_model_collectives", a.name)]
    census = grad_sync_census(a.optimized_text, a.min_elements)
    psums = sum(r["count"] for r in census["rows"]
                if r["op"] == "all-reduce"
                and a.collective_axis(r) == "model")
    gathers = sum(r["count"] for r in census["rows"]
                  if r["op"] == "all-gather"
                  and a.collective_axis(r) == "model")
    # the CE stats (pmax + stacked psum, one shared size class) are
    # visible only when their batch-shaped operands clear the floor
    ce_visible = 2 if a.tp_ce_stat_elements >= a.min_elements else 0
    expected_psums = a.tp_expected_psums + ce_visible
    out = []
    if psums != expected_psums:
        out.append(Finding(
            "tp-psum-signature",
            f"step carries {psums} model-axis all-reduce(s), expected "
            f"exactly {expected_psums} ({a.tp_expected_psums} structural: "
            "one per residual join forward + its backward mirror per "
            "parallel region, +2 for the vocab-parallel embedding when "
            f"engaged; +{ce_visible} parallel-vocab CE stats at "
            f"{a.tp_ce_stat_elements} elements vs floor "
            f"{a.min_elements})", a.name))
    if gathers != a.tp_expected_model_gathers:
        out.append(Finding(
            "tp-psum-signature",
            f"step carries {gathers} model-axis all-gather(s), expected "
            f"exactly {a.tp_expected_model_gathers} — the parallel-vocab "
            "cross-entropy computes the loss from local logit columns; "
            "a vocab-scale model-axis gather is the regression it "
            "replaced", a.name))
    return out


@rule("fsdp-gather-rides-data-only", "hlo",
      "under TP x FSDP every param gather/scatter rides the data axes "
      "only — nothing spans the model axis or the whole mesh",
      "the 1/M wire reduction IS the composition's win: each model shard "
      "gathers/scatters only its local parameter slice over its data "
      "replicas. A collective grouped over (data x model) — or an extra "
      "model-axis gather beyond the logits budget — means the layout "
      "regressed to full-parameter traffic while the flag claims the "
      "division (training/loop.py _fsdp_step; ISSUE 13).")
def check_fsdp_gather_rides_data_only(a: StepArtifacts) -> List[Finding]:
    if not a.tp_engaged:
        return []
    census = grad_sync_census(a.optimized_text, a.min_elements)
    out = []
    spanning = [(r["op"], r["result_shape"]) for r in census["rows"]
                if r["op"] in ("all-gather", "reduce-scatter", "all-to-all")
                and a.collective_axis(r) in ("all", "other", "unknown")]
    if spanning:
        out.append(Finding(
            "fsdp-gather-rides-data-only",
            f"{len(spanning)} gradient/param-sized collective(s) ride "
            f"groups spanning beyond one axis: {spanning[:5]} — the FSDP "
            "wire must stay on the data axes (model-axis traffic is the "
            "TP psum/logits budget only)", a.name))
    model_movers = [(r["op"], r["result_shape"]) for r in census["rows"]
                    if r["op"] in ("reduce-scatter", "all-to-all")
                    and a.collective_axis(r) == "model"]
    if model_movers:
        out.append(Finding(
            "fsdp-gather-rides-data-only",
            f"{len(model_movers)} gradient-sized reduce-scatter/"
            f"all-to-all(s) ride the MODEL axis: {model_movers[:5]} — "
            "param/grad movement belongs on the data axes", a.name))
    return out


# Entry parameters the compiled module keeps fully replicated:
# `%param = f32[...] parameter(k), sharding={replicated}`. Index the shape
# from the same line so the check needs no cross-line state.
_REPLICATED_ENTRY_PARAM_RE = re.compile(
    r"=\s*(\S+\[[\d,]*\][^ ]*)\s+parameter\(\d+\)[^\n]*"
    r"sharding=\{replicated\}")


@rule("fsdp-no-full-param-residency", "hlo",
      "no parameter/moment-sized buffer is replicated at rest under "
      "explicit FSDP",
      "dividing at-rest parameter+moment memory by the DP degree is the "
      "mode's whole point; a replicated param input in the lowered module "
      "(or a replicated live buffer on the state) means the step is "
      "paying full residency while the flag claims the division — the "
      "zero1-sharded-state argument extended to the parameters "
      "themselves.")
def check_fsdp_no_full_param_residency(a: StepArtifacts) -> List[Finding]:
    if not a.fsdp_engaged:
        return []
    out = []
    for label, buffers in (("parameter", a.replicated_param_buffers),
                           ("optimizer-state", a.replicated_state_buffers)):
        if buffers:
            rows = ", ".join(f"{p} ({n} elements)" for p, n in buffers[:5])
            more = len(buffers) - 5
            out.append(Finding(
                "fsdp-no-full-param-residency",
                f"{len(buffers)} {label} buffer(s) >= {a.min_elements} "
                f"elements are fully replicated under fsdp_explicit: "
                f"{rows}" + (f" (+{more} more)" if more > 0 else ""),
                a.name))
    # the lowered-module read: entry parameters the compiled step takes as
    # REPLICATED operands at gradient/param scale (the live-state read
    # above can miss a layout the compiler re-materializes)
    big = [m.group(1) for m in
           _REPLICATED_ENTRY_PARAM_RE.finditer(a.optimized_text)
           if hlo_result_elements(m.group(1)) >= a.min_elements]
    if big:
        out.append(Finding(
            "fsdp-no-full-param-residency",
            f"compiled fsdp step takes {len(big)} replicated entry "
            f"parameter(s) at gradient/param scale: {big[:5]}", a.name))
    return out


@rule("donated-buffers-elided", "hlo",
      "donate_state really aliases input and output buffers",
      "a step that copies the full parameters instead of updating them "
      "in place doubles peak HBM; donation must survive to the optimized "
      "module's input_output_alias table, not just the jit argnums.")
def check_donation(a: StepArtifacts) -> List[Finding]:
    if not a.config.get("donate_state", True):
        return []
    # An engaged alias table prints entries like
    # `input_output_alias={ {0}: (0, {1}, may-alias), ... }`; a module that
    # kept no donation prints no table at all (an empty `{ }` never has the
    # inner `{index}` tuple key).
    if not re.search(r"input_output_alias=\{\s*\{", a.optimized_text):
        return [Finding(
            "donated-buffers-elided",
            "donate_state=True but the optimized module carries no "
            "input_output_alias entries — the update copies the full "
            "parameter buffers instead of reusing them", a.name)]
    return []


# The Pallas/Mosaic lowering marker on TPU: pallas_call compiles to a
# custom-call whose target names the Mosaic kernel. CPU interpreter mode
# inlines the kernel as ordinary HLO — no custom-call exists there, so the
# rule below only binds on TPU artifacts.
_PALLAS_CUSTOM_CALL_RE = re.compile(
    r'custom_call_target="(?:tpu_custom_call|[Mm]osaic[^"]*)"')

# The codec kernels' pallas_call names (ops/quantize.py) — they flow into
# the custom-call's op_name metadata / Mosaic module name, which is how a
# quantize custom-call is told apart from any OTHER Pallas kernel in the
# same step (flash/ring attention lowers to the same tpu_custom_call
# target; its presence must not vouch for the codec's).
_QUANTIZE_KERNEL_NAMES = ("fused_quantize_int8_rows",
                          "fused_dequant_sum_rows")


@rule("fused-quantize-kernel-present", "hlo",
      "a fused_quantize int8 config really lowers Pallas custom-calls",
      "the fused codec's win is ONE VMEM pass per quantize/dequant stage; "
      "if the Pallas kernels silently fail to lower (a gate regression, an "
      "import fallback) the step quietly runs the XLA-composed chain while "
      "the config claims the kernel path — the same silent-fallback class "
      "compressed-wire guards for the wire dtype (ops/quantize.py).")
def check_fused_quantize_kernel(a: StepArtifacts) -> List[Finding]:
    if a.wire_mode not in ("int8", "int8_multihop", "int8_hier"):
        return []  # no int8 codec in the step — nothing to fuse
    if not (a.grad_sync_engaged or a.zero1_engaged or a.fsdp_engaged):
        return []  # passthrough config: the codec never runs
    if a.wire_mode == "int8_hier" and not a.hier_engaged:
        return []  # slices=1 passthrough — see check_compressed_wire
    fused = a.config.get("fused_quantize")
    if fused is None and a.backend == "tpu":
        # auto (the production default): resolve the tri-state exactly the
        # way the codec does at trace time — on TPU auto selects the
        # kernels unless the env override pins them off. Abstaining on
        # auto would leave the DEFAULT configuration unguarded, the one
        # place the silent-fallback class this rule exists for ships from.
        try:
            from ..ops.quantize import resolve_fused
            fused = resolve_fused(None)
        except Exception:  # pragma: no cover - pallas import unavailable
            fused = False
    if not fused:
        return []
    if a.backend != "tpu":
        # interpreter mode inlines the kernels as plain HLO ops — there is
        # no custom-call to assert; the numerics are pinned by the parity
        # tests instead (tests/test_quantize.py)
        return []
    calls = [ln for ln in a.optimized_text.splitlines()
             if _PALLAS_CUSTOM_CALL_RE.search(ln)]
    if not calls:
        return [Finding(
            "fused-quantize-kernel-present",
            "fused_quantize=True on an int8 wire, but the optimized HLO "
            "contains no Pallas/Mosaic custom-call (tpu_custom_call) — "
            "the fused codec kernels did not lower; the step is running "
            "the XLA-composed chain while claiming the kernel path",
            a.name)]
    if any(name in ln for ln in calls for name in _QUANTIZE_KERNEL_NAMES):
        return []
    # Custom-calls exist but none is named as a codec kernel. Only treat
    # that as a violation when this HLO render demonstrably carries kernel
    # identity (op_name metadata) on those lines — a metadata-stripped
    # dump can't distinguish kernels, so presence has to suffice there.
    if any('op_name="' in ln for ln in calls):
        return [Finding(
            "fused-quantize-kernel-present",
            "fused_quantize=True on an int8 wire: the optimized HLO has "
            "Pallas/Mosaic custom-calls, but none is a quantize codec "
            "kernel (fused_quantize_int8_rows / fused_dequant_sum_rows) — "
            "another Pallas kernel (e.g. flash attention) is masking a "
            "silent fallback of the codec to the XLA-composed chain",
            a.name)]
    return []


# Host-transfer markers in optimized HLO: async transfers flagged
# is_host_transfer, infeed/outfeed ops, and python-callback custom calls
# (jax.debug.print / pure_callback / io_callback lower to these).
_HOST_TRANSFER_RE = re.compile(
    r"is_host_transfer=true"
    r"|\b(?:infeed|outfeed)(?:-start|-done)?[.\w]*\("
    r"|custom_call_target=\"[^\"]*(?:callback|host_|HostCallback)[^\"]*\"")


# one alias-table entry looks like `{3}: (31, {}, may-alias)`; counting the
# `{out}: (param` heads counts aliased buffers
_ALIAS_ENTRY_RE = re.compile(r"\{\d+\}:\s*\(\d+")


@rule("paged-pool-donated", "hlo",
      "the paged decode step aliases EVERY page-pool buffer in place",
      "the slot engine's shared decode step donates the whole paged KV "
      "pool (serving/continuous.py lower_paged_decode): 2 layer-stacked "
      "buffers fp32 (k/v pages), 4 int8 (codes + scales). Any "
      "pool leaf out of the alias table is copied on EVERY generated "
      "token for EVERY slot — and the copy is pool-sized, not slot-sized, "
      "so the tax scales with the whole fleet's cache, exactly what "
      "paging exists to avoid. The presence-only donation rule cannot "
      "see one dropped leaf; this rule counts the table against the "
      "pool's leaf census (``paged_cache_leaves``).")
def check_paged_pool_donated(a: StepArtifacts) -> List[Finding]:
    if not a.config.get("serving_paged"):
        return []
    expect = int(a.config.get("paged_cache_leaves", 0))
    m = re.search(r"input_output_alias=\{(.*?\))\s*\}", a.optimized_text,
                  re.DOTALL)
    entries = len(_ALIAS_ENTRY_RE.findall(m.group(1))) if m else 0
    if entries < expect:
        return [Finding(
            "paged-pool-donated",
            f"paged decode step aliases {entries} of the >= {expect} "
            "pool buffers (k/v pages + int8 scales + slot control) — the "
            "un-aliased ones are copied pool-wide on every generated "
            "token", a.name)]
    return []


@rule("spec-verify-donated", "hlo",
      "the speculative verify step aliases the page pool AND every slot "
      "control buffer in place",
      "the K+1-window verify step replaces the plain decode step in every "
      "speculative round (serving/speculative.py lower_spec_verify) and "
      "donates pool + control exactly like it — but it also RETURNS an "
      "extra per-slot n_emit output, and an output-order slip there would "
      "silently knock donated buffers out of the alias table: every round "
      "would then copy the pool (pool-sized, fleet-wide — the tax paging "
      "exists to avoid) while the presence-only donation rule stays "
      "green. This rule counts the alias table against the FULL donated "
      "census (``spec_cache_leaves`` = pool leaves + control leaves), so "
      "the n_emit side output must cost zero entries.")
def check_spec_verify_donated(a: StepArtifacts) -> List[Finding]:
    if not a.config.get("serving_spec"):
        return []
    expect = int(a.config.get("spec_cache_leaves", 0))
    m = re.search(r"input_output_alias=\{(.*?\))\s*\}", a.optimized_text,
                  re.DOTALL)
    entries = len(_ALIAS_ENTRY_RE.findall(m.group(1))) if m else 0
    if entries < expect:
        return [Finding(
            "spec-verify-donated",
            f"speculative verify step aliases {entries} of the "
            f">= {expect} donated buffers (k/v pool + slot control) — "
            "the un-aliased ones are copied on every verify round",
            a.name)]
    return []


@rule("elastic-reshard-census", "hlo",
      "a resharded N->M state's train step carries exactly the clean-at-M "
      "collective census",
      "the elastic reshard promises a pure re-slice: same avals, same "
      "shardings, same compiled step. A leaf landed replicated (or in any "
      "off-canonical layout) makes XLA insert extra data movement into "
      "EVERY post-resize step while the resize claims zero overhead — "
      "this pins the resharded lowering to the clean-at-M census, op by "
      "op and shape by shape (resilience/elastic.py; ISSUE 11).")
def check_elastic_reshard_census(a: StepArtifacts) -> List[Finding]:
    if not a.config.get("elastic_reshard"):
        return []
    return _elastic_census_findings(a, "elastic-reshard-census",
                                    "clean-at-M")


def _elastic_census_findings(a: StepArtifacts, rule_name: str,
                             clean_noun: str) -> List[Finding]:
    """The shared census pin of both elastic directions: the resharded
    state's lowered step must carry EXACTLY the clean-world census
    (``elastic_expected_census``, embedded by the evaluator)."""
    expected = a.config.get("elastic_expected_census")
    if expected is None:
        return [Finding(
            rule_name,
            f"elastic config evaluated without a {clean_noun} expected "
            "census — the evaluator must lower the clean state and "
            "snapshot its collective_census", a.name)]
    got = collective_census(a.optimized_text)

    def keyed(rows):
        return {(r["op"], r["result_shape"], r.get("replica_groups", "")):
                r["count"] for r in rows}

    got_k, want_k = keyed(got), keyed(expected)
    if got_k != want_k:
        extra = {k: v for k, v in got_k.items()
                 if v != want_k.get(k, 0)}
        missing = {k: v for k, v in want_k.items()
                   if v != got_k.get(k, 0)}
        return [Finding(
            rule_name,
            "resharded step's collective census differs from the "
            f"{clean_noun} census — resharded-only/changed: {extra}; "
            f"clean-only/changed: {missing}. The reshard smuggled data "
            "movement into (or dropped it from) the step", a.name)]
    return []


@rule("elastic-grow-census", "hlo",
      "a grown M->N state's train step carries exactly the clean-at-N "
      "collective census",
      "the GROW leg of the elastic contract (ISSUE 12): a state resharded "
      "UP when preempted capacity returns (zero-extended flat shards, "
      "zero-extended EF rows) must lower to EXACTLY the census a "
      "clean-at-N state lowers to — a grow that lands a leaf replicated "
      "or off-layout would smuggle data movement into every post-grow "
      "step while the resize claims a pure re-slice "
      "(resilience/capacity.py + supervisor._maybe_grow).")
def check_elastic_grow_census(a: StepArtifacts) -> List[Finding]:
    if not a.config.get("elastic_grow"):
        return []
    return _elastic_census_findings(a, "elastic-grow-census",
                                    "clean-at-N")


@rule("no-host-transfer", "hlo",
      "no host transfers inside the compiled step",
      "a host callback or infeed/outfeed in the step serializes the device "
      "on the host every iteration — the .item()-per-step bottleneck the "
      "loop design removed (training/loop.py), reintroduced invisibly.")
def check_no_host_transfer(a: StepArtifacts) -> List[Finding]:
    hits = sorted({m.group(0).strip() for m in
                   _HOST_TRANSFER_RE.finditer(a.optimized_text)})
    if hits:
        return [Finding(
            "no-host-transfer",
            f"compiled step contains host transfers: {hits}", a.name)]
    return []


@rule("dp-sync-present", "hlo",
      "the plain data-parallel step really carries gradient-sized sync",
      "every other census bound is vacuous if the floor is above the "
      "model's gradient traffic — the dp arm proves the instrument sees "
      "the all-reduce DDP's reducer would issue.")
def check_dp_sync_present(a: StepArtifacts) -> List[Finding]:
    if (a.zero1_engaged or a.grad_sync_engaged or a.fsdp_engaged
            or a.n_shards <= 1
            or int(a.config.get("grad_accum", 1)) > 1
            # serving steps carry no gradients at all — this rule's floor
            # guard is about the TRAIN step's reducer, not a scoping knob
            # to relax: an inference forward with an all-reduce would be
            # the bug, not the absence of one
            or a.config.get("serving_paged")
            or a.config.get("serving_spec")):
        # grad-accum keeps sync inside a scan; count it only on the plain arm
        return []
    census = weight_update_census(a.optimized_text, a.min_elements)
    if census["all-reduce"] == 0:
        return [Finding(
            "dp-sync-present",
            f"data-parallel step shows no gradient-sized all-reduce — the "
            f"census floor (min_elements={a.min_elements}) is above the "
            "model's gradient transfers, or gradient sync vanished",
            a.name)]
    return []


def check_artifacts(a: StepArtifacts,
                    rules: Optional[List[str]] = None) -> List[Finding]:
    """Run every (selected) HLO rule over one config's artifacts."""
    from .contracts import iter_rules

    findings: List[Finding] = []
    for r in iter_rules(kind="hlo", names=rules):
        findings.extend(r.check(a))
    return findings


# ---------------------------------------------------------------------------
# Contract evaluation (lower the canonical matrix on the local mesh)
# ---------------------------------------------------------------------------


def _tiny_lm_setup(mesh, config: Dict[str, Any]):
    """(trainer, state, batch) for the tiny contract model — small enough
    that the full matrix lowers on the CPU test mesh in well under a
    minute, big enough that every leaf clears the census floor."""
    import jax
    import numpy as np

    from ..models.gpt2 import GPT2LMHead
    from ..parallel import shard_batch
    from ..training import TrainConfig, Trainer
    from ..training.optim import sgd
    from ..training.tasks import LanguageModelingTask

    seq, vocab = 16, 64
    trainer = Trainer(LanguageModelingTask(), mesh,
                      TrainConfig(seed=0, **config))
    state = trainer.init_state(
        GPT2LMHead(vocab_size=vocab, hidden_dim=32, depth=2, num_heads=2,
                   max_position=seq),
        np.zeros((1, seq), np.int32), sgd(0.1), jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    n = 2 * mesh.size
    batch = shard_batch(
        {"input_ids": rng.randint(0, vocab, (n, seq)).astype(np.int32),
         "weight": np.ones(n, np.float32)}, mesh)
    return trainer, state, batch


def replicated_large_buffers(tree: Any, min_elements: int
                             ) -> Tuple[Tuple[str, int], ...]:
    """(path, size) of committed array leaves >= min_elements whose sharding
    is fully replicated — the zero1-sharded-state rule's input."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        sharding = getattr(leaf, "sharding", None)
        size = getattr(leaf, "size", 0)
        if sharding is None or size < min_elements:
            continue
        if sharding.is_fully_replicated:
            out.append((jax.tree_util.keystr(path), int(size)))
    return tuple(out)


def paged_serving_artifacts(engine, name: str = "serving_paged"
                            ) -> StepArtifacts:
    """StepArtifacts of a SlotEngine's shared paged decode step — the
    serving sibling of the train-step snapshot. ``paged_cache_leaves``
    is the page pool's donated-leaf census — the pool is stacked across
    layers (models/layers.py PagedKV), so it is 2 buffers fp32 (k/v
    pages), 4 int8 (k/v codes + k/v scales), regardless of depth — and
    `paged-pool-donated` demands the WHOLE pool aliased, scales included:
    a dropped scale buffer silently doubles int8 pool traffic."""
    import jax

    from ..parallel.mesh import batch_shard_count

    lowered = engine.lower_paged_decode()
    optimized = lowered.compile().as_text()
    try:
        preopt = preopt_hlo_text(lowered)
    except Exception:  # pragma: no cover - backend without HLO dialect
        preopt = None
    pool_leaves = 4 if engine.config.kv_dtype == "int8" else 2
    return StepArtifacts(
        name=name,
        optimized_text=optimized,
        preopt_text=preopt,
        config={"serving_paged": True, "donate_state": True,
                "paged_cache_leaves": pool_leaves},
        n_shards=batch_shard_count(engine.mesh),
        backend=jax.default_backend(),
    )


def spec_serving_artifacts(engine, name: str = "serving_spec"
                           ) -> StepArtifacts:
    """StepArtifacts of a SpeculativeEngine's K+1-window verify step —
    the speculative sibling of `paged_serving_artifacts`.
    ``spec_cache_leaves`` is the FULL donated census: the fp32 pool's 2
    layer-stacked buffers plus every slot-control leaf — the verify step
    returns an extra (rows,) n_emit output, and `spec-verify-donated`
    demands that side output cost the alias table nothing."""
    import jax

    from ..parallel.mesh import batch_shard_count

    lowered = engine.lower_spec_verify()
    optimized = lowered.compile().as_text()
    try:
        preopt = preopt_hlo_text(lowered)
    except Exception:  # pragma: no cover - backend without HLO dialect
        preopt = None
    leaves = 2 + len(engine._control)
    return StepArtifacts(
        name=name,
        optimized_text=optimized,
        preopt_text=preopt,
        config={"serving_spec": True, "donate_state": True,
                "spec_cache_leaves": leaves},
        n_shards=batch_shard_count(engine.mesh),
        backend=jax.default_backend(),
    )


def evaluate_paged_serving_contract(contract: Contract,
                                    mesh=None) -> StepArtifacts:
    """The ``kind="serving_paged"`` evaluator: build the tiny contract
    model behind the REAL continuous-batching path (serving/continuous.py
    SlotEngine), lower the shared paged decode step, and snapshot its
    artifacts. The matrix entry pins the int8 arm
    (``paged_kv_dtype="int8"``) because that is the path with the most
    leaves to drop from the alias table — codes AND scales per block —
    and the fp32 arm's table is a strict subset of it."""
    import jax
    import numpy as np

    from ..models.gpt2 import GPT2LMHead
    from ..parallel.mesh import MeshSpec, batch_shard_count, build_mesh
    from ..serving.continuous import SlotEngine
    from ..serving.paged import PagedServeConfig

    if mesh is None:
        mesh = build_mesh(MeshSpec(), devices=jax.devices())
    n_shards = batch_shard_count(mesh)
    if n_shards < contract.min_shards:
        raise ValueError(
            f"contract {contract.name!r} needs >= {contract.min_shards} "
            f"batch shards (got {n_shards})")
    model = GPT2LMHead(vocab_size=64, hidden_dim=32, depth=2, num_heads=2,
                       max_position=32)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    cfg = PagedServeConfig(
        buckets=(8,), rows=4, max_new_tokens=4, page_size=4,
        kv_dtype=contract.config.get("paged_kv_dtype", "fp32"))
    engine = SlotEngine(model, mesh, cfg, params)
    artifacts = paged_serving_artifacts(engine, name=contract.name)
    return dataclasses.replace(
        artifacts, config={**artifacts.config, **contract.config,
                           "paged_cache_leaves":
                           artifacts.config["paged_cache_leaves"]},
        min_elements=contract.min_elements)


def evaluate_spec_serving_contract(contract: Contract,
                                   mesh=None) -> StepArtifacts:
    """The ``kind="serving_spec"`` evaluator: tiny target + even tinier
    draft behind the REAL speculative path (serving/speculative.py
    SpeculativeEngine), lower the K+1-window verify step, snapshot its
    artifacts. fp32 pool by construction — the engine refuses int8 (the
    exactness gate), so unlike `serving_paged` there is no int8 arm to
    pin; the census here is pool + full control."""
    import jax
    import numpy as np

    from ..models.gpt2 import GPT2LMHead
    from ..parallel.mesh import MeshSpec, batch_shard_count, build_mesh
    from ..serving.paged import PagedServeConfig
    from ..serving.speculative import SpeculativeEngine

    if mesh is None:
        mesh = build_mesh(MeshSpec(), devices=jax.devices())
    n_shards = batch_shard_count(mesh)
    if n_shards < contract.min_shards:
        raise ValueError(
            f"contract {contract.name!r} needs >= {contract.min_shards} "
            f"batch shards (got {n_shards})")
    # smallest config that still exercises the full alias table: the
    # donated census (pool + control leaves) is independent of depth /
    # width / rows / K, and the verify-window compile is the eval's
    # wall cost — this runs on every full-matrix pass in tier-1
    model = GPT2LMHead(vocab_size=64, hidden_dim=16, depth=1, num_heads=2,
                       max_position=32)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    draft = GPT2LMHead(vocab_size=64, hidden_dim=16, depth=1, num_heads=2,
                       max_position=32)
    draft_params = draft.init(jax.random.PRNGKey(1),
                              np.zeros((1, 8), np.int32),
                              train=False)["params"]
    cfg = PagedServeConfig(buckets=(8,), rows=2, max_new_tokens=2,
                           page_size=4)
    engine = SpeculativeEngine(model, mesh, cfg, params, draft,
                               draft_params, spec_k=1)
    artifacts = spec_serving_artifacts(engine, name=contract.name)
    return dataclasses.replace(
        artifacts, config={**artifacts.config, **contract.config,
                           "spec_cache_leaves":
                           artifacts.config["spec_cache_leaves"]},
        min_elements=contract.min_elements)


def evaluate_elastic_contract(contract: Contract,
                              mesh=None) -> StepArtifacts:
    """The ``kind="elastic"`` evaluator (ISSUEs 11 + 12), both
    directions. SHRINK (``elastic_reshard``): build the tiny contract
    state at the FULL world N, reshard it down to M = N/2 through the
    real elastic path (resilience.elastic.reshard_train_state — the same
    code a Supervisor resize runs), lower the M-world trainer's step on
    the resharded state, and snapshot its artifacts with the clean-at-M
    census embedded as the expectation (``elastic_expected_census``).
    GROW (``elastic_grow``): the mirror — build at M = N/2, reshard UP to
    N (zero-extended shards/EF rows, the capacity-return resize), lower
    the N-world trainer's step, expect the clean-at-N census. jit
    lowering keys on avals + shardings only, so census equality holds iff
    the reshard landed every leaf in the canonical target-world layout."""
    import jax

    from ..parallel.mesh import MeshSpec, batch_shard_count, build_mesh
    from ..resilience.elastic import reshard_train_state

    if mesh is None:
        mesh = build_mesh(MeshSpec(), devices=jax.devices())
    n = batch_shard_count(mesh)
    if n < contract.min_shards:
        raise ValueError(
            f"contract {contract.name!r} needs >= {contract.min_shards} "
            f"batch shards (got {n}) — the halved world must still "
            "engage the sharded update")
    m = n // 2
    sub_mesh = build_mesh(MeshSpec(),
                          devices=list(mesh.devices.flat)[:m])
    train_cfg = {k: v for k, v in contract.config.items()
                 if k not in ("elastic_reshard", "elastic_grow")}
    grow = bool(contract.config.get("elastic_grow"))
    trainer_n, state_n, batch_n = _tiny_lm_setup(mesh, train_cfg)
    trainer_m, state_m, batch_m = _tiny_lm_setup(sub_mesh, train_cfg)
    if grow:
        resharded = reshard_train_state(state_m, m, n, trainer_n, state_n)
        clean_trainer, clean_state, batch = trainer_n, state_n, batch_n
        out_shards = n
    else:
        resharded = reshard_train_state(state_n, n, m, trainer_m, state_m)
        clean_trainer, clean_state, batch = trainer_m, state_m, batch_m
        out_shards = m
    key = jax.random.PRNGKey(1)
    clean_text = clean_trainer._train_step.lower(
        clean_state, batch, key).compile().as_text()
    lowered = clean_trainer._train_step.lower(resharded, batch, key)
    optimized = lowered.compile().as_text()
    try:
        preopt = preopt_hlo_text(lowered)
    except Exception:  # pragma: no cover - backend without HLO dialect
        preopt = None
    return StepArtifacts(
        name=contract.name,
        optimized_text=optimized,
        preopt_text=preopt,
        config={**contract.config,
                "elastic_expected_census": collective_census(clean_text)},
        n_shards=out_shards,
        min_elements=contract.min_elements,
        backend=jax.default_backend(),
    )


def evaluate_contract(contract: Contract, mesh=None) -> StepArtifacts:
    """Lower + compile one contract's config on `mesh` (default: a pure-DP
    mesh over all local devices) and snapshot the artifacts the rules read.

    Raises ValueError when the mesh has fewer batch shards than the
    contract needs (zero1/grad_sync are identity passthroughs there —
    evaluating the contract would vacuously pass; the caller decides
    whether that is a skip or an error). ``kind="serving_paged"``
    contracts route to `evaluate_paged_serving_contract` (the SlotEngine's
    shared paged decode step instead of a Trainer step);
    ``kind="serving_spec"`` to
    `evaluate_spec_serving_contract` (the speculative K+1-window verify
    step); ``kind="elastic"`` to `evaluate_elastic_contract`
    (the resharded-vs-clean census pin).
    """
    import jax

    from ..parallel.grad_sync import build_bucket_plan
    from ..parallel.mesh import MeshSpec, batch_shard_count, build_mesh

    if contract.kind == "serving_paged":
        return evaluate_paged_serving_contract(contract, mesh=mesh)
    if contract.kind == "serving_spec":
        return evaluate_spec_serving_contract(contract, mesh=mesh)
    if contract.kind == "elastic":
        return evaluate_elastic_contract(contract, mesh=mesh)
    if mesh is None:
        spec = (MeshSpec.parse(contract.mesh_spec) if contract.mesh_spec
                else MeshSpec())
        mesh = build_mesh(spec, devices=jax.devices())
    n_shards = batch_shard_count(mesh)
    if n_shards < contract.min_shards:
        raise ValueError(
            f"contract {contract.name!r} needs >= {contract.min_shards} "
            f"batch shards (got {n_shards}) — on fewer, the mode is an "
            "identity passthrough and the contract is vacuous")
    trainer, state, batch = _tiny_lm_setup(mesh, contract.config)
    lowered = trainer._train_step.lower(state, batch, jax.random.PRNGKey(1))
    optimized = lowered.compile().as_text()
    try:
        preopt = preopt_hlo_text(lowered)
    except Exception:  # pragma: no cover - backend without HLO dialect
        preopt = None
    plan = build_bucket_plan(state.params,
                             float(contract.config.get("bucket_cap_mb", 0.0)))
    is_fsdp = bool(contract.config.get("fsdp_explicit"))
    replicated = (replicated_large_buffers(state.opt_state,
                                           contract.min_elements)
                  if (contract.config.get("zero1") or is_fsdp) else ())
    replicated_params = (replicated_large_buffers(state.params,
                                                  contract.min_elements)
                        if is_fsdp else ())
    group_sizes = (trainer._fsdp_plan.padded_group_sizes
                   if is_fsdp and trainer._fsdp_plan is not None else ())
    tp_psums, tp_gathers = trainer.tp_expected_model_collectives()
    return StepArtifacts(
        name=contract.name,
        optimized_text=optimized,
        preopt_text=preopt,
        config=dict(contract.config),
        n_shards=n_shards,
        total_grad_bytes=plan.total_bytes,
        min_elements=contract.min_elements,
        replicated_state_buffers=replicated,
        replicated_param_buffers=replicated_params,
        layer_group_padded_sizes=group_sizes,
        backend=jax.default_backend(),
        model_shards=trainer._tp_n,
        tp_expected_psums=tp_psums,
        tp_expected_model_gathers=tp_gathers,
        # _tiny_lm_setup batches 2 rows per device over n_shards shards,
        # seq 16 — the same shapes the lowering above traced
        tp_ce_stat_elements=trainer.tp_expected_ce_stat_elements(
            2 * mesh.size // max(n_shards, 1), 16),
        slice_shards=(trainer._hier.n_slices if trainer._hier is not None
                      else 1),
    )


def run_contract_matrix(contracts=None, mesh=None, rules=None):
    """Evaluate the canonical matrix; returns (findings, statuses) where
    statuses maps contract name -> "pass" | "fail" | "skipped (...)".
    Skips (not enough shards for a mode to engage) are reported, never
    silently dropped — a matrix that quietly checked nothing would be the
    checker's own contract violation."""
    from .contracts import CONTRACT_MATRIX

    findings: List[Finding] = []
    statuses: Dict[str, str] = {}
    for contract in (contracts if contracts is not None else CONTRACT_MATRIX):
        try:
            artifacts = evaluate_contract(contract, mesh=mesh)
        except ValueError as e:
            statuses[contract.name] = f"skipped ({e})"
            continue
        found = check_artifacts(artifacts, rules=rules)
        findings.extend(found)
        statuses[contract.name] = "fail" if found else "pass"
    return findings, statuses


# ---------------------------------------------------------------------------
# Raise-on-violation wrappers (the historical acceptance-gate API)
# ---------------------------------------------------------------------------


def verify_zero1_collectives(replicated_text: str, zero1_text: str,
                             min_elements: int = 8192) -> dict:
    """The acceptance check for the zero1 mode (ISSUE 1): in the compiled
    zero1 step, gradient-sized all-reduces are REPLACED by reduce-scatter +
    all-gather. Returns the two weight-update censuses plus a verdict dict;
    raises AssertionError naming the offending ops when the replacement did
    not happen (a silent fallback to all-reduce would erase the win while
    the flag still claims it)."""
    rep = weight_update_census(replicated_text, min_elements)
    z1 = weight_update_census(zero1_text, min_elements)
    if rep["all-reduce"] == 0:
        raise AssertionError(
            "replicated step shows no gradient-sized all-reduce — the "
            f"census floor ({min_elements} elements) is above the model's "
            "gradient transfers; lower min_elements")
    problems = []
    if z1["all-reduce"]:
        problems.append(
            f"zero1 step still contains {z1['all-reduce']} gradient-sized "
            f"all-reduce(s): {[r for r in z1['rows'] if r['op'] == 'all-reduce']}")
    if not z1["reduce-scatter"]:
        problems.append("zero1 step contains no reduce-scatter")
    if not z1["all-gather"]:
        problems.append("zero1 step contains no all-gather")
    if problems:
        raise AssertionError("; ".join(problems))
    return {"replicated": rep, "zero1": z1}


def verify_grad_sync_collectives(
    optimized_text: str,
    *,
    total_grad_bytes: int,
    bucket_cap_mb: float,
    wire_dtype: str = "fp32",
    wire_text: Optional[str] = None,
    min_elements: int = 8192,
    slack: int = 2,
) -> dict:
    """The ISSUE-2 acceptance check for the bucketed reducer: the compiled
    step performs at most ``ceil(total_grad_bytes / bucket_cap) x
    collectives_per_bucket(wire_dtype) + slack`` gradient-sized collectives,
    and compressed modes put bf16/int8 on the wire. The per-bucket factor is
    1 for the single-hop wires and 2 for the DynamiQ-style multi-hop int8
    mode (``wire_dtype="int8_multihop"``: s8 reduce-scatter + requantized s8
    gather legitimately spend two collectives per bucket) — the bound is
    parameterized by wire mode, not hard-coded, so implementing the
    multi-hop form never requires relaxing the checker. ``wire_text``
    defaults to ``optimized_text``; pass the pre-optimization HLO on
    backends that promote small floats (CPU). Raises AssertionError naming
    the violation; returns the censuses.
    """
    if wire_dtype not in WIRE_MODES:
        raise ValueError(f"unknown wire mode {wire_dtype!r} "
                         f"(choose from {WIRE_MODES})")
    census = grad_sync_census(optimized_text, min_elements)
    n_buckets = expected_buckets(total_grad_bytes, bucket_cap_mb)
    per_bucket = collectives_per_bucket(wire_dtype)
    bound = n_buckets * per_bucket + slack
    if census["n_collectives"] > bound:
        raise AssertionError(
            f"bucketed step carries {census['n_collectives']} gradient-"
            f"sized collectives, more than ceil({total_grad_bytes}B / "
            f"{bucket_cap_mb}MB) x {per_bucket} ({wire_dtype}) + {slack} = "
            f"{bound}: {census['by_op']} — bucketing is not engaged (or "
            f"the census floor min_elements={min_elements} is below scalar "
            "traffic)")
    if census["n_collectives"] == 0:
        raise AssertionError(
            "no gradient-sized collectives found — the census floor "
            f"(min_elements={min_elements}) is above the model's gradient "
            "transfers; lower it")
    if wire_dtype == "int8_multihop":
        problems = _multihop_hop_problems(census)
        if problems:
            raise AssertionError(
                "; ".join(problems) + f" — census: {census['by_op']} (a "
                "single-hop codec mislabeled as multihop sails under the "
                "2/bucket budget; the hop signature is the check)")
    wire_census = (grad_sync_census(wire_text, min_elements)
                   if wire_text is not None else census)
    expect = WIRE_HLO_DTYPE[wire_dtype]
    if not wire_census["wire_dtypes"].get(expect):
        raise AssertionError(
            f"wire_dtype={wire_dtype!r} promises {expect} collective "
            f"operands on the wire, but the HLO shows "
            f"{wire_census['wire_dtypes']}")
    return {"census": census, "wire": wire_census["wire_dtypes"],
            "bound": bound}

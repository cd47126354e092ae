"""Framework for the parallelism contract checker: findings, the rule
registry, and the declarative config matrix the HLO engine evaluates.

A `Rule` is one named, documented check; `Finding` is one violation it
reports. Rules never raise on violations — they return findings, so one
`analysis check` run reports everything at once (the verify_* wrappers in
`hlo_rules` keep the old raise-on-violation behavior for callers that want
an acceptance gate, e.g. experiments/scaling.py).

A `Contract` is one canonical training config (TrainConfig kwargs plus the
floor below which collectives are metric noise). The matrix below is the
set of configs whose compiled HLO must keep its promises on every PR:
the plain data-parallel step, the zero1 sharded update, the explicit
bucketed reducer at each wire dtype (with and without grad accumulation),
and explicit full-parameter FSDP (fp32 and the fully compressed
int8_multihop wire).
`hlo_rules.evaluate_contract` lowers each on the CPU test mesh and runs
every HLO rule over the result.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

# Wire modes the contracts understand — all five are implemented
# (parallel/grad_sync.py WIRE_DTYPES). "int8_multihop" is the DynamiQ-style
# s8 reduce-scatter + requantize + s8 all-gather form: it legitimately
# spends TWO collectives per bucket, so the census bound is parameterized
# by mode instead of hard-coding 1 — the mode landed with no checker
# relaxation, exactly as this comment promised when it was a ROADMAP item.
# "int8_hier" is the two-tier topology-aware form (ISSUE 16): exact fp32
# reduce-scatter + all-gather inside the slice, the s8 multihop pair across
# slices — 4 gradient-sized collectives per bucket, classified per tier by
# the hier-tier-signature rule.
WIRE_MODES = ("fp32", "bf16", "int8", "int8_multihop", "int8_hier")

# HLO dtype each wire mode promises on gradient-sized collective operands.
# For "int8_hier" this is the SLOW-TIER promise: cross-slice gradient
# collectives ride s8; the intra-slice pair is exempt (exact fp32 by
# design — no-fp32-wire filters by tier).
WIRE_HLO_DTYPE = {"fp32": "f32", "bf16": "bf16", "int8": "s8",
                  "int8_multihop": "s8", "int8_hier": "s8"}


def collectives_per_bucket(wire_mode: str) -> int:
    """Gradient collectives one bucket legitimately costs under `wire_mode`.

    Single-hop modes sync a bucket with ONE collective (psum, or the s8
    gather). The multi-hop int8 form reduces in two hops (s8 all-to-all
    reduce-scatter, requantized s8 all-gather), so its census bound is 2
    per bucket — the contract knows the mode, the bound is never hand-
    relaxed. The hierarchical form spends 4: the exact intra-slice
    reduce-scatter and all-gather bracket the cross-slice s8 pair
    (grad_sync._int8_hier_sum).
    """
    if wire_mode not in WIRE_MODES:
        raise ValueError(f"unknown wire mode {wire_mode!r} "
                         f"(choose from {WIRE_MODES})")
    return {"int8_multihop": 2, "int8_hier": 4}.get(wire_mode, 1)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation: where, what, and which rule said so."""

    rule: str
    message: str
    location: str = ""  # "path:line" (AST) or a contract/config name (HLO)

    def __str__(self) -> str:
        loc = f"{self.location}: " if self.location else ""
        return f"{loc}[{self.rule}] {self.message}"

    def as_dict(self) -> dict:
        return {"rule": self.rule, "location": self.location,
                "message": self.message}


@dataclasses.dataclass(frozen=True)
class Rule:
    """One named check. `kind` is "hlo" (runs on StepArtifacts) or "ast"
    (runs on parsed source). `rationale` is the why — it renders in
    ``analysis check --list`` and the README catalog stays honest by
    quoting it."""

    name: str
    kind: str
    description: str
    rationale: str
    check: Callable[..., List[Finding]]


_REGISTRY: Dict[str, Rule] = {}


def rule(name: str, kind: str, description: str, rationale: str):
    """Decorator registering a check function as a named Rule."""

    def deco(fn: Callable[..., List[Finding]]):
        if name in _REGISTRY:
            raise ValueError(f"duplicate rule name {name!r}")
        _REGISTRY[name] = Rule(name=name, kind=kind, description=description,
                               rationale=rationale, check=fn)
        return fn

    return deco


def iter_rules(kind: Optional[str] = None,
               names: Optional[Iterable[str]] = None) -> List[Rule]:
    """Registered rules, optionally filtered by kind and/or names.

    Unknown names raise — a typo'd ``--rules`` selection silently checking
    nothing would be the checker failing its own contract. Importing the
    engines here (not at module import) keeps this module dependency-free
    for the AST-only path.
    """
    from . import ast_rules, concurrency_rules, hlo_rules  # noqa: F401  (registration side effect)

    if names is not None:
        wanted = list(names)
        unknown = [n for n in wanted if n not in _REGISTRY]
        if unknown:
            raise KeyError(
                f"unknown rule(s) {unknown}; known: {sorted(_REGISTRY)}")
        rules = [_REGISTRY[n] for n in wanted]
    else:
        rules = [_REGISTRY[n] for n in sorted(_REGISTRY)]
    if kind is not None:
        rules = [r for r in rules if r.kind == kind]
    return rules


@dataclasses.dataclass(frozen=True)
class Contract:
    """One canonical config whose lowered HLO must keep its promises.

    ``config`` holds TrainConfig kwargs (zero1 / bucket_cap_mb / wire_dtype
    / grad_accum / donate_state / overlap_grad_sync). ``min_elements`` is
    the census floor separating gradient-sized collectives from scalar
    metric traffic — sized to the tiny contract model, NOT the 8192 default
    of production censuses. ``min_shards`` gates configs that only engage
    on a multi-shard mesh (zero1 / grad_sync passthrough convention).
    ``kind`` selects the evaluator: "train" lowers a Trainer step
    (`hlo_rules._tiny_lm_setup`); "serving_paged" lowers the SlotEngine's
    shared paged decode step (`hlo_rules.evaluate_paged_serving_contract`,
    ISSUE 17) — the token server's decode-step contract (no host
    transfers, the page pool donated), run by the same tier-1 ``analysis
    check`` gate; "serving_spec" the speculative engine's verify step;
    "elastic" lowers the SAME train step twice at
    the target world — once from a clean state, once from a state
    resharded by resilience.elastic (down N->M for ``elastic_reshard``,
    UP M->N for ``elastic_grow``) — and pins the censuses equal
    (`hlo_rules.evaluate_elastic_contract`, ISSUEs 11 + 12).
    """

    name: str
    description: str
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    min_elements: int = 128
    min_shards: int = 1
    kind: str = "train"
    # Mesh the contract lowers on: "" = the default pure-DP mesh over all
    # local devices; the explicit TP x FSDP contracts (ISSUE 13) name a
    # 2-D spec ("data=4,model=2") parsed by parallel.mesh.MeshSpec.
    mesh_spec: str = ""


# The canonical matrix (ISSUE 3): dp, zero1, grad_sync x wire dtypes,
# grad-accum on/off. The bucket cap is tiny (in MB) so the tiny contract
# model still splits into >1 bucket and the ceil bound actually binds.
_CAP = 0.02  # ~5.2k fp32 elements per bucket

CONTRACT_MATRIX: Tuple[Contract, ...] = (
    Contract("dp", "implicit data-parallel step (XLA-inserted grad sync)"),
    Contract("dp_accum", "implicit path under gradient accumulation",
             config=dict(grad_accum=2)),
    Contract("zero1", "ZeRO-1 sharded weight update (scatter/update/gather)",
             config=dict(zero1=True), min_shards=2),
    Contract("zero1_bf16", "zero1 with the reduce-scatter half at bf16",
             config=dict(zero1=True, wire_dtype="bf16"), min_shards=2),
    Contract("zero1_int8_mh",
             "zero1 fully compressed: s8 all-to-all scatter (error "
             "feedback) + s8 delta-quantized param all-gather "
             "(quantized_delta_all_gather) — both halves off fp32",
             config=dict(zero1=True, wire_dtype="int8_multihop"),
             min_shards=2),
    Contract("gsync_fp32", "bucketed reducer, exact fp32 wire",
             config=dict(bucket_cap_mb=_CAP), min_shards=2),
    Contract("gsync_bf16", "bucketed reducer, bf16 wire",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="bf16"),
             min_shards=2),
    Contract("gsync_int8", "bucketed reducer, int8 wire + error feedback",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="int8"),
             min_shards=2),
    Contract("gsync_bf16_accum",
             "bucketed bf16 reducer with in-scan overlapped accumulation",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="bf16",
                         grad_accum=2), min_shards=2),
    Contract("gsync_int8_mh",
             "bucketed reducer, DynamiQ multi-hop int8 wire (s8 "
             "reduce-scatter + requantized s8 all-gather, 2/bucket)",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="int8_multihop"),
             min_shards=2),
    Contract("gsync_int8_mh_accum",
             "multi-hop int8 reducer with in-scan overlapped accumulation",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="int8_multihop",
                         grad_accum=2), min_shards=2),
    # Two-tier topology-aware wire (ISSUE 16) on the (slice=2, data=4)
    # factored CPU mesh: per bucket, an exact fp32 intra-slice
    # reduce-scatter, the s8 multihop pair across slices (the ONLY
    # compressed tier — EF lives there), and an exact fp32 intra-slice
    # all-gather. The hier-tier-signature rule classifies every gradient
    # collective's replica groups by tier (the PR-12 axis classifier,
    # generalized) and pins the per-tier signature; no-fp32-wire exempts
    # only the intra-slice (ici) tier.
    Contract("gsync_int8_hier",
             "bucketed reducer, two-tier hier wire: exact fp32 ICI "
             "reduce-scatter/all-gather inside the slice, s8 multihop "
             "pair across slices (4/bucket, per-tier classified)",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="int8_hier"),
             min_shards=2, mesh_spec="slice=2,data=4"),
    Contract("gsync_int8_hier_accum",
             "two-tier hier reducer with in-scan overlapped accumulation",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="int8_hier",
                         grad_accum=2), min_shards=2,
             mesh_spec="slice=2,data=4"),
    Contract("zero1_int8_hier",
             "zero1 with the two-tier wire: hier scatter (exact fast "
             "reduce-scatter + s8 cross-slice exchange w/ EF) and the s8 "
             "cross-slice + exact intra-slice param delta gather",
             config=dict(zero1=True, wire_dtype="int8_hier"),
             min_shards=2, mesh_spec="slice=2,data=4"),
    Contract("gsync_int8_mh_fused",
             "multi-hop int8 wire with the fused Pallas codec kernels "
             "(ops/quantize.py; interpreter mode on the CPU matrix — the "
             "kernel path must keep every census/wire/donation promise "
             "the XLA-composed path keeps, with no relaxation; on TPU "
             "fused-quantize-kernel-present additionally asserts the "
             "Mosaic custom-calls really lowered)",
             config=dict(bucket_cap_mb=_CAP, wire_dtype="int8_multihop",
                         fused_quantize=True), min_shards=2),
    # Explicit full-parameter FSDP (ISSUE 7): params + moments flat-sharded
    # 1/N at rest, one just-in-time param all-gather per layer group, one
    # gradient reduce-scatter per layer group back into the shard layout.
    # The fsdp-* rules bind here: gather count == layer groups, scatter
    # signature present, no full-param/moment residency at rest.
    Contract("fsdp", "explicit FSDP, exact fp32 gathers + fp32 scatter",
             config=dict(fsdp_explicit=True), min_shards=2),
    Contract("fsdp_accum",
             "explicit FSDP under gradient accumulation (per-layer "
             "scatters inside the microbatch scan; gathers stay one per "
             "layer group in the step prologue)",
             config=dict(fsdp_explicit=True, grad_accum=2), min_shards=2),
    Contract("fsdp_int8_mh",
             "explicit FSDP fully compressed: s8 per-layer gradient "
             "scatter (error feedback) + s8 param gathers "
             "(quantized_shard_all_gather) — both wire directions off "
             "fp32, per-layer census unchanged",
             config=dict(fsdp_explicit=True, wire_dtype="int8_multihop"),
             min_shards=2),
    # Explicit TP x FSDP on the 2-D ("data","model") mesh (ISSUE 13): the
    # tp-psum-signature budget binds (one megatron psum per residual join
    # + backward mirrors + the vocab-parallel embedding pair + the
    # parallel-vocab CE's two stat psums, ZERO model-axis gathers —
    # ISSUE 16 replaced the vocab-scale logits gather), every param
    # gather/scatter rides the data axes only
    # (fsdp-gather-rides-data-only), the per-layer gather/scatter census
    # holds over the TP-LOCAL layer plan, and no gradient-sized all-reduce
    # survives off the model axis. No existing rule is relaxed: 1-D
    # artifacts never consult the axis classifier. min_elements=64 (not
    # the default 128): the CE stats are (rows, seq-1, 2)-shaped — 120
    # elements at the tiny contract batch — and the gather-regression pin
    # is only as strong as the floor that lets the census SEE the head's
    # collectives.
    Contract("fsdp_tp",
             "explicit megatron TP x FSDP on data=4,model=2: model-axis "
             "psum budget + data-axis-only param wire, exact fp32",
             config=dict(fsdp_explicit=True), min_shards=2,
             min_elements=64, mesh_spec="data=4,model=2"),
    Contract("fsdp_tp_int8_mh",
             "explicit TP x FSDP fully compressed: s8 data-axis gradient "
             "scatter (EF per model shard) + s8 data-axis param gathers; "
             "model-axis activation psums stay exact fp32 by design",
             config=dict(fsdp_explicit=True, wire_dtype="int8_multihop"),
             min_shards=2, min_elements=64, mesh_spec="data=4,model=2"),
    # The token server's decode-step contract (ISSUE 17): the SlotEngine's
    # SHARED decode step — one program serving every slot at once — must
    # carry no host transfers (a callback in the decode loop stalls every
    # generated token) and must alias the ENTIRE page pool in
    # place: paged-pool-donated counts the alias table against the pool's
    # leaf census (paged_cache_leaves). Pinned on the int8 arm because it
    # has the most leaves to drop (k/v codes + k/v scales per block); a
    # missing scale buffer is invisible to the presence-only donation
    # rule but doubles int8 pool traffic on every generated token. The
    # zero-recompile-across-joins/leaves half is runtime behavior, pinned
    # by tests/test_continuous.py and asserted by `serving bench`.
    Contract("serving_paged",
             "paged int8 continuous-batching decode: no host transfers, "
             "full page pool (codes + scales) donated in place "
             "(serving/continuous.py lower_paged_decode)",
             config=dict(serving_paged=True, donate_state=True,
                         paged_kv_dtype="int8"),
             kind="serving_paged"),
    # The speculative-verify contract (ISSUE 19): the target's K+1-window
    # verify step — the program that replaces the plain decode step in
    # every speculative round — must carry no host transfers and must
    # donate pool + control EXACTLY like the plain step: a verify path
    # that copies the pool pays the per-token memory tax the paged
    # contract exists to prevent, multiplied by every round, and the
    # extra n_emit output must NOT cost the alias table an entry
    # (spec-verify-donated counts entries against the fp32 pool + control
    # leaf census). The bitwise stream-parity half is runtime behavior,
    # pinned by tests/test_speculative.py.
    Contract("serving_spec",
             "speculative K+1-window verify: no host transfers, pool + "
             "control donated in place with the n_emit side output "
             "costing no alias entry (serving/speculative.py "
             "lower_spec_verify)",
             config=dict(serving_spec=True, donate_state=True),
             kind="serving_spec"),
    # The control re-plan base contract (ISSUE 20): the config the online
    # perf tuner's candidates are evaluated AGAINST. control/apply.py
    # contract_gate overlays a candidate's overrides (wire_dtype /
    # bucket_cap_mb / overlap_grad_sync / grad_accum — tuner.TUNABLE_KEYS)
    # on this base and runs the FULL HLO rule set over the lowered
    # result; any finding (or a config that cannot even lower) refuses
    # the candidate and the run keeps its old config. The base uses the
    # explicit bucketed reducer so a candidate's bucket-cap/wire choice
    # actually changes the lowered collectives the rules see.
    Contract("control_replan",
             "base config the online tuner's candidates overlay: "
             "bucketed fp32 reducer whose every candidate override must "
             "re-pass the full rule set before apply_decision commits it",
             config=dict(bucket_cap_mb=_CAP), min_shards=2),
    # The elastic-reshard contract (ISSUE 11): a state resharded N -> M by
    # resilience.elastic must lower to EXACTLY the HLO census a clean-at-M
    # state lowers to — a reshard that lands a leaf replicated (or in any
    # off-canonical layout) would smuggle extra collectives into every
    # post-resize step while the run claims a pure re-slice. Evaluated on
    # the zero1 layout (flat-padded moments — the shapes that actually
    # change across worlds); min_shards=4 so the halved world still
    # engages the sharded update.
    Contract("elastic_reshard",
             "a reshardedN->M train step's collective census matches the "
             "clean-at-M census (no reshard-smuggled collectives)",
             config=dict(elastic_reshard=True, zero1=True),
             min_shards=4, kind="elastic"),
    # The GROW leg (ISSUE 12): the same pin in the capacity-return
    # direction — a state grown M -> N (zero-extended flat shards +
    # zero-extended EF rows, the supervisor's boundary grow) must lower
    # to EXACTLY the clean-at-N census.
    Contract("elastic_grow",
             "a grown M->N train step's collective census matches the "
             "clean-at-N census (no grow-smuggled collectives)",
             config=dict(elastic_grow=True, zero1=True),
             min_shards=4, kind="elastic"),
)


def get_contract(name: str) -> Contract:
    for c in CONTRACT_MATRIX:
        if c.name == name:
            return c
    raise KeyError(f"unknown contract {name!r}; "
                   f"known: {[c.name for c in CONTRACT_MATRIX]}")

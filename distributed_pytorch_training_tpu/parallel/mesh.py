"""Device mesh construction.

The reference has no mesh: DDP is a flat world of one-process-per-GPU over
NCCL (/root/reference/train_ddp.py:65). The TPU-native design makes the device
topology explicit as a named `jax.sharding.Mesh`; every parallelism strategy
(DP / FSDP-style / TP / SP / PP / EP) is an axis of that mesh, and a model's
PartitionSpecs say which axes each tensor dimension is split over.

Axis naming convention (used by all partition rules in `models/`):

* ``data``  — data parallelism: batch dimension sharded; gradient psum rides
              this axis (the DDP all-reduce equivalent, ref :305-310).
* ``fsdp``  — parameter/optimizer-state sharding (ZeRO-ish); batch is sharded
              over (data, fsdp) jointly, params gathered per-layer by XLA.
* ``model`` — tensor parallelism (megatron-style split of weight matrices).
* ``seq``   — sequence/context parallelism (ring attention KV rotation).
* ``pipe``  — pipeline stages.
* ``expert``— expert parallelism for MoE layers.
* ``slice`` — the slow-interconnect outer tier (ICI islands joined by DCN):
              batch is sharded over it like ``data``, but the hierarchical
              gradient wire (``--wire-dtype int8_hier``) treats collectives
              over it as expensive and compresses them (grad_sync.py).

Axis order in the physical mesh matters on TPU: `mesh_utils.create_device_mesh`
maps the *last* axes onto the tightest ICI rings, so the most
communication-hungry axes (model, seq) go last.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Canonical axis names.
DATA = "data"
FSDP = "fsdp"
MODEL = "model"
SEQ = "seq"
PIPE = "pipe"
EXPERT = "expert"
SLICE = "slice"

# The order axes are laid out in the physical mesh — bandwidth-hungry last.
# ``slice`` is OUTERMOST (most-major): linear replica ids group by slice, so
# consecutive ids share an ICI island and the hierarchical wire's "fast tier"
# replica groups are contiguous ranges (analysis/hlo_rules.py classifies
# tiers from exactly this layout).
AXIS_ORDER: tuple[str, ...] = (SLICE, PIPE, DATA, FSDP, EXPERT, SEQ, MODEL)

# The canonical axis-name registry. Code elsewhere must use the constants
# above (or AXIS_ORDER/BATCH_AXES), never the string literals: the
# `axis-name-registry` lint (analysis/ast_rules.py) flags literals in
# collective/PartitionSpec positions outside this module, and its
# import-free mirror of this set is pinned to AXIS_NAMES by a tier-1 test.
AXIS_NAMES: frozenset = frozenset(AXIS_ORDER)

# Axes a batch dimension may be sharded over (see sharding.batch_spec).
# ``slice`` is a batch axis: a multi-slice fleet runs data parallelism
# across slices, so every slice-axis size folds into the global batch.
BATCH_AXES: tuple[str, ...] = (SLICE, DATA, FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. ``-1`` on exactly one axis means "all remaining
    devices". The default is pure data parallelism — the reference's only
    strategy (SURVEY.md §2c)."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    slice: int = 1

    def resolved(self, n_devices: int) -> dict[str, int]:
        sizes = {
            SLICE: self.slice,
            PIPE: self.pipe,
            DATA: self.data,
            FSDP: self.fsdp,
            EXPERT: self.expert,
            SEQ: self.seq,
            MODEL: self.model,
        }
        bad = {k: v for k, v in sizes.items() if v < 1 and v != -1}
        if bad:
            raise ValueError(
                f"axis sizes must be >= 1 (or -1 for 'all remaining'), got {bad}")
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but {n_devices} are present"
            )
        return sizes

    @staticmethod
    def parse(text: str) -> "MeshSpec":
        """Parse ``"data=4,model=2"`` (CLI ``--mesh`` flag)."""
        valid = {f.name for f in dataclasses.fields(MeshSpec)}
        kwargs = {}
        for part in text.split(","):
            part = part.strip()
            if not part:
                continue
            k, eq, v = part.partition("=")
            k = k.strip()
            if k not in valid:
                raise ValueError(
                    f"--mesh: unknown axis {k!r}; valid axes: {sorted(valid)}"
                )
            if not eq or not v.strip().lstrip("-").isdigit():
                raise ValueError(
                    f"--mesh: expected '<axis>=<int>' pairs, got {part!r} "
                    f"(e.g. 'data=4,model=2')"
                )
            size = int(v)
            if size < 1 and size != -1:
                raise ValueError(
                    f"--mesh: axis size must be >= 1 (or -1 for 'all "
                    f"remaining devices'), got {part!r}"
                )
            kwargs[k] = size
        return MeshSpec(**kwargs)


def dcn_factors(sizes: dict, n_slices: int) -> tuple[dict, dict]:
    """Split a logical mesh shape into (per_slice, dcn) factors for a
    multi-slice pod: ``sizes[a] == per_slice[a] * dcn[a]`` and
    ``prod(dcn) == n_slices``.

    Only the latency-tolerant axes may span DCN — the explicit ``slice``
    axis first (it exists to name the DCN tier), then ``data`` (gradient
    all-reduce is once per step and overlappable), then ``pipe``
    (per-microbatch point-to-point activations are small), then ``fsdp``.
    ``model``/``seq``/``expert`` collectives are per-layer and
    bandwidth-hungry: they stay inside a slice, on ICI, always. This is the
    scaling-book recipe the reference's flat NCCL world cannot express
    (train_ddp.py:65 — one undifferentiated process group for everything)."""
    dcn = {a: 1 for a in AXIS_ORDER}
    rem = n_slices
    # callers may pass shapes without the (newer) slice axis — absent
    # axes have size 1 and cannot absorb a DCN factor
    for a in (SLICE, DATA, PIPE, FSDP):
        g = math.gcd(sizes.get(a, 1), rem)
        dcn[a] = g
        rem //= g
    if rem != 1:
        raise ValueError(
            f"mesh {sizes} cannot span {n_slices} slices: the slice count "
            f"must divide into the slice/data/pipe/fsdp axes (model/seq/"
            f"expert stay within a slice — their collectives need ICI). "
            f"E.g. for {n_slices} slices use data={n_slices}*k.")
    per = {a: sizes.get(a, 1) // dcn[a] for a in AXIS_ORDER}
    return per, dcn


def _slice_count(devices: Sequence[jax.Device]) -> int:
    ids = {getattr(d, "slice_index", None) for d in devices}
    ids.discard(None)
    return max(1, len(ids))


def _unwrap_devices(dev_array: np.ndarray) -> np.ndarray:
    """Virtual-slice proxies (testing) are only for LAYOUT — every Mesh must
    hold the real devices underneath, including on hybrid-construction
    fallback paths."""
    return np.array(
        [getattr(d, "base_device", d) for d in dev_array.flat],
        dtype=object).reshape(dev_array.shape)


class _VirtualSliceDevice:
    """A device dressed with a synthetic ``slice_index``.

    Lets the multi-slice path (``dcn_factors`` ->
    ``mesh_utils.create_hybrid_device_mesh``) run END-TO-END on hosts with
    no multi-slice hardware (CPU test meshes, the driver's dry-run).
    ``build_mesh`` unwraps ``base_device`` after the layout is computed, so
    the resulting Mesh holds real devices and executes normally."""

    def __init__(self, device, slice_index: int):
        self.base_device = device
        self.slice_index = slice_index

    def __getattr__(self, name):
        return getattr(self.base_device, name)

    def __repr__(self):
        return f"VirtualSlice({self.slice_index}, {self.base_device!r})"


def with_virtual_slices(devices: Sequence[jax.Device],
                        n_slices: int) -> list:
    """Partition `devices` into `n_slices` equal contiguous virtual slices
    (testing helper; see _VirtualSliceDevice)."""
    if len(devices) % n_slices:
        raise ValueError(
            f"{len(devices)} devices do not split into {n_slices} slices")
    per = len(devices) // n_slices
    return [_VirtualSliceDevice(d, i // per) for i, d in enumerate(devices)]


def build_mesh(
    spec: Optional[MeshSpec] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named device mesh; TPU-topology-aware when possible.

    With the default spec this produces a 1-D ``data`` mesh over all devices —
    the TPU-native equivalent of the reference's DDP world (train_ddp.py:65).

    Multi-slice pods (devices reporting distinct ``slice_index``, i.e.
    ICI islands joined by DCN) get a HYBRID mesh: ``dcn_factors`` sends the
    slice-spanning parallelism to the latency-tolerant axes and
    ``mesh_utils.create_hybrid_device_mesh`` lays devices out so every
    other axis's collectives ride ICI within a slice.
    """
    spec = spec or MeshSpec()
    if devices is None:
        devices = jax.devices()
    sizes = spec.resolved(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)

    # A layout the topology helpers refuse is an error on a TPU (a plain
    # reshape there would put DCN- or ICI-crossing collectives on the wrong
    # axes without saying so); the reshape is for CPU test meshes, whose
    # devices have no topology to respect.
    on_tpu = devices[0].platform == "tpu"
    n_slices = _slice_count(devices)
    if n_slices > 1:
        per, dcn = dcn_factors(sizes, n_slices)  # raises on un-splittable
        try:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                tuple(per[a] for a in AXIS_ORDER),
                tuple(dcn[a] for a in AXIS_ORDER),
                devices=list(devices))
            return Mesh(_unwrap_devices(dev_array), AXIS_ORDER)
        except (ValueError, AssertionError, NotImplementedError) as e:
            if on_tpu:
                raise
            logging.getLogger(__name__).warning(
                "hybrid mesh construction failed (%s); falling back to the "
                "single-slice layout (CPU test mesh)", e)

    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    except (ValueError, AssertionError, NotImplementedError):
        if on_tpu:
            raise
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(_unwrap_devices(dev_array), AXIS_ORDER)


def validate_mesh_usage(
    mesh: Mesh,
    *,
    rules=None,
    attention: str = "xla",
    is_moe: bool = False,
    pipelined: bool = False,
) -> None:
    """Reject meshes with axes the selected config cannot use.

    The reference cannot express this failure mode (DDP's world is one flat
    axis), but here ``--mesh pipe=2`` with a non-pipelined model would
    replicate all work across half the devices with no warning — devices
    silently wasted. Each check names the flag combination that would
    actually use the axis.

    ``rules`` is the model's PartitionRules (or None); an axis is "usable"
    for params only if some rule can place a dim on it.
    """
    rule_axes = rules.axes_used() if rules is not None else set()
    problems = []
    if mesh.shape[PIPE] > 1 and not pipelined:
        problems.append(
            f"pipe={mesh.shape[PIPE]} but the selected model does not run "
            "through the pipeline (use a pipelined model config, e.g. "
            "gpt2_*_pipe, or drop the pipe axis)")
    if mesh.shape[SEQ] > 1 and attention not in ("ring", "ulysses"):
        problems.append(
            f"seq={mesh.shape[SEQ]} but --attention {attention!r} does not "
            "shard the sequence (use --attention ring or ulysses)")
    if mesh.shape[EXPERT] > 1 and not is_moe:
        problems.append(
            f"expert={mesh.shape[EXPERT]} but the model has no MoE layers "
            "(use an *_moe model or drop the expert axis)")
    if mesh.shape[MODEL] > 1 and MODEL not in rule_axes:
        problems.append(
            f"model={mesh.shape[MODEL]} but the model's partition rules "
            "never use the tensor-parallel axis (ResNets ship replicated-"
            "only rules; transformers support TP)")
    if problems:
        raise ValueError(
            "mesh axes that would silently waste devices:\n  - "
            + "\n  - ".join(problems))
    if mesh.shape[FSDP] > 1 and FSDP not in rule_axes:
        # fsdp devices still do data-parallel work (batch is sharded over
        # (data, fsdp)) so this is a degradation, not a waste — warn.
        logging.getLogger(__name__).warning(
            "fsdp=%d but the model's partition rules never shard params on "
            "the fsdp axis — running as plain data parallelism (no ZeRO "
            "memory win)", mesh.shape[FSDP])


# `validate_mesh_usage` under the name the --mesh CLI threading uses
# (ISSUE 13 satellite): every --mesh consumer — train.py and the serving
# CLI — must reject axes the selected model/config cannot use LOUDLY
# instead of silently replicating work across them. A true alias (not a
# forwarding wrapper), so the two names can never drift apart.
validate_mesh = validate_mesh_usage


def batch_shard_count(mesh: Mesh) -> int:
    """Number of ways the global batch is split (product of batch axes)."""
    return int(np.prod([mesh.shape[a] for a in BATCH_AXES]))


def local_batch_size(per_device_batch: int, mesh: Mesh) -> int:
    """This host's share of the global batch.

    Preserves the reference's per-device batch semantic (train_ddp.py:27
    "mini-batch size *per GPU*"): global batch = per_device_batch x
    (#devices on batch axes); each host feeds its local slice.
    """
    local_devices = [d for d in mesh.devices.flat if d.process_index == jax.process_index()]
    num, den = per_device_batch * len(local_devices) * batch_shard_count(mesh), mesh.size
    if num % den:
        raise ValueError(
            f"batch shards ({batch_shard_count(mesh)}) do not divide evenly "
            f"across this host's {len(local_devices)} of {mesh.size} devices "
            f"at per-device batch {per_device_batch}"
        )
    return num // den

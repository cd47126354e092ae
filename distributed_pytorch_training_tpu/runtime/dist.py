"""Distributed process runtime.

TPU-native re-design of the reference's L0 layer
(/root/reference/train_ddp.py:49-73):

* ``is_distributed`` (ref :49-50) — reference reads ``WORLD_SIZE``; here a
  process is "distributed" when the JAX runtime reports >1 process (multi-host
  pod) OR when test overrides are set.
* ``setup_distributed`` (ref :53-68) — reference calls
  ``dist.init_process_group(backend="nccl", init_method="env://")`` and binds a
  CUDA device per process. On TPU there is ONE process per host (not per chip);
  ``jax.distributed.initialize()`` performs the rendezvous, and all local chips
  belong to this process. There is no per-device binding step.
* ``cleanup_distributed`` (ref :71-73) — ``jax.distributed.shutdown()``.

Environment contract
--------------------
The reference consumes ``WORLD_SIZE``/``RANK``/``LOCAL_RANK`` (the torchrun
contract, ref :61-63). The TPU pod runtime auto-discovers topology, so none of
those are required; for parity and for tests we honor optional overrides:

* ``DPT_COORDINATOR_ADDRESS`` / ``DPT_NUM_PROCESSES`` / ``DPT_PROCESS_ID`` —
  explicit multi-host rendezvous (forwarded to ``jax.distributed.initialize``).
* On GKE/Cloud TPU pods, ``jax.distributed.initialize()`` with no args works.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from pathlib import Path
from typing import Optional

import jax

logger = logging.getLogger(__name__)

_INITIALIZED = False


def cpu_requested() -> bool:
    """True iff ``JAX_PLATFORMS`` is literally ``cpu`` — the one way to ask
    for a CPU run (tests, dry-runs, the 8-device virtual mesh)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_backend() -> str:
    """THE platform rule, shared by every entry point that measures or
    trains (train.py, the serving CLI, experiments/scaling.py,
    chip_smoke.py): CPU only when asked for by name. If ``JAX_PLATFORMS``
    is literally ``cpu`` the run is a CPU run; otherwise the resolved
    backend must be ``tpu`` or this raises — a program that found no
    accelerator must never carry on quietly on the host. Returns the
    resolved backend (touching it brings the backend up)."""
    backend = jax.default_backend()
    if cpu_requested() or backend == "tpu":
        return backend
    raise RuntimeError(
        f"JAX resolved to backend {backend!r} ({len(jax.devices())}x "
        f"{jax.devices()[0].device_kind}) but JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r} did not ask for the CPU by "
        "name: no TPU was found. Set JAX_PLATFORMS=cpu for a CPU run "
        "(tests, dry-runs); otherwise fix the accelerator.")


# The warm-restart compilation cache tri-state (ISSUE 11): elastic
# resizes, supervisor restarts and serving-fleet autoscaling all pay a
# full recompile of the (re)built step without it.
#   auto (default/unset) — enable on accelerator backends only (XLA:CPU
#        reloads are unsafe, see below);
#   on   — enable regardless of backend (the operator vouches for the
#          environment; on CPU the known AOT-reload hazard applies);
#   off  — never enable (debugging stale-cache suspicion).
COMPILE_CACHE_ENV = "DPT_COMPILE_CACHE"
_COMPILE_CACHE_MODES = ("auto", "on", "off")

# Where the cache lives is decided HERE and nowhere else. jax reads
# JAX_COMPILATION_CACHE_DIR itself, so when it is set this module never
# touches ``jax_compilation_cache_dir``; when it is not, the directory is
# the fixed <checkout>/.jax_cache (the path is part of XLA's cache key —
# a directory that moves, e.g. under a temp dir, never hits).
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_mode(mode: Optional[str] = None) -> str:
    """Resolve the tri-state: explicit ``mode`` wins, else the
    ``DPT_COMPILE_CACHE`` env var, else "auto". Invalid values are a loud
    error — a typo'd "ON " silently meaning auto would be the
    silent-fallback class the analysis rules exist to kill."""
    resolved = mode if mode is not None else \
        os.environ.get(COMPILE_CACHE_ENV, "auto").strip().lower() or "auto"
    if resolved not in _COMPILE_CACHE_MODES:
        raise ValueError(
            f"{COMPILE_CACHE_ENV}={resolved!r} is not one of "
            f"{_COMPILE_CACHE_MODES}")
    return resolved


def compile_cache_dir() -> Path:
    """The one compile-cache directory: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(CACHE_DIR_ENV)
    return Path(env) if env else _CHECKOUT_CACHE_DIR


def enable_persistent_compile_cache(mode: Optional[str] = None) -> bool:
    """Turn XLA's persistent compile cache on at `compile_cache_dir`.
    Returns True iff a cache is in force afterwards. ``mode`` is the
    ``DPT_COMPILE_CACHE`` tri-state (see above; None reads the env var,
    default "auto").

    In "auto", gated on the RESOLVED backend, not env vars: XLA:CPU's
    persistent-cache reloads are unsafe here — AOT entries record pseudo
    machine features (+prefer-no-scatter/gather) that fail the feature
    match on reload, and the mismatch-loaded executables desynchronized an
    8-device collective rendezvous into a fatal abort (observed 2026-07-31
    on the virtual CPU mesh: ``cpu_aot_loader.cc`` mismatch warnings, then
    ``rendezvous.cc`` termination). A refusal where the variable already
    switched the cache on inside jax switches it back off. The verdict is
    recorded as a ``compile_cache_enabled`` telemetry counter so a
    restart-downtime A/B can attribute its win."""
    resolved = compile_cache_mode(mode)
    backend = jax.default_backend()
    from_env = bool(os.environ.get(CACHE_DIR_ENV))
    enabled = resolved == "on" or (resolved == "auto" and backend != "cpu")
    if from_env:
        jax.config.update("jax_enable_compilation_cache", enabled)
    elif enabled:
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT_CACHE_DIR))
    if enabled:
        # The key is the program AND its scope names, nothing else. jax's
        # key drops a program's metadata by default, and the
        # `jax.named_scope` paths a profiler trace is read by ARE metadata:
        # a directory shared with another checkout would hand back that
        # checkout's executable under its scope names (PERF.md section 7).
        # So the metadata goes into the key, and file names and line
        # numbers come out of the metadata (locations keep the scope path
        # and the primitive): an edit that moves a line, or a second
        # checkout at another path, still finds every program it did not
        # change. The price: compiled programs carry no source_file /
        # source_line.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        jax.config.update("jax_traceback_in_locations_limit", 0)
    from .. import telemetry

    telemetry.counter("compile_cache_enabled", int(enabled),
                      mode=resolved, backend=backend,
                      cache_dir=str(compile_cache_dir()))
    return enabled


@dataclasses.dataclass(frozen=True)
class DistContext:
    """What `setup_distributed` returns — the TPU analogue of the reference's
    ``(rank, world_size, local_rank)`` triple (train_ddp.py:68).

    ``process_index``/``process_count`` are host-level (one process per host);
    ``device_count`` is the number of addressable-from-anywhere chips in the
    global mesh, which is the number that plays the reference's ``world_size``
    role for per-device batch-size math (ref :27 "mini-batch size *per GPU*").
    """

    process_index: int
    process_count: int
    local_device_count: int
    device_count: int

    @property
    def is_main(self) -> bool:
        """True on the metrics/logging writer process (ref rank==0, :229, :350)."""
        return self.process_index == 0


def _pod_runtime_detected() -> bool:
    """True when env advertises a multi-host TPU pod whose rendezvous is
    auto-discoverable by a no-arg ``jax.distributed.initialize()``."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    num_slices = os.environ.get("MEGASCALE_NUM_SLICES")
    return bool(num_slices and int(num_slices) > 1)


def is_distributed() -> bool:
    """Multi-host? (Reference semantics: WORLD_SIZE>1, train_ddp.py:49-50.)

    Note the meaning shift: on GPU+DDP every *device* is a process, so
    single-host-4-GPU is "distributed". On TPU, 8 chips on one host are a
    plain single-process `Mesh` — collectives still happen, but no process
    group is needed. "Distributed" here therefore means multi-process
    (multi-host), which is the only case needing rendezvous.
    """
    if os.environ.get("DPT_NUM_PROCESSES"):
        return int(os.environ["DPT_NUM_PROCESSES"]) > 1
    return jax.process_count() > 1


def setup_distributed() -> DistContext:
    """Initialize the multi-host runtime if needed; return the process context.

    Maps train_ddp.py:53-68. Blocking rendezvous (like ``init_process_group``
    with ``env://``, ref :65) happens inside ``jax.distributed.initialize``.
    Safe to call when single-host: returns a trivial context, mirroring the
    reference's ``(0, 1, 0)`` fast path (ref :58-59).
    """
    global _INITIALIZED

    coord = os.environ.get("DPT_COORDINATOR_ADDRESS")
    nproc = os.environ.get("DPT_NUM_PROCESSES")
    if not _INITIALIZED:
        if coord and nproc and int(nproc) > 1:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(nproc),
                process_id=int(os.environ.get("DPT_PROCESS_ID", "0")),
            )
            _INITIALIZED = True
        elif _pod_runtime_detected():
            # Cloud TPU pod: topology is auto-discoverable; no-arg initialize
            # performs the rendezvous (the ref's env:// equivalent, :65).
            # Failures must NOT be swallowed — proceeding uninitialized would
            # silently train per-host un-synced models.
            jax.distributed.initialize()
            _INITIALIZED = True
    if _INITIALIZED:
        logger.info(
            "jax.distributed initialized: process %d/%d",
            jax.process_index(),
            jax.process_count(),
        )

    return DistContext(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        device_count=jax.device_count(),
    )


def cleanup_distributed() -> None:
    """Tear down the multi-host runtime (maps train_ddp.py:71-73)."""
    global _INITIALIZED
    if _INITIALIZED:
        jax.distributed.shutdown()
        _INITIALIZED = False


def per_process_seed(seed: int, process_index: Optional[int] = None) -> int:
    """The reference's per-rank seed rule: ``seed + rank``
    (/root/reference/train_ddp.py:76-78) — de-correlates host-side RNG streams
    across processes (e.g. CPU-side augmentation) on purpose.

    NOTE the split responsibility in the TPU design: *device-side* randomness
    (in-jit augmentation, dropout) uses ONE shared `PRNGKey(seed)` folded with
    the step counter — it operates on the global batch, so per-sample streams
    are already de-correlated and must be identical across hosts for SPMD to
    agree. *Host-side* randomness must use THIS rule, or every host would
    produce the same "random" numbers.
    """
    if process_index is None:
        process_index = jax.process_index()
    return seed + process_index


def set_seed(seed: int, process_index: Optional[int] = None) -> "np.random.Generator":
    """Seed host-side RNGs with ``seed + rank`` (maps set_seed, ref :76-78).

    Seeds Python's and NumPy's global generators (for any library code that
    reaches for them) and returns a dedicated ``np.random.Generator`` for
    framework host-side use. Device-side keys are NOT derived here — pass
    ``jax.random.PRNGKey(seed)`` (unfolded) to the Trainer so every host
    traces the same program with the same key.
    """
    import random

    import numpy as np

    s = per_process_seed(seed, process_index)
    random.seed(s)
    np.random.seed(s % (2 ** 32))
    return np.random.default_rng(s)

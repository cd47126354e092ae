"""AST lint engine: source-level parallelism contracts, checked on real
syntax trees instead of regexes.

The predecessor (tests/test_compat_lint.py's regex) fired on *mentions* of
the shard_map entry points inside docstrings and string literals — prose
about the rule tripped the rule. An `ast` visitor only sees real imports,
attribute accesses, and calls, so the false-positive class is structural,
not patched around.

Every rule reports `Finding`s with file:line locations. Suppression is
per-line: append ``# analysis: disable=<rule-name>`` (or ``disable=all``)
to the offending line — meant for experiment branches that knowingly break
a contract, and visible in review precisely because it sits on the line.

The engine is dependency-free by design (no jax import): linting the repo
must never require initializing a backend. The axis-name registry is
therefore a literal copy of `parallel/mesh.py`'s AXIS_ORDER; a tier-1 test
asserts the two stay identical.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .contracts import Finding, rule

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
PKG_ROOT = Path(__file__).resolve().parent.parent

# The one allowed home of the raw shard_map entry points (the version-compat
# shim — ROADMAP "jax version skew").
SHARD_MAP_SHIM = "parallel/collectives.py"

# Mirror of parallel/mesh.py AXIS_NAMES (kept import-free; test-pinned).
AXIS_NAMES = frozenset({"data", "fsdp", "model", "seq", "pipe", "expert",
                        "slice"})

# Collective-call names whose axis argument must come from the registry.
_AXIS_CALLS = frozenset({
    "psum", "pmean", "pmax", "psum_scatter", "all_gather", "all_to_all",
    "ppermute", "ppermute_ring", "axis_index", "axis_size",
})

_DISABLE_RE = re.compile(r"#\s*analysis:\s*disable=([\w\-,\s]+)")


@dataclasses.dataclass
class FileContext:
    """One parsed source file, shared across the rules that visit it."""

    path: Path
    relpath: str
    tree: ast.Module
    lines: List[str]
    # alias maps built once per file (imports are module-level in this repo)
    modules: Dict[str, str] = dataclasses.field(default_factory=dict)
    members: Dict[str, str] = dataclasses.field(default_factory=dict)

    @classmethod
    def parse(cls, path: Path, repo: Path = REPO_ROOT) -> "FileContext":
        src = path.read_text()
        try:
            rel = path.resolve().relative_to(repo).as_posix()
        except ValueError:  # outside the repo (synthetic test files)
            rel = path.as_posix()
        ctx = cls(path=path, relpath=rel,
                  tree=ast.parse(src, filename=str(path)),
                  lines=src.splitlines())
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    ctx.modules[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    if a.name != "*":
                        ctx.members[a.asname or a.name] = \
                            f"{node.module}.{a.name}"
        return ctx

    def loc(self, node: ast.AST) -> str:
        return f"{self.relpath}:{getattr(node, 'lineno', 0)}"

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted path of a Name/Attribute chain with import aliases
        expanded: `np.random.rand` -> "numpy.random.rand" under
        `import numpy as np`; `shard_map` -> its from-import source."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        head = node.id
        if head in self.members:
            parts.append(self.members[head])
        elif head in self.modules:
            parts.append(self.modules[head])
        else:
            parts.append(head)
        return ".".join(reversed(parts))

    def suppressed(self, finding: Finding) -> bool:
        try:
            lineno = int(finding.location.rsplit(":", 1)[1])
            line = self.lines[lineno - 1]
        except (IndexError, ValueError):
            return False
        m = _DISABLE_RE.search(line)
        if not m:
            return False
        names = {n.strip() for n in m.group(1).split(",")}
        return "all" in names or finding.rule in names


# ---------------------------------------------------------------------------
# Shared traced-function discovery (rules 2 and 3)
# ---------------------------------------------------------------------------

_JIT_NAMES = ("jax.jit", "jax.pmap")


def _is_jit_name(resolved: Optional[str]) -> bool:
    return resolved in _JIT_NAMES


def _is_shard_map_name(resolved: Optional[str]) -> bool:
    return bool(resolved) and resolved.split(".")[-1] == "shard_map"


def traced_function_names(ctx: FileContext) -> Set[str]:
    """Names of functions this file hands to jax.jit / shard_map (by call
    argument or decorator) — their bodies, including nested defs, run under
    tracing. A per-file heuristic: good enough because the repo's traced
    entry points are always wrapped in the module that defines them."""
    traced: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            fn = ctx.resolve(node.func)
            if (_is_jit_name(fn) or _is_shard_map_name(fn)) and node.args:
                target = node.args[0]
                if isinstance(target, ast.Name):
                    traced.add(target.id)
                elif isinstance(target, ast.Attribute):
                    traced.add(target.attr)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                base = dec.func if isinstance(dec, ast.Call) else dec
                fn = ctx.resolve(base)
                if _is_jit_name(fn) or _is_shard_map_name(fn):
                    traced.add(node.name)
                # @partial(jax.jit, ...) / @functools.partial(shard_map, ...)
                if isinstance(dec, ast.Call) and fn and \
                        fn.split(".")[-1] == "partial" and dec.args:
                    inner = ctx.resolve(dec.args[0])
                    if _is_jit_name(inner) or _is_shard_map_name(inner):
                        traced.add(node.name)
    return traced


def _traced_defs(ctx: FileContext, extra_names: Iterable[str] = ()
                 ) -> List[ast.FunctionDef]:
    names = traced_function_names(ctx) | set(extra_names)
    return [n for n in ast.walk(ctx.tree)
            if isinstance(n, ast.FunctionDef) and n.name in names]


# ---------------------------------------------------------------------------
# Rule 1: shard_map only via the compat shim
# ---------------------------------------------------------------------------


@rule("shard-map-shim-only", "ast",
      "shard_map is used only through the parallel/collectives.py shim",
      "the raw entry point moved (jax.experimental.shard_map -> "
      "jax.shard_map) and its replication flag was renamed (check_rep -> "
      "check_vma) across jax versions, and every body here needs the "
      "check OFF; one wrapper is the one place that knows both. Mentions "
      "in docstrings and strings do not count — only real imports, "
      "attribute accesses, and kwargs.")
def check_shard_map_shim(ctx: FileContext) -> List[Finding]:
    if ctx.relpath.endswith(SHARD_MAP_SHIM):
        return []
    name = "shard-map-shim-only"
    out: List[Finding] = []
    # `jax.experimental.shard_map.shard_map` is ONE use: ast.walk visits
    # the outer Attribute before its inner chain, so flag the outer node
    # and skip its descendants (else the same line reports twice).
    inner_seen: Set[int] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.experimental.shard_map"):
                    out.append(Finding(
                        name, f"direct import of {a.name} (import "
                        "`shard_map` from "
                        "distributed_pytorch_training_tpu.parallel)",
                        ctx.loc(node)))
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            if node.module == "jax.experimental.shard_map" or (
                    node.module in ("jax", "jax.experimental")
                    and any(a.name == "shard_map" for a in node.names)):
                out.append(Finding(
                    name, f"direct shard_map import from {node.module} "
                    "(use the parallel/collectives.py shim)",
                    ctx.loc(node)))
        elif isinstance(node, ast.Attribute):
            if id(node) in inner_seen:
                continue
            resolved = ctx.resolve(node) or ""
            if resolved in ("jax.shard_map",
                            "jax.experimental.shard_map") or \
                    resolved.startswith("jax.experimental.shard_map."):
                out.append(Finding(
                    name, f"direct use of {resolved} (use the "
                    "parallel/collectives.py shim)", ctx.loc(node)))
                inner_seen.update(
                    id(sub) for sub in ast.walk(node) if sub is not node)
        elif isinstance(node, ast.Call):
            fn = ctx.resolve(node.func)
            if _is_shard_map_name(fn):
                bad = [k.arg for k in node.keywords
                       if k.arg in ("check_rep", "check_vma")]
                if bad:
                    out.append(Finding(
                        name, f"shard_map called with {bad} — the shim "
                        "owns the replication-check flag (its NAME is the "
                        "version skew)", ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------
# Rule 2: no impure host calls inside traced bodies
# ---------------------------------------------------------------------------

# Impure prefixes: calls whose result differs run-to-run. Pure numpy shape
# math (np.prod(np.shape(x))) is trace-time constant folding and stays
# legal; np.random/stdlib random/time bake ONE trace-time draw into the
# compiled program silently — the program replays it forever.
_IMPURE_PREFIXES = ("time.", "random.", "numpy.random.")


@rule("no-impure-calls-in-traced", "ast",
      "no time/random/np.random calls inside jit/shard_map-traced bodies",
      "an impure host call inside a traced body executes ONCE at trace "
      "time and its result is baked into the compiled program as a "
      "constant — every step replays the same 'random' draw or timestamp, "
      "silently. (Pure numpy shape math is trace-time constant folding "
      "and is allowed.)")
def check_impure_in_traced(ctx: FileContext) -> List[Finding]:
    out: List[Finding] = []
    seen: Set[int] = set()
    for fndef in _traced_defs(ctx):
        for node in ast.walk(fndef):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            resolved = ctx.resolve(node.func)
            if not resolved:
                continue
            if any(resolved == p[:-1] or resolved.startswith(p)
                   for p in _IMPURE_PREFIXES):
                out.append(Finding(
                    "no-impure-calls-in-traced",
                    f"{resolved}() inside traced function "
                    f"`{fndef.name}` — executes once at trace time, baked "
                    "into the program as a constant (use jax.random / "
                    "device-side state)", ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------
# Rule 3: no device syncs in training/loop.py step paths
# ---------------------------------------------------------------------------

_SYNC_CALLS = ("jax.device_get", "jax.block_until_ready")


def _scan_sync_calls(ctx: FileContext, fndefs, rule_name: str,
                     scope_desc: str, cost: str) -> List[Finding]:
    """The shared sync-call detector behind `no-host-sync-in-step` and
    `no-host-sync-in-decode`: `.item()` / `_SYNC_CALLS` / `float()`/`int()`
    on non-constants inside the given function defs. ONE detector — a
    future extension (e.g. catching `np.asarray` fetches) lands in both
    rules by construction instead of drifting between copies.
    ``scope_desc`` names the scanned region in messages ("step path" /
    "decode loop"); ``cost`` names what one sync costs ("per-step" /
    "per-token")."""
    out: List[Finding] = []
    seen: Set[int] = set()
    for fndef in fndefs:
        for node in ast.walk(fndef):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                out.append(Finding(
                    rule_name, f".item() inside {scope_desc} "
                    f"`{fndef.name}` — a {cost} device sync",
                    ctx.loc(node)))
                continue
            resolved = ctx.resolve(node.func)
            if resolved in _SYNC_CALLS:
                out.append(Finding(
                    rule_name, f"{resolved}() inside {scope_desc} "
                    f"`{fndef.name}` — a {cost} device sync",
                    ctx.loc(node)))
            elif resolved in ("float", "int") and node.args and \
                    not isinstance(node.args[0], ast.Constant):
                out.append(Finding(
                    rule_name, f"{resolved}() on a device value inside "
                    f"{scope_desc} `{fndef.name}` — forces a host fetch",
                    ctx.loc(node)))
    return out


@rule("no-host-sync-in-step", "ast",
      "no .item()/float()/device_get syncs inside training/loop.py step "
      "paths",
      "the reference's per-step .item() was its throughput bottleneck "
      "(train_ddp.py:217); the loop design fetches only at print "
      "boundaries. A sync creeping back into a step function stalls the "
      "device once per step — invisible in tests, ruinous at scale.")
def check_host_sync_in_step(ctx: FileContext) -> List[Finding]:
    if not ctx.relpath.endswith("training/loop.py"):
        return []
    step_names = {n.name for n in ast.walk(ctx.tree)
                  if isinstance(n, ast.FunctionDef)
                  and (n.name.endswith("_step") or
                       n.name.endswith("_step_impl"))}
    return _scan_sync_calls(ctx, _traced_defs(ctx, extra_names=step_names),
                            "no-host-sync-in-step", "step path", "per-step")


# The serving decode hot loop's home and function names
# (serving/continuous.py `_step_decode_loop`, plus anything a refactor
# names *_decode_loop). One host fetch per finished SLOT is the design
# (after the burst's last step, in _complete_finished); a fetch inside
# the loop stalls the device once per generated TOKEN, for EVERY slot in
# the pool.
_DECODE_LOOP_FILES = ("serving/continuous.py",)


@rule("no-host-sync-in-decode", "ast",
      "no .item()/float()/device_get syncs inside the serving decode loop "
      "(serving/continuous.py _step_decode_loop)",
      "the decode loop runs one compiled step per generated token with "
      "every chained value (token, positions, cache) staying on device; "
      "a host fetch creeping in serializes the device per TOKEN — the "
      "training loop's .item() anti-pattern, multiplied by max_new_tokens "
      "per request.")
def check_host_sync_in_decode(ctx: FileContext) -> List[Finding]:
    if not any(ctx.relpath.endswith(f) for f in _DECODE_LOOP_FILES):
        return []
    loops = [n for n in ast.walk(ctx.tree)
             if isinstance(n, ast.FunctionDef)
             and n.name.endswith("_decode_loop")]
    return _scan_sync_calls(ctx, loops, "no-host-sync-in-decode",
                            "decode loop", "per-token")


# ---------------------------------------------------------------------------
# Rule 4: axis-name literals only from the mesh registry
# ---------------------------------------------------------------------------


def _literal_strings(node: ast.AST) -> List[Tuple[str, ast.AST]]:
    """String constants in an axis-argument position: the constant itself
    or the elements of a tuple/list of constants."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [(node.value, node)]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [(e.value, e) for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


@rule("axis-name-registry", "ast",
      "mesh axis names appear as string literals only in parallel/mesh.py",
      "every axis literal outside the registry is a rename hazard: "
      "collectives psum over 'data' while the mesh was built with the "
      "constants, and a registry change silently strands the literal — "
      "the axis typo failure mode _axes_present guards at runtime, "
      "caught at lint time instead.")
def check_axis_name_registry(ctx: FileContext) -> List[Finding]:
    if ctx.relpath.endswith("parallel/mesh.py"):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func) or ""
        base = resolved.split(".")[-1]
        candidates: List[Tuple[str, ast.AST]] = []
        if base in ("PartitionSpec", "P"):
            for arg in node.args:
                candidates += _literal_strings(arg)
        elif base in _AXIS_CALLS and len(node.args) >= 2:
            candidates += _literal_strings(node.args[1])
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axis_names"):
                candidates += _literal_strings(kw.value)
        for value, lit in candidates:
            if value in AXIS_NAMES:
                out.append(Finding(
                    "axis-name-registry",
                    f"axis name {value!r} as a string literal in "
                    f"{base}(...) — import the constant from "
                    "parallel/mesh.py (DATA/FSDP/MODEL/SEQ/PIPE/EXPERT "
                    "or BATCH_AXES)", ctx.loc(lit)))
    return out


# ---------------------------------------------------------------------------
# Rule 5: os._exit only in resilience/heartbeat.py
# ---------------------------------------------------------------------------

# The one sanctioned home of the abrupt-exit primitive (hard_exit):
# preemption's hard deadline routes through it.
# Matched on exact trailing path COMPONENTS, not a string suffix — a
# future `myresilience/heartbeat.py` must not inherit the exemption.
OS_EXIT_HOME = ("resilience", "heartbeat.py")


@rule("no-bare-os-exit", "ast",
      "os._exit appears only in resilience/heartbeat.py (hard_exit)",
      "an abrupt exit while this process holds the chip skips the "
      "runtime's release of it, and skips every flush and checkpoint "
      "barrier the process owes. The one legitimate abrupt exit — "
      "preemption's zombie-prevention deadline — lives behind "
      "resilience/heartbeat.py's hard_exit, which documents when an "
      "abrupt exit is allowed and what cleanup it owes first; a bare "
      "os._exit anywhere else is an unaccounted one.")
def check_no_bare_os_exit(ctx: FileContext) -> List[Finding]:
    if tuple(ctx.relpath.replace("\\", "/").split("/")[-2:]) == OS_EXIT_HOME:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        # flag the ATTRIBUTE access, not just calls: `ex = os._exit` then
        # `ex(1)` is the same hazard with one extra hop
        if isinstance(node, ast.Attribute) or isinstance(node, ast.Name):
            resolved = ctx.resolve(node)
            if resolved == "os._exit":
                out.append(Finding(
                    "no-bare-os-exit",
                    "os._exit outside resilience/heartbeat.py — an abrupt "
                    "exit skips the chip's release and every owed flush; "
                    "use resilience.heartbeat.hard_exit (or the preemption "
                    "guard's deadline) so the exit is accounted for",
                    ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------
# Rule: jax.profiler session entry points only via utils/profiling.py
# ---------------------------------------------------------------------------

# The one sanctioned home of the raw jax profiler session primitives
# (StepProfiler + trace_session own the process-wide session guard).
# Matched on exact trailing path COMPONENTS like OS_EXIT_HOME — a future
# `myutils/profiling.py` must not inherit the exemption.
PROFILER_HOME = ("utils", "profiling.py")

_PROFILER_SESSION_NAMES = ("jax.profiler.start_trace",
                           "jax.profiler.stop_trace")


@rule("profiler-session-via-stepprofiler-only", "ast",
      "jax.profiler.start_trace/stop_trace appear only in "
      "utils/profiling.py",
      "jax holds ONE profiler session per process: a second start_trace "
      "while one is open raises from deep inside jax, and a leaked open "
      "session silently fails every later capture — with ISSUE 15's "
      "on-demand and anomaly-triggered captures, windows can now open at "
      "RUNTIME from the HTTP thread and the watchdog, so every session "
      "entry must route through utils/profiling.py's process-wide guard "
      "(StepProfiler / trace_session), which refuses-and-counts "
      "(`profiler_busy`) instead of crashing. A bare start_trace "
      "anywhere else reintroduces the clobber.")
def check_profiler_session_home(ctx: FileContext) -> List[Finding]:
    if tuple(ctx.relpath.replace("\\", "/").split("/")[-2:]) \
            == PROFILER_HOME:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        # flag the reference itself (Name or Attribute), not just calls:
        # `st = jax.profiler.start_trace` then `st(d)` is the same hazard
        if isinstance(node, (ast.Attribute, ast.Name)):
            resolved = ctx.resolve(node)
            if resolved in _PROFILER_SESSION_NAMES:
                out.append(Finding(
                    "profiler-session-via-stepprofiler-only",
                    f"{resolved} outside utils/profiling.py — raw "
                    "session entry points bypass the process-wide "
                    "session guard (a concurrent on-demand capture would "
                    "clobber it); use utils.profiling.StepProfiler or "
                    "trace_session", ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------

# The one sanctioned home of raw Pallas kernels: the package's ops/
# directory (flash/ring/ulysses attention, the fused int8 quantize codecs).
# Matched on exact trailing path components like OS_EXIT_HOME — a future
# `somewhere_else/ops/` must not inherit the exemption.
PALLAS_HOME = ("distributed_pytorch_training_tpu", "ops")

_PALLAS_CALL_NAMES = (
    "jax.experimental.pallas.pallas_call",
    "jax.experimental.pallas.tpu.pallas_call",
)


@rule("pallas-call-in-ops-only", "ast",
      "pl.pallas_call appears only under distributed_pytorch_training_tpu/"
      "ops/",
      "a Pallas kernel carries per-backend obligations the rest of the "
      "codebase must not re-derive ad hoc: a TPU gate with an interpreter-"
      "mode fallback (the XLA-composed path stays the CPU/tier-1 "
      "reference), a cost estimate, a bit-exactness or tolerance contract "
      "pinned by tests, and VMEM block-shape rules. ops/ is where those "
      "conventions live (flash_backend_supported, "
      "quantize_backend_supported); a pallas_call inlined elsewhere ships "
      "an ungated kernel that breaks the first time tier-1 runs on CPU.")
def check_pallas_call_in_ops(ctx: FileContext) -> List[Finding]:
    parts = tuple(ctx.relpath.replace("\\", "/").split("/"))
    if parts[-3:-1] == PALLAS_HOME:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        # flag the reference itself (Name or Attribute), not just calls:
        # `k = pl.pallas_call(...)` via an alias is the same kernel escape
        if isinstance(node, (ast.Attribute, ast.Name)):
            resolved = ctx.resolve(node)
            if resolved in _PALLAS_CALL_NAMES:
                out.append(Finding(
                    "pallas-call-in-ops-only",
                    "pl.pallas_call outside distributed_pytorch_training_"
                    "tpu/ops/ — raw kernels live in ops/ behind a backend "
                    "gate + interpreter fallback (the "
                    "flash_backend_supported convention); export a gated "
                    "wrapper from ops/ instead",
                    ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------

# The package and its one leaf: experiments/ holds the studies
# (scaling.py, plots.py, their harness), and only entry points may stand
# on it. Matched on exact path components, like PALLAS_HOME.
PACKAGE = PALLAS_HOME[0]
EXPERIMENTS = "experiments"


def _import_targets(here: Tuple[str, ...],
                    node: ast.AST) -> List[Tuple[str, ...]]:
    """The absolute dotted module paths an import statement binds or
    reaches into, as tuples of components: a relative `from ..a import b`
    is resolved against ``here``, the importing file's directory, and each
    imported name is appended (it may be a submodule)."""
    if isinstance(node, ast.Import):
        return [tuple(a.name.split(".")) for a in node.names]
    if not isinstance(node, ast.ImportFrom):
        return []
    base = here[:len(here) - (node.level - 1)] if node.level else ()
    base += tuple(node.module.split(".")) if node.module else ()
    return [base + (a.name,) for a in node.names]


@rule("experiments-is-a-leaf", "ast",
      "no module of the package outside experiments/ imports the "
      "experiments package",
      "experiments/ is the reference's 'scaling experiments' and 'gradient "
      "sync profiling': drivers and their measuring recipe, reshaped "
      "whenever a study changes. When the token server built its engine "
      "through experiments.harness and the telemetry plane parsed captures "
      "through experiments.trace_analysis, none of it could change without "
      "breaking `serve`. How an engine is built is serving/'s decision, how "
      "a capture is split is telemetry/'s; entry points (train.py, "
      "experiments/scaling.py) may stand on experiments/, the package may "
      "not.")
def check_experiments_is_a_leaf(ctx: FileContext) -> List[Finding]:
    parts = tuple(ctx.relpath.replace("\\", "/").split("/"))
    if PACKAGE not in parts[:-1]:
        return []  # a root script or a test: an entry point, not the package
    inside = parts[parts.index(PACKAGE) + 1:]
    if inside[0] == EXPERIMENTS:
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        for target in _import_targets(parts[:-1], node):
            if (PACKAGE, EXPERIMENTS) in zip(target, target[1:]):
                out.append(Finding(
                    "experiments-is-a-leaf",
                    f"{'/'.join(inside)} imports {'.'.join(target)} — "
                    "the package does not stand on experiments/; move "
                    "what is needed to the package that owns the decision "
                    "(serving/build.py, telemetry/trace_analysis.py, "
                    "models/registry.py are where the last ones went)",
                    ctx.loc(node)))
                break
    return out


# ---------------------------------------------------------------------------
# Rule 7: no telemetry emission inside traced bodies
# ---------------------------------------------------------------------------

# The telemetry package's module name (any import path component match:
# absolute `distributed_pytorch_training_tpu.telemetry`, relative
# `..telemetry`, `from .. import telemetry`).
_TELEMETRY_MODULE = "telemetry"


def _telemetry_bindings(ctx: FileContext
                        ) -> Tuple[Set[str], Set[str], Set[str]]:
    """(module aliases, member names, dotted prefixes) this file bound to
    the telemetry package. Walked here directly (not via ctx.members)
    because the repo imports telemetry RELATIVELY (``from .. import
    telemetry``), which the shared alias maps skip by design.

    An UNALIASED ``import pkg.telemetry`` binds only the ROOT name
    ``pkg`` — flagging every call rooted at ``pkg`` would false-positive
    on ``pkg.parallel.psum(...)``, so that form is tracked as the full
    dotted prefix (``pkg.telemetry``) and matched against the call's raw
    attribute chain instead."""
    mods: Set[str] = set()
    members: Set[str] = set()
    dotted: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                parts = a.name.split(".")
                if _TELEMETRY_MODULE not in parts:
                    continue
                if a.asname:
                    mods.add(a.asname)
                elif len(parts) == 1:
                    mods.add(a.name)  # `import telemetry` itself
                else:
                    dotted.add(a.name)
        elif isinstance(node, ast.ImportFrom):
            mod_parts = (node.module or "").split(".")
            if _TELEMETRY_MODULE in mod_parts:
                # from ..telemetry import span / from ..telemetry.recorder
                # import Recorder — every bound name is a telemetry member
                for a in node.names:
                    members.add(a.asname or a.name)
            else:
                # from .. import telemetry [as tel]
                for a in node.names:
                    if a.name == _TELEMETRY_MODULE:
                        mods.add(a.asname or a.name)
    return mods, members, dotted


def _raw_dotted(node: ast.AST) -> Optional[str]:
    """The literal dotted text of a Name/Attribute chain (no alias
    expansion), or None for non-trivial roots (calls, subscripts)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


@rule("telemetry-emit-outside-traced", "ast",
      "telemetry Recorder calls are forbidden inside jit/shard_map-traced "
      "bodies",
      "a telemetry emit inside a traced body would execute ONCE at trace "
      "time (recording a single bogus event, never one per step) and — "
      "worse — any attempt to make it per-step would need a host callback "
      "or sync inside the compiled step, exactly the stall class the "
      "no-host-sync-in-step rule exists to kill. Instrumentation is "
      "host-side by contract: spans wrap the dispatched step, they never "
      "live inside it (PARITY.md pins telemetry-on/off HLO identity).")
def check_telemetry_in_traced(ctx: FileContext) -> List[Finding]:
    mods, members, dotted = _telemetry_bindings(ctx)
    if not mods and not members and not dotted:
        return []
    name = "telemetry-emit-outside-traced"
    out: List[Finding] = []
    seen: Set[int] = set()
    for fndef in _traced_defs(ctx):
        for node in ast.walk(fndef):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            func = node.func
            # telemetry.span(...) / tel.recorder.emit(...): any attribute
            # chain rooted at a telemetry module alias
            head = func
            while isinstance(head, ast.Attribute):
                head = head.value
            hit = (isinstance(head, ast.Name) and head.id in mods
                   and isinstance(func, ast.Attribute))
            # span(...) imported from the telemetry package directly
            hit = hit or (isinstance(func, ast.Name) and func.id in members)
            # pkg.telemetry.emit(...) under an unaliased dotted import:
            # matched against the dotted prefix, so pkg.parallel.psum(...)
            # rooted at the same package name never false-positives
            if not hit and dotted:
                raw = _raw_dotted(func)
                hit = bool(raw) and any(raw.startswith(d + ".")
                                        for d in dotted)
            if hit:
                out.append(Finding(
                    name,
                    f"telemetry call inside traced function "
                    f"`{fndef.name}` — emission is host-side only "
                    "(executes once at trace time here; wrap the "
                    "dispatched step instead)", ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------
# Rule 8: every emitted span name is registered
# ---------------------------------------------------------------------------

# The emission helpers whose first argument is a span NAME (module-level
# `telemetry.span(...)` / `telemetry.span_event(...)` and their member
# imports — the only in-repo emission idioms; `Recorder.emit("span", ...)`
# stays internal to the telemetry package).
_SPAN_EMITTERS = frozenset({"span", "span_event"})


def _registered_span_names() -> frozenset:
    # telemetry/recorder.py is jax-free by contract (the engine's no-
    # backend rule holds), so unlike AXIS_NAMES the registry is imported,
    # not mirrored — one definition, nothing to drift.
    from ..telemetry.recorder import REGISTERED_SPAN_NAMES

    return frozenset(REGISTERED_SPAN_NAMES)


@rule("span-names-registered", "ast",
      "every telemetry span name emitted in-repo appears in the "
      "recorder's span-name registry",
      "`telemetry summary` buckets spans by NAME against the canonical "
      "registry (SPAN_NAMES / SERVING_SPAN_NAMES / ELASTIC_SPAN_NAMES / "
      "AUX_SPAN_NAMES in telemetry/recorder.py) and silently files "
      "anything else under 'unaccounted' — a typo'd or unregistered span "
      "name vanishes from the step-time split instead of failing loudly, "
      "and the fleet aggregator's phase attribution never sees it. New "
      "span names are one registry line away; dynamic (non-literal) "
      "names are flagged too, because a name the linter cannot read is a "
      "name the registry cannot vouch for.")
def check_span_names_registered(ctx: FileContext) -> List[Finding]:
    mods, members, dotted = _telemetry_bindings(ctx)
    if not mods and not members and not dotted:
        return []
    # local names bound to the emitters via member imports, ALIASES
    # included: `from ..telemetry import span_event as se` binds `se` to
    # span_event — _telemetry_bindings keeps only the bound name, so the
    # original-name mapping is re-derived here (the pallas rule's
    # alias-aware convention)
    member_emitters: Dict[str, str] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) \
                and _TELEMETRY_MODULE in (node.module or "").split("."):
            for a in node.names:
                if a.name in _SPAN_EMITTERS:
                    member_emitters[a.asname or a.name] = a.name
    registry = _registered_span_names()
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        emitter = None
        if isinstance(func, ast.Attribute) and func.attr in _SPAN_EMITTERS:
            head = func
            while isinstance(head, ast.Attribute):
                head = head.value
            if isinstance(head, ast.Name) and head.id in mods:
                emitter = func.attr
            elif dotted:
                raw = _raw_dotted(func)
                if raw and any(raw.startswith(d + ".") for d in dotted):
                    emitter = func.attr
        elif isinstance(func, ast.Name) and func.id in member_emitters:
            emitter = member_emitters[func.id]
        if emitter is None or not node.args:
            continue
        name_arg = node.args[0]
        if isinstance(name_arg, ast.Constant) \
                and isinstance(name_arg.value, str):
            if name_arg.value not in registry:
                out.append(Finding(
                    "span-names-registered",
                    f"span name {name_arg.value!r} in {emitter}(...) is "
                    "not in the telemetry span-name registry — "
                    "`telemetry summary` would bucket it into "
                    "'unaccounted'; add it to the right *_SPAN_NAMES "
                    "tuple in telemetry/recorder.py", ctx.loc(name_arg)))
        else:
            out.append(Finding(
                "span-names-registered",
                f"dynamic span name in {emitter}(...) — the registry "
                "cannot vouch for a name the linter cannot read; emit a "
                "registered literal (or suppress on this line if the "
                "dynamism is deliberate)", ctx.loc(name_arg)))
    return out


# ---------------------------------------------------------------------------
# Rule 9: control decisions reach the re-plan surface only via apply.py
# ---------------------------------------------------------------------------

# The one sanctioned home of re-plan calls from the control package:
# control/apply.py (apply_decision — the contract-gated commit point).
# Matched on exact trailing path components like OS_EXIT_HOME.
CONTROL_APPLY_HOME = ("control", "apply.py")

# The re-plan surface: the Supervisor's boundary commit points, the
# elastic re-plan primitives they ride, and the armed callbacks. A
# reference to ANY of these from a control/ module other than apply.py
# is a policy resharding the fleet directly.
_REPLAN_SURFACE = frozenset({
    "boundary_shrink", "boundary_retune", "reshard_train_state",
    "plan_elastic_world", "replan_cb", "retune_cb", "_replan",
    "_maybe_grow",
})


@rule("control-decisions-gated", "ast",
      "control/ modules reach the re-plan surface (boundary_shrink / "
      "boundary_retune / reshard_train_state / plan_elastic_world / the "
      "replan callbacks) only through control/apply.py",
      "control/ is split by contract: policies (straggler.py, tuner.py, "
      "autopilot.py) measure and PROPOSE; only apply.py COMMITS, because "
      "apply_decision is where the contract gate and the decision log "
      "live. A policy calling boundary_shrink or reshard_train_state "
      "directly reshapes the fleet with no gate run and no ControlDecision "
      "emitted — the exact ungoverned mutation the control plane exists "
      "to prevent. Flagged on the reference (Name or Attribute), not just "
      "calls: `commit = sup.boundary_shrink` then `commit(...)` is the "
      "same bypass with one extra hop.")
def check_control_decisions_gated(ctx: FileContext) -> List[Finding]:
    parts = tuple(ctx.relpath.replace("\\", "/").split("/"))
    if len(parts) < 2 or parts[-2] != "control":
        return []
    if parts[-2:] == CONTROL_APPLY_HOME:
        return []
    name = "control-decisions-gated"
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        hit: Optional[str] = None
        if isinstance(node, ast.Attribute) and node.attr in _REPLAN_SURFACE:
            hit = node.attr
        elif isinstance(node, ast.Name) and node.id in _REPLAN_SURFACE:
            hit = node.id
        if hit is not None:
            out.append(Finding(
                name,
                f"`{hit}` referenced from a control/ policy module — the "
                "re-plan surface is reachable from control/ only through "
                "apply.py's apply_decision (the contract gate + decision "
                "log); emit a ControlDecision and let the Supervisor's "
                "boundary hook commit it", ctx.loc(node)))
    return out


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def iter_source_files(repo: Path = REPO_ROOT) -> List[Path]:
    """The linted set: the package plus repo-top-level scripts — the same
    scope the old regex lint covered. Tests are exempt (they hold the
    synthetic violations the mutation tests feed the rules)."""
    pkg = repo / "distributed_pytorch_training_tpu"
    return sorted(pkg.rglob("*.py")) + sorted(repo.glob("*.py"))


def run_ast_rules(files: Optional[Iterable[Path]] = None,
                  rules: Optional[List[str]] = None,
                  repo: Path = REPO_ROOT) -> List[Finding]:
    """Run every (selected) AST rule over `files` (default: the repo set).
    Files that fail to parse produce a finding instead of crashing the
    run — a syntax error is a finding, not an analyzer failure.

    Kind "ast" rules see one FileContext at a time; kind "ast-global"
    rules (the lock-order graph) run ONCE over the whole parsed set —
    their findings anchor to a file:line, so per-line suppression still
    applies through that file's context."""
    from .contracts import iter_rules

    selected = [r for r in iter_rules(names=rules)
                if r.kind in ("ast", "ast-global")]
    per_file = [r for r in selected if r.kind == "ast"]
    global_rules = [r for r in selected if r.kind == "ast-global"]
    findings: List[Finding] = []
    contexts: Dict[str, FileContext] = {}
    for path in (files if files is not None else iter_source_files(repo)):
        path = Path(path)
        try:
            ctx = FileContext.parse(path, repo=repo)
        except (SyntaxError, ValueError) as e:
            findings.append(Finding(
                "parse-error", f"could not parse: {e}",
                str(path)))
            continue
        contexts[ctx.relpath] = ctx
        for r in per_file:
            for f in r.check(ctx):
                if not ctx.suppressed(f):
                    findings.append(f)
    for r in global_rules:
        for f in r.check(list(contexts.values())):
            ctx = contexts.get(f.location.rsplit(":", 1)[0])
            if ctx is None or not ctx.suppressed(f):
                findings.append(f)
    return findings

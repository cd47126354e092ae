"""Concurrency-discipline rules: the host-side control plane, linted.

The serving/resilience/telemetry layers are thread-heavy by design (worker
replicas, metrics servers), and the last two PRs each
shipped a hand-found race fix. These rules turn the locking discipline
into checked annotations instead of review folklore:

* **guarded-by** — declare the lock that protects a shared attribute at
  its assignment site::

      self._free: List[int] = []   # guarded-by: _lock

  Every read/write of ``self._free`` in the owning class outside a
  ``with self._lock:`` body is then a finding. Methods whose CALLERS hold
  the lock are marked on the ``def`` line::

      def _take_page(self):   # lock-held: _lock

  Several alternatives may be listed (``# guarded-by: _lock, _cv``) —
  holding any one satisfies the rule. Class-level state uses the same
  convention (``_seeds = iter(...)  # guarded-by: _seeds_lock``) and is
  matched through both ``self.X`` and ``ClassName.X`` access spellings.

* **lock-order-acyclic** — the one global rule (kind ``ast-global``):
  collect every lexically nested acquisition (``with A: ... with B:``)
  across all files into one graph of per-class lock identities
  (``PagePool._lock``, ``RequestQueue._cv``, module locks as
  ``profiling._SESSION_LOCK``) and flag cycles — two threads walking a
  cycle from different ends deadlock. Lexical nesting only: an
  acquisition reached through a method call in another class is invisible
  here; the runtime half (``utils/locktrace.py``, ``DPT_LOCKCHECK=1``)
  records those orders at test time and
  :func:`check_runtime_consistency` merges them back into this graph.

* **no-blocking-under-lock** — socket / urlopen / subprocess /
  ``time.sleep`` / ``.join()`` / ``.result()`` / ``.wait()`` /
  queue-``.get()`` calls lexically inside a held-lock body (the exact
  Router health-probe bug class PR 17 fixed by hand: an HTTP round trip
  under the router lock serializes every dispatch on every thread).
  Calling ``.wait()`` on the held lock itself is exempt — a Condition
  wait RELEASES its lock.

* **thread-lifecycle** — every ``threading.Thread`` must be
  ``daemon=True`` or joined somewhere in its file: a non-daemon,
  never-joined thread outlives shutdown and hangs interpreter exit.

All findings honor the per-line ``# analysis: disable=<rule>`` suppression
(visible in review, reason stated on the line). Like the rest of the AST
engine this module is dependency-free — linting must never require a
backend, so it must NOT import utils.locktrace (whose parent package pulls
jax); locktrace imports *this* module lazily for its cross-check.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .ast_rules import REPO_ROOT, FileContext, iter_source_files
from .contracts import Finding, rule

_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([\w,\s]+)")
_LOCK_HELD_RE = re.compile(r"#\s*lock-held:\s*([\w,\s]+)")

# Constructor tails that produce a lock-ish object. named_lock /
# named_condition are utils.locktrace's instrumented constructors — from
# the rules' point of view they ARE the lock.
_LOCK_FACTORIES = frozenset({"Lock", "RLock", "Condition",
                             "named_lock", "named_condition"})

# with-target attribute names that read as locks even when the constructor
# is out of view (helper-built locks, locks declared in another file).
_LOCKISH_NAME = re.compile(r"lock|mutex|cond(ition)?$|(^|_)cv$|(^|_)mu$",
                           re.IGNORECASE)

_BLOCKING_CALLS = frozenset({
    "time.sleep", "urllib.request.urlopen", "socket.create_connection",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen",
})

# receivers whose .get() blocks (queue.Queue and kin); dict.get never
# takes a timeout, so a timeout kwarg marks a blocking get regardless.
_QUEUEISH = re.compile(r"(^|_)q(ueue)?s?\d*$|queue", re.IGNORECASE)


def _raw(node: ast.AST) -> Optional[str]:
    """Literal dotted text of a Name/Attribute chain (no alias expansion):
    the identity locks are matched by (``self._lock``, ``t.daemon``)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _owned_attr(expr: ast.AST, cls_name: str) -> Optional[str]:
    """Attribute name X when `expr` is ``self.X`` or ``<ClassName>.X``."""
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name) \
            and expr.value.id in ("self", cls_name):
        return expr.attr
    return None


def _comment_names(lines: List[str], lo: int, hi: int,
                   rx: re.Pattern) -> Tuple[str, ...]:
    """First `rx` annotation in source lines [lo, hi] (1-based, inclusive)
    — an assignment or def signature may span several physical lines."""
    for i in range(lo, min(hi, len(lines)) + 1):
        m = rx.search(lines[i - 1])
        if m:
            return tuple(n.strip() for n in m.group(1).split(",")
                         if n.strip())
    return ()


@dataclasses.dataclass
class ClassLockModel:
    """One class's declared locking discipline: which attributes are
    locks, which are guarded (and by what), which methods assume a lock
    is already held at entry."""

    name: str
    lock_attrs: Set[str]
    # attr -> (allowed lock names, declaration lineno)
    guards: Dict[str, Tuple[Tuple[str, ...], int]]
    # method name -> locks held by contract at entry
    lock_held: Dict[str, Tuple[str, ...]]

    @property
    def lock_universe(self) -> Set[str]:
        """Every name this class treats as a lock — constructed locks
        plus anything a guarded-by / lock-held annotation names (the
        declaration is authoritative even when the constructor is built
        by a helper the model cannot see)."""
        u = set(self.lock_attrs)
        for locks, _ in self.guards.values():
            u.update(locks)
        for locks in self.lock_held.values():
            u.update(locks)
        return u


def class_lock_model(ctx: FileContext, cls: ast.ClassDef) -> ClassLockModel:
    """Collect the lock/guard declarations of one class: class-level
    assignments plus ``self.X = ...`` sites anywhere in ``__init__``."""
    lock_attrs: Set[str] = set()
    guards: Dict[str, Tuple[Tuple[str, ...], int]] = {}
    lock_held: Dict[str, Tuple[str, ...]] = {}

    def scan_assign(stmt: ast.stmt, attr: str) -> None:
        hi = getattr(stmt, "end_lineno", stmt.lineno) or stmt.lineno
        names = _comment_names(ctx.lines, stmt.lineno, hi, _GUARDED_RE)
        if names:
            guards[attr] = (names, stmt.lineno)
        value = getattr(stmt, "value", None)
        if isinstance(value, ast.Call):
            resolved = ctx.resolve(value.func) or ""
            if resolved.split(".")[-1] in _LOCK_FACTORIES:
                lock_attrs.add(attr)

    for node in cls.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    scan_assign(node, t.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            sig_end = max(node.lineno, node.body[0].lineno - 1)
            held = _comment_names(ctx.lines, node.lineno, sig_end,
                                  _LOCK_HELD_RE)
            if held:
                lock_held[node.name] = held
            if node.name == "__init__":
                for stmt in ast.walk(node):
                    if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                        tgts = stmt.targets if isinstance(stmt, ast.Assign) \
                            else [stmt.target]
                        for t in tgts:
                            attr = _owned_attr(t, cls.name)
                            if attr is not None:
                                scan_assign(stmt, attr)
    return ClassLockModel(name=cls.name, lock_attrs=lock_attrs,
                          guards=guards, lock_held=lock_held)


# ---------------------------------------------------------------------------
# Rule: guarded-by
# ---------------------------------------------------------------------------


def _guard_walk(ctx: FileContext, cls_name: str, model: ClassLockModel,
                universe: Set[str], node: ast.AST, held: Set[str],
                where: str, out: List[Finding]) -> None:
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        # nested def: a closure may run on another thread after the lock
        # is gone — its body starts from its own lock-held contract only
        inner = set(model.lock_held.get(node.name, ()))
        for child in node.body:
            _guard_walk(ctx, cls_name, model, universe, child, inner,
                        node.name, out)
        return
    if isinstance(node, ast.Lambda):
        _guard_walk(ctx, cls_name, model, universe, node.body, set(),
                    where, out)
        return
    if isinstance(node, (ast.With, ast.AsyncWith)):
        acquired: Set[str] = set()
        for item in node.items:
            attr = _owned_attr(item.context_expr, cls_name)
            if attr is not None and attr in universe:
                acquired.add(attr)
            _guard_walk(ctx, cls_name, model, universe, item, held,
                        where, out)
        for child in node.body:
            _guard_walk(ctx, cls_name, model, universe, child,
                        held | acquired, where, out)
        return
    if isinstance(node, ast.Attribute):
        attr = _owned_attr(node, cls_name)
        if attr is not None and attr in model.guards:
            locks, decl = model.guards[attr]
            if not (set(locks) & held):
                want = " or ".join(f"`with self.{l}:`" for l in locks)
                out.append(Finding(
                    "guarded-by",
                    f"`{cls_name}.{where}` touches `self.{attr}` outside "
                    f"{want} — declared `# guarded-by: "
                    f"{', '.join(locks)}` at {ctx.relpath}:{decl}; hold "
                    "the lock, mark the method `# lock-held:`, or "
                    "suppress with the reason on this line",
                    ctx.loc(node)))
    for child in ast.iter_child_nodes(node):
        _guard_walk(ctx, cls_name, model, universe, child, held, where,
                    out)


@rule(
    "guarded-by", "ast",
    "a `# guarded-by:`-annotated attribute touched outside its lock",
    "declaring the protecting lock at the attribute's assignment site "
    "makes the locking discipline machine-checkable: every unlocked "
    "read/write in the owning class is a race the next refactor ships")
def check_guarded_by(ctx: FileContext) -> List[Finding]:
    out: List[Finding] = []
    for cls in (n for n in ast.walk(ctx.tree)
                if isinstance(n, ast.ClassDef)):
        model = class_lock_model(ctx, cls)
        if not model.guards:
            continue
        universe = model.lock_universe
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name == "__init__":
                continue    # construction precedes sharing
            held = set(model.lock_held.get(fn.name, ()))
            for stmt in fn.body:
                _guard_walk(ctx, cls.name, model, universe, stmt, held,
                            fn.name, out)
    return out


# ---------------------------------------------------------------------------
# Rule: no-blocking-under-lock
# ---------------------------------------------------------------------------


def _module_level_locks(ctx: FileContext) -> Set[str]:
    out: Set[str] = set()
    for node in ctx.tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            value = getattr(node, "value", None)
            if isinstance(value, ast.Call):
                resolved = ctx.resolve(value.func) or ""
                if resolved.split(".")[-1] in _LOCK_FACTORIES:
                    out.update(t.id for t in targets
                               if isinstance(t, ast.Name))
    return out


def _blocking_reason(ctx: FileContext, call: ast.Call,
                     held: Sequence[str]) -> Optional[str]:
    resolved = ctx.resolve(call.func)
    if resolved in _BLOCKING_CALLS:
        return f"`{resolved}(...)`"
    if not isinstance(call.func, ast.Attribute):
        return None
    meth = call.func.attr
    recv = _raw(call.func.value)
    if recv is not None and recv in held:
        return None     # waiting on the held lock itself releases it
    kwnames = {k.arg for k in call.keywords}
    npos = len(call.args)
    show = recv or "<expr>"
    if meth == "join":
        numeric = npos == 1 and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, (int, float)) \
            and not isinstance(call.args[0].value, bool)
        if npos == 0 or "timeout" in kwnames or numeric:
            return f"`{show}.join(...)`"
    elif meth == "result" and npos <= 1:
        return f"`{show}.result(...)`"
    elif meth in ("wait", "wait_for"):
        return f"`{show}.{meth}(...)`"
    elif meth == "get":
        last = (recv or "").split(".")[-1]
        if "timeout" in kwnames or _QUEUEISH.search(last):
            return f"`{show}.get(...)`"
    return None


@rule(
    "no-blocking-under-lock", "ast",
    "a blocking call (socket/urlopen/subprocess/sleep/join/result/wait/"
    "queue-get) lexically inside a held-lock body",
    "a blocking call under a lock serializes every thread that needs the "
    "lock on the slowest caller — the Router health-probe bug class: one "
    "unreachable replica's 2s HTTP timeout stalled every dispatch")
def check_no_blocking_under_lock(ctx: FileContext) -> List[Finding]:
    out: List[Finding] = []
    module_locks = _module_level_locks(ctx)
    models: Dict[str, ClassLockModel] = {}

    def model_of(cls: ast.ClassDef) -> ClassLockModel:
        if cls.name not in models:
            models[cls.name] = class_lock_model(ctx, cls)
        return models[cls.name]

    def lockish(expr: ast.AST, cls: Optional[ast.ClassDef]) -> Optional[str]:
        raw = _raw(expr)
        if raw is None:
            return None
        parts = raw.split(".")
        if len(parts) == 2 and cls is not None \
                and parts[0] in ("self", cls.name):
            if parts[1] in model_of(cls).lock_universe \
                    or _LOCKISH_NAME.search(parts[1]):
                return raw
            return None
        if len(parts) == 1 and (parts[0] in module_locks
                                or _LOCKISH_NAME.search(parts[0])):
            return raw
        if len(parts) == 2 and _LOCKISH_NAME.search(parts[1]):
            return raw  # OtherClass._lock spelled cross-class
        return None

    def walk(node: ast.AST, held: List[str],
             cls: Optional[ast.ClassDef]) -> None:
        if isinstance(node, ast.ClassDef):
            for child in node.body:
                walk(child, [], node)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            start: List[str] = []
            if cls is not None:
                start = [f"self.{l}" for l in
                         model_of(cls).lock_held.get(node.name, ())]
            for child in node.body:
                walk(child, start, cls)
            return
        if isinstance(node, ast.Lambda):
            walk(node.body, [], cls)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquired: List[str] = []
            for item in node.items:
                raw = lockish(item.context_expr, cls)
                if raw is not None:
                    acquired.append(raw)
                walk(item, held, cls)
            for child in node.body:
                walk(child, held + acquired, cls)
            return
        if isinstance(node, ast.Call) and held:
            reason = _blocking_reason(ctx, node, held)
            if reason is not None:
                locks = ", ".join(f"`{h}`" for h in held)
                out.append(Finding(
                    "no-blocking-under-lock",
                    f"{reason} while holding {locks} — every thread that "
                    "needs the lock now waits on this call too; move it "
                    "outside the critical section (snapshot under the "
                    "lock, act outside it) or suppress with the reason "
                    "on this line",
                    ctx.loc(node)))
        for child in ast.iter_child_nodes(node):
            walk(child, held, cls)

    for stmt in ctx.tree.body:
        walk(stmt, [], None)
    return out


# ---------------------------------------------------------------------------
# Rule: lock-order-acyclic (global) + the exported graph
# ---------------------------------------------------------------------------


def _collect_lock_edges(
        ctxs: Sequence[FileContext]) -> Dict[Tuple[str, str], str]:
    """The global nested-acquisition graph: (outer, inner) -> first
    location where `inner` was taken while `outer` was held. Identities
    are class-qualified (``PagePool._lock``) so the same discipline reads
    identically from every file — and matches the names the runtime
    tracer records (utils/locktrace.py)."""
    edges: Dict[Tuple[str, str], str] = {}
    for ctx in ctxs:
        module_locks = _module_level_locks(ctx)
        stem = ctx.relpath.rsplit("/", 1)[-1].removesuffix(".py")
        models: Dict[str, ClassLockModel] = {}

        def model_of(cls: ast.ClassDef) -> ClassLockModel:
            if cls.name not in models:
                models[cls.name] = class_lock_model(ctx, cls)
            return models[cls.name]

        def lock_id(expr: ast.AST,
                    cls: Optional[ast.ClassDef]) -> Optional[str]:
            raw = _raw(expr)
            if raw is None:
                return None
            parts = raw.split(".")
            if len(parts) == 2 and cls is not None \
                    and parts[0] in ("self", cls.name):
                if parts[1] in model_of(cls).lock_universe \
                        or _LOCKISH_NAME.search(parts[1]):
                    return f"{cls.name}.{parts[1]}"
                return None
            if len(parts) == 2 and parts[0][:1].isupper() \
                    and _LOCKISH_NAME.search(parts[1]):
                return f"{parts[0]}.{parts[1]}"  # OtherClass._lock
            if len(parts) == 1 and parts[0] in module_locks:
                return f"{stem}.{parts[0]}"
            return None  # local/aliased locks carry no stable identity

        def walk(node: ast.AST, held: List[str],
                 cls: Optional[ast.ClassDef]) -> None:
            if isinstance(node, ast.ClassDef):
                for child in node.body:
                    walk(child, [], node)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                start: List[str] = []
                if cls is not None:
                    start = [f"{cls.name}.{l}" for l in
                             model_of(cls).lock_held.get(node.name, ())]
                for child in node.body:
                    walk(child, start, cls)
                return
            if isinstance(node, ast.Lambda):
                walk(node.body, [], cls)
                return
            if isinstance(node, (ast.With, ast.AsyncWith)):
                new: List[str] = []
                for item in node.items:
                    lid = lock_id(item.context_expr, cls)
                    if lid is not None:
                        new.append(lid)
                    walk(item, held, cls)
                for outer in held:
                    for inner in new:
                        if outer != inner:
                            edges.setdefault((outer, inner),
                                             ctx.loc(node))
                for child in node.body:
                    walk(child, held + new, cls)
                return
            for child in ast.iter_child_nodes(node):
                walk(child, held, cls)

        for stmt in ctx.tree.body:
            walk(stmt, [], None)
    return edges


def _find_cycles(edge_keys: Iterable[Tuple[str, str]]) -> List[List[str]]:
    """Strongly-connected components of size > 1 (plus self-loops) —
    each is a set of locks acquirable in a cyclic order."""
    adj: Dict[str, List[str]] = {}
    nodes: Set[str] = set()
    self_loops: List[str] = []
    for a, b in edge_keys:
        nodes.update((a, b))
        if a == b:
            self_loops.append(a)
            continue
        adj.setdefault(a, []).append(b)
    # iterative Tarjan
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]
    for root in sorted(nodes):
        if root in index:
            continue
        work: List[Tuple[str, int]] = [(root, 0)]
        while work:
            v, i = work.pop()
            if i == 0:
                index[v] = low[v] = counter[0]
                counter[0] += 1
                stack.append(v)
                on_stack.add(v)
            recursed = False
            children = adj.get(v, [])
            while i < len(children):
                w = children[i]
                i += 1
                if w not in index:
                    work.append((v, i))
                    work.append((w, 0))
                    recursed = True
                    break
                if w in on_stack:
                    low[v] = min(low[v], index[w])
            if recursed:
                continue
            if low[v] == index[v]:
                scc: List[str] = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == v:
                        break
                if len(scc) > 1:
                    sccs.append(sorted(scc))
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[v])
    return sccs + [[n] for n in sorted(set(self_loops))]


@rule(
    "lock-order-acyclic", "ast-global",
    "a cycle in the global nested-lock-acquisition graph",
    "two threads that take a lock cycle from different ends deadlock; "
    "one global acquisition order (checked here, observed at runtime by "
    "utils/locktrace.py) makes that impossible by construction")
def check_lock_order_acyclic(
        ctxs: Sequence[FileContext]) -> List[Finding]:
    edges = _collect_lock_edges(list(ctxs))
    out: List[Finding] = []
    for cycle in _find_cycles(edges.keys()):
        members = set(cycle)
        def _line_order(loc: str) -> Tuple[str, int]:
            path, _, line = loc.rpartition(":")
            return (path, int(line) if line.isdigit() else 0)

        locs = sorted({loc for (a, b), loc in edges.items()
                       if a in members and b in members},
                      key=_line_order)
        out.append(Finding(
            "lock-order-acyclic",
            f"locks {' -> '.join(cycle + [cycle[0]])} are acquired in a "
            f"cycle (nested `with` sites: {', '.join(locs[:4])}) — "
            "impose one global acquisition order, or suppress on the "
            "first site with the reason the orders can never meet",
            locs[0] if locs else "<unknown>:0"))
    return out


def lock_order_graph(files: Optional[Iterable[Path]] = None,
                     repo: Path = REPO_ROOT) -> Dict[Tuple[str, str], str]:
    """The static acquisition graph over `files` (default: the linted
    set) — the reference utils/locktrace.py cross-checks runtime orders
    against. Unparseable files are skipped (run_ast_rules reports them)."""
    ctxs: List[FileContext] = []
    for p in (files if files is not None else iter_source_files(repo)):
        try:
            ctxs.append(FileContext.parse(Path(p), repo=repo))
        except (SyntaxError, ValueError):
            continue
    return _collect_lock_edges(ctxs)


def check_runtime_consistency(
        runtime_edges: Iterable[Tuple[str, str]],
        static_edges: Optional[Dict[Tuple[str, str], str]] = None,
) -> List[str]:
    """Merge runtime-observed acquisition orders into the static graph
    and report inconsistencies: a runtime edge that reverses a static
    one, or any cycle in the merged graph. Empty list = consistent."""
    static = dict(static_edges) if static_edges is not None \
        else lock_order_graph()
    problems: List[str] = []
    runtime = list(runtime_edges)
    for a, b in runtime:
        if (b, a) in static:
            problems.append(
                f"runtime order {a} -> {b} reverses the static "
                f"acquisition at {static[(b, a)]}")
    merged = dict(static)
    for a, b in runtime:
        merged.setdefault((a, b), "<runtime>")
    for cycle in _find_cycles(merged.keys()):
        problems.append(
            "merged static+runtime lock graph has a cycle: "
            + " -> ".join(cycle + [cycle[0]]))
    return problems


# ---------------------------------------------------------------------------
# Rule: thread-lifecycle
# ---------------------------------------------------------------------------


@rule(
    "thread-lifecycle", "ast",
    "a threading.Thread neither daemonized nor joined in its file",
    "a non-daemon thread nobody joins outlives every shutdown path: the "
    "interpreter hangs at exit waiting for it, and SIGTERM drains stall")
def check_thread_lifecycle(ctx: FileContext) -> List[Finding]:
    daemon_set: Set[str] = set()
    joined: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                raw = _raw(t)
                if raw and raw.endswith(".daemon") \
                        and isinstance(node.value, ast.Constant) \
                        and node.value.value is True:
                    daemon_set.add(raw[: -len(".daemon")])
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "join":
            recv = _raw(node.func.value)
            if recv:
                joined.add(recv)
    parents = {child: p for p in ast.walk(ctx.tree)
               for child in ast.iter_child_nodes(p)}
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and ctx.resolve(node.func) == "threading.Thread"):
            continue
        kw = {k.arg: k.value for k in node.keywords}
        d = kw.get("daemon")
        if isinstance(d, ast.Constant) and d.value is True:
            continue
        p = parents.get(node)
        targets: List[str] = []
        if isinstance(p, ast.Assign):
            targets = [r for t in p.targets if (r := _raw(t))]
        elif isinstance(p, ast.AnnAssign):
            r = _raw(p.target)
            targets = [r] if r else []
        if any(t in daemon_set or t in joined for t in targets):
            continue
        out.append(Finding(
            "thread-lifecycle",
            "threading.Thread created neither `daemon=True` nor joined "
            "anywhere in this file — it outlives shutdown and hangs "
            "interpreter exit; daemonize it, join it on the stop path, "
            "or suppress with the reason it is collected elsewhere",
            ctx.loc(node)))
    return out

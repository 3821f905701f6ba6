"""speclint — AST-based static analysis encoding this repo's invariants.

The engine is deliberately small: parse every ``.py`` under the package
once into :class:`ModuleInfo`, run each :class:`Rule` (per-module checks
plus whole-project graph checks), filter inline suppressions, then diff
against the ratcheting baseline. Rules encode bugs this codebase has
actually shipped and fixed by hand in review — see docs/analysis.md for
the rule-by-rule history:

``fork-safety``
    Every module-level ``threading.Lock/RLock/Condition`` must be
    re-initialized by an ``os.register_at_fork(after_in_child=...)``
    hook (the PR 6 class: gen-pool forks inheriting locks held by
    front-door supervisor threads), and nothing may start a thread at
    import time.
``blocking-under-lock``
    No ``time.sleep``, socket ``recv``/``accept``/``connect``,
    ``subprocess`` calls, timeout-less ``Future.result()`` or
    queue ``get()`` inside a ``with <lock>:`` body (the PR 3/PR 4
    class: slow or unbounded work serialized under a hot lock).
``lock-order``
    The static lock-acquisition graph — nested ``with`` statements
    plus intra-package call edges — must be acyclic; any cycle is a
    potential deadlock. ``analysis.lockwatch`` is the runtime
    counterpart cross-checking this graph against live acquisitions.
``jit-purity``
    Functions reachable from ``jax.jit``/``vmap`` wrap sites must not
    read ``os.environ``, call ``time.*``/stdlib ``random``, take
    locks, or bump obs counters — the value would be silently baked
    into the compiled program at trace time (the ``_use_device()``
    snapshot-once lesson from PR 3, generalized).
``obs-discipline``
    Device-timed spans (the body assigns ``sp.result``) must declare
    ``work_bytes`` (no roofline verdict otherwise — the 878 Ghash/s
    lesson), and every counter/gauge/histogram/span name must match
    the Prometheus-safe grammar and be declared in ``obs/catalog.py``.
``env-registry``
    Every ``ETH_SPECS_*`` environment read must be declared once in
    ``envreg.py`` (default + docs anchor); declared vars nothing reads
    are stale. docs/env-reference.md is generated from the registry.
``fault-site-registry``
    Every ``fault.check(site)`` / ``fault.corrupt(site)`` literal must
    be declared in ``fault/sites.py``, and every declared site must be
    referenced by a chaos test or the docs failure matrix.

Suppression: a trailing or preceding-line comment
``# speclint: disable=<rule>[,<rule>...]`` silences a finding at that
line — reviewed escape hatches, visible in the diff. Baseline:
``speclint_baseline.json`` maps finding fingerprints (path::rule::symbol,
line-number free so they survive unrelated edits) to counts; the CLI
fails on any non-baselined finding and refuses a baseline update that
grows a rule's count (the ratchet — findings may only be fixed, never
accumulated).
"""

from __future__ import annotations

import ast
import itertools
import json
import os
import re
from dataclasses import dataclass, field

PACKAGE = "eth_consensus_specs_tpu"

_SUPPRESS_RE = re.compile(r"#\s*speclint:\s*disable=([\w,\-]+)")
_LOCK_CTORS = {"Lock", "RLock", "Condition"}
_LOCKISH_NAME_RE = re.compile(r"(?i)(?:^|_)(lock|cond|mutex)s?$|_lock$|_cond$")
_METRIC_GRAMMAR_RE = re.compile(r"^[a-z][a-z0-9_]*(?:\.[a-z0-9_*]+)*$")

ALL_RULES = (
    "fork-safety",
    "blocking-under-lock",
    "lock-order",
    "jit-purity",
    "obs-discipline",
    "env-registry",
    "fault-site-registry",
)


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    symbol: str  # stable anchor: lock/env/site/function name
    message: str

    @property
    def fingerprint(self) -> str:
        # line-number free on purpose: unrelated edits above a finding
        # must not churn the baseline
        return f"{self.path}::{self.rule}::{self.symbol}"

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "symbol": self.symbol,
            "message": self.message,
            "fingerprint": self.fingerprint,
        }


@dataclass
class ModuleInfo:
    """One parsed module plus everything the rules need resolved."""

    path: str  # absolute
    relpath: str  # repo-relative
    modname: str  # dotted, package-relative ("serve.admission")
    tree: ast.Module
    suppressions: dict[int, set[str]] = field(default_factory=dict)
    # local name -> package-relative dotted module it refers to
    import_map: dict[str, str] = field(default_factory=dict)
    # module-level constants: NAME -> str value (for site-name resolution)
    str_consts: dict[str, str] = field(default_factory=dict)
    # module-level lock names -> lineno
    module_locks: dict[str, int] = field(default_factory=dict)
    # (class, attr) -> lineno for self.<attr> = threading.Lock() in methods
    class_locks: dict[tuple[str, str], int] = field(default_factory=dict)


# ------------------------------------------------------------ module parse --


def _parse_suppressions(source: str) -> dict[int, set[str]]:
    out: dict[int, set[str]] = {}
    for lineno, line in enumerate(source.splitlines(), 1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[lineno] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _is_lock_ctor(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Attribute) and fn.attr in _LOCK_CTORS:
        return isinstance(fn.value, ast.Name) and fn.value.id == "threading"
    if isinstance(fn, ast.Name) and fn.id in _LOCK_CTORS:
        return True
    # analysis.lockwatch.wrap(threading.Lock(), "name") — still a lock
    if isinstance(fn, ast.Attribute) and fn.attr == "wrap" and node.args:
        return _is_lock_ctor(node.args[0])
    if isinstance(fn, ast.Name) and fn.id == "wrap" and node.args:
        return _is_lock_ctor(node.args[0])
    return False


def _build_import_map(tree: ast.Module, modname: str) -> dict[str, str]:
    """local name -> package-relative dotted module, for intra-package
    call-edge resolution."""
    out: dict[str, str] = {}
    pkg_parts = modname.split(".")[:-1]  # containing package of this module
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.name
                if name.startswith(PACKAGE + ".") or name == PACKAGE:
                    rel = name[len(PACKAGE) + 1 :] if name != PACKAGE else ""
                    out[alias.asname or name.split(".")[-1]] = rel
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative import
                base = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                prefix = ".".join(base + ([node.module] if node.module else []))
            elif node.module and (
                node.module == PACKAGE or node.module.startswith(PACKAGE + ".")
            ):
                prefix = node.module[len(PACKAGE) + 1 :] if node.module != PACKAGE else ""
            else:
                continue
            for alias in node.names:
                target = f"{prefix}.{alias.name}" if prefix else alias.name
                out[alias.asname or alias.name] = target
    return out


def load_module(path: str, repo_root: str, package_root: str) -> ModuleInfo | None:
    try:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError):
        return None
    relpath = os.path.relpath(path, repo_root).replace(os.sep, "/")
    rel_to_pkg = os.path.relpath(path, package_root).replace(os.sep, "/")
    modname = rel_to_pkg[:-3].replace("/", ".")
    if modname.endswith(".__init__"):
        modname = modname[: -len(".__init__")]
    mi = ModuleInfo(
        path=path,
        relpath=relpath,
        modname=modname,
        tree=tree,
        suppressions=_parse_suppressions(source),
    )
    mi.import_map = _build_import_map(tree, modname)
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt, val = node.targets[0], node.value
            if isinstance(tgt, ast.Name):
                if _is_lock_ctor(val):
                    mi.module_locks[tgt.id] = node.lineno
                elif isinstance(val, ast.Constant) and isinstance(val.value, str):
                    mi.str_consts[tgt.id] = val.value
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target, ast.Name):
            if node.value is not None and _is_lock_ctor(node.value):
                mi.module_locks[node.target.id] = node.lineno
    for cls in [n for n in tree.body if isinstance(n, ast.ClassDef)]:
        for sub in ast.walk(cls):
            if (
                isinstance(sub, ast.Assign)
                and len(sub.targets) == 1
                and isinstance(sub.targets[0], ast.Attribute)
                and isinstance(sub.targets[0].value, ast.Name)
                and sub.targets[0].value.id == "self"
                and _is_lock_ctor(sub.value)
            ):
                mi.class_locks[(cls.name, sub.targets[0].attr)] = sub.lineno
    return mi


# -------------------------------------------------------- lock identities --


def _lock_identity(mi: ModuleInfo, expr: ast.AST, cls: str | None) -> str | None:
    """Resolve a with-item expression to a stable lock identity, or None
    when it is not recognizably a lock. Identities match what
    analysis.lockwatch wraps use, so the static and runtime graphs share
    a namespace."""
    if isinstance(expr, ast.Name):
        if expr.id in mi.module_locks:
            return f"{mi.modname}.{expr.id}"
        if _LOCKISH_NAME_RE.search(expr.id):
            return f"{mi.modname}.{expr.id}"
        return None
    if (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
        and cls is not None
    ):
        if (cls, expr.attr) in mi.class_locks or _LOCKISH_NAME_RE.search(expr.attr):
            return f"{mi.modname}.{cls}.{expr.attr}"
    # ALIAS._LOCK — a module-level lock referenced through an import
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name):
        target_mod = mi.import_map.get(expr.value.id)
        if target_mod is not None and _LOCKISH_NAME_RE.search(expr.attr):
            return f"{target_mod}.{expr.attr}"
    return None


def _lockish(mi: ModuleInfo, expr: ast.AST, cls: str | None) -> bool:
    return _lock_identity(mi, expr, cls) is not None


# ------------------------------------------------------------- call graph --


@dataclass
class FuncInfo:
    qualname: str  # "serve.service.VerifyService._submit"
    modname: str
    node: ast.AST
    acquires: set[str] = field(default_factory=set)  # lock identities
    calls: set[str] = field(default_factory=set)  # resolved callee qualnames
    # (held lock identity, callee qualname, lineno)
    held_calls: list[tuple[str, str, int]] = field(default_factory=list)
    # (held lock identity, acquired lock identity, lineno)
    held_acquires: list[tuple[str, str, int]] = field(default_factory=list)
    # (held lock identity, lineno, blocking-call description)
    blocking: list[tuple[str, int, str]] = field(default_factory=list)


def _resolve_call(mi: ModuleInfo, node: ast.Call, cls: str | None) -> str | None:
    fn = node.func
    if isinstance(fn, ast.Name):
        return f"{mi.modname}.{fn.id}"  # same-module function (validated later)
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
        base = fn.value.id
        if base == "self" and cls is not None:
            return f"{mi.modname}.{cls}.{fn.attr}"
        target_mod = mi.import_map.get(base)
        if target_mod is not None:
            return f"{target_mod}.{fn.attr}"
    return None


class _FuncWalker(ast.NodeVisitor):
    """Walk one function body tracking the held-lock stack through
    nested ``with`` statements, collecting acquisitions, call edges, and
    blocking-call sites."""

    def __init__(self, mi: ModuleInfo, cls: str | None, fi: FuncInfo):
        self.mi = mi
        self.cls = cls
        self.fi = fi
        self.held: list[str] = []

    def visit_With(self, node: ast.With) -> None:  # noqa: N802 — ast API
        pushed = 0
        for item in node.items:
            expr = item.context_expr
            ident = _lock_identity(self.mi, expr, self.cls)
            if ident is not None:
                self.fi.acquires.add(ident)
                if self.held:
                    self.fi.held_acquires.append((self.held[-1], ident, node.lineno))
                self.held.append(ident)
                pushed += 1
        for stmt in node.body:
            self.visit(stmt)
        for _ in range(pushed):
            self.held.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:  # noqa: N802
        pass  # nested defs are separate functions; don't inherit the held stack

    visit_AsyncFunctionDef = visit_FunctionDef

    def _held_lock_exprs(self) -> set[str]:
        return set(self.held)

    def visit_Call(self, node: ast.Call) -> None:  # noqa: N802
        callee = _resolve_call(self.mi, node, self.cls)
        if callee is not None:
            self.fi.calls.add(callee)
            if self.held:
                self.fi.held_calls.append((self.held[-1], callee, node.lineno))
        if self.held:
            what = _blocking_call(self.mi, node, self.cls, self._held_lock_exprs())
            if what is not None:
                self.fi.blocking.append((self.held[-1], node.lineno, what))
        self.generic_visit(node)


def _blocking_call(
    mi: ModuleInfo, node: ast.Call, cls: str | None, held: set[str]
) -> str | None:
    """Classify a call as blocking-under-lock, or None. ``held`` carries
    the identities of currently held locks so the Condition idiom
    (``self._cond.wait()`` inside ``with self._cond``) is exempt."""
    fn = node.func
    kwnames = {kw.arg for kw in node.keywords}
    if isinstance(fn, ast.Attribute):
        base = fn.value
        if isinstance(base, ast.Name) and base.id == "time" and fn.attr == "sleep":
            return "time.sleep"
        if fn.attr in ("recv", "recv_into", "accept", "connect", "sendall", "makefile"):
            return f"socket .{fn.attr}()"
        if isinstance(base, ast.Name) and base.id in ("subprocess",):
            return f"subprocess.{fn.attr}"
        if isinstance(base, ast.Name) and base.id == "os" and fn.attr == "system":
            return "os.system"
        if fn.attr == "result" and not node.args and "timeout" not in kwnames:
            return "Future.result() without timeout"
        if fn.attr in ("wait", "acquire", "join", "get"):
            # exempt waiting on a lock/condition we already hold (the
            # Condition wait idiom releases it while waiting)
            ident = _lock_identity(mi, base, cls)
            if ident is not None and ident in held:
                return None
            has_timeout = (
                "timeout" in kwnames
                or any(not isinstance(a, ast.Constant) or a.value is not None
                       for a in node.args)
            )
            if fn.attr == "get" and not has_timeout:
                last = base.attr if isinstance(base, ast.Attribute) else (
                    base.id if isinstance(base, ast.Name) else ""
                )
                if re.search(r"(?i)(^|_)q(ueue)?$", last):
                    return "queue get() without timeout"
            if fn.attr == "join" and not node.args and "timeout" not in kwnames:
                last = base.attr if isinstance(base, ast.Attribute) else (
                    base.id if isinstance(base, ast.Name) else ""
                )
                if re.search(r"(?i)(thread|proc|worker)", last):
                    return "thread join() without timeout"
    elif isinstance(fn, ast.Name):
        if fn.id == "sleep":
            return "sleep"
    return None


def _iter_functions(mi: ModuleInfo):
    """Yield (cls_or_None, FunctionDef) for every function in the module,
    including methods (one level of class nesting, which is all this
    codebase uses)."""
    for node in mi.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield None, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield node.name, sub


def build_function_table(modules: list[ModuleInfo]) -> dict[str, FuncInfo]:
    table: dict[str, FuncInfo] = {}
    for mi in modules:
        for cls, fn in _iter_functions(mi):
            qual = f"{mi.modname}.{cls}.{fn.name}" if cls else f"{mi.modname}.{fn.name}"
            fi = FuncInfo(qualname=qual, modname=mi.modname, node=fn)
            w = _FuncWalker(mi, cls, fi)
            for stmt in fn.body:
                w.visit(stmt)
            table[qual] = fi
    # keep only call edges that resolve to a known function
    for fi in table.values():
        fi.calls = {c for c in fi.calls if c in table}
        fi.held_calls = [(h, c, ln) for h, c, ln in fi.held_calls if c in table]
    return table


def may_acquire_fixpoint(table: dict[str, FuncInfo]) -> dict[str, set[str]]:
    """Transitive lock-acquisition sets over intra-package call edges."""
    may: dict[str, set[str]] = {q: set(fi.acquires) for q, fi in table.items()}
    changed = True
    while changed:
        changed = False
        for q, fi in table.items():
            for callee in fi.calls:
                extra = may.get(callee, set()) - may[q]
                if extra:
                    may[q] |= extra
                    changed = True
    return may


def build_lock_graph(
    modules: list[ModuleInfo], table: dict[str, FuncInfo] | None = None
) -> dict:
    """The static lock-order graph: direct nested-with edges plus edges
    through intra-package calls made while a lock is held. Returns
    {"edges": {(a, b): [(relpath, lineno), ...]}, "locks": set[str]}.
    ``analysis.lockwatch`` cross-checks its live edges against this."""
    if table is None:
        table = build_function_table(modules)
    may = may_acquire_fixpoint(table)
    by_mod = {mi.modname: mi for mi in modules}
    edges: dict[tuple[str, str], list[tuple[str, int]]] = {}

    def add(a: str, b: str, modname: str, lineno: int) -> None:
        if a == b:
            return
        relpath = by_mod[modname].relpath if modname in by_mod else modname
        edges.setdefault((a, b), []).append((relpath, lineno))

    for fi in table.values():
        for a, b, ln in fi.held_acquires:
            add(a, b, fi.modname, ln)
        for a, callee, ln in fi.held_calls:
            for b in may.get(callee, ()):
                add(a, b, fi.modname, ln)
    locks = {lk for pair in edges for lk in pair}
    for mi in modules:
        for name in mi.module_locks:
            locks.add(f"{mi.modname}.{name}")
        for (cls, attr) in mi.class_locks:
            locks.add(f"{mi.modname}.{cls}.{attr}")
    return {"edges": edges, "locks": locks}


def find_cycles(edges: dict[tuple[str, str], list]) -> list[list[str]]:
    """Every elementary cycle's node set (via strongly connected
    components — one finding per SCC keeps the report stable)."""
    graph: dict[str, set[str]] = {}
    for a, b in edges:
        graph.setdefault(a, set()).add(b)
        graph.setdefault(b, set())
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    onstack: set[str] = set()
    stack: list[str] = []
    counter = [0]
    sccs: list[list[str]] = []

    def strongconnect(v: str) -> None:  # iterative Tarjan
        work = [(v, iter(sorted(graph[v])))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        onstack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    onstack.add(w)
                    work.append((w, iter(sorted(graph[w]))))
                    advanced = True
                    break
                if w in onstack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                comp = []
                while True:
                    w = stack.pop()
                    onstack.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                if len(comp) > 1 or node in graph.get(node, ()):
                    sccs.append(sorted(comp))

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    return sccs


# ------------------------------------------------------------------ rules --


def rule_fork_safety(mi: ModuleInfo) -> list[Finding]:
    findings: list[Finding] = []
    has_at_fork = False
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr == "register_at_fork":
                has_at_fork = True
    # names re-assigned under a `global` declaration inside any function
    # (the re-init hook pattern: fault/spec.py:81, obs/flight.py:79)
    reinit: set[str] = set()
    for _, fn in _iter_functions(mi):
        globals_declared: set[str] = set()
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Global):
                globals_declared.update(sub.names)
            elif isinstance(sub, ast.Assign):
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Name) and tgt.id in globals_declared:
                        reinit.add(tgt.id)
    for name, lineno in sorted(mi.module_locks.items()):
        if name not in reinit or not has_at_fork:
            why = (
                "no os.register_at_fork hook in this module"
                if not has_at_fork
                else "no at-fork re-init function reassigns it (global + assign)"
            )
            findings.append(
                Finding(
                    "fork-safety",
                    mi.relpath,
                    lineno,
                    name,
                    f"module-level lock {name} is not re-initialized after fork: "
                    f"{why}; a forked child inherits it possibly held by a "
                    "thread that does not exist there (see fault/spec.py:81)",
                )
            )
    # thread creation at import time: Thread(...).start() in module body
    for node in mi.tree.body:
        for sub in ast.walk(node) if not isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ) else ():
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "start"
                and isinstance(sub.func.value, ast.Call)
            ):
                inner = sub.func.value.func
                name = inner.attr if isinstance(inner, ast.Attribute) else (
                    inner.id if isinstance(inner, ast.Name) else ""
                )
                if name == "Thread":
                    findings.append(
                        Finding(
                            "fork-safety",
                            mi.relpath,
                            sub.lineno,
                            "import-time-thread",
                            "thread started at import time: importing this "
                            "module in a fork-then-import process leaks a "
                            "thread every consumer pays for",
                        )
                    )
    return findings


def rule_blocking_under_lock(
    modules: list[ModuleInfo], table: dict[str, FuncInfo]
) -> list[Finding]:
    by_mod = {mi.modname: mi for mi in modules}
    findings: list[Finding] = []
    for fi in table.values():
        mi = by_mod[fi.modname]
        qual = fi.qualname[len(fi.modname) + 1 :]
        for held, lineno, what in fi.blocking:
            findings.append(
                Finding(
                    "blocking-under-lock",
                    mi.relpath,
                    lineno,
                    f"{qual}:{what}",
                    f"{what} inside `with {held}:` — every other thread "
                    "contending this lock stalls for the call's full "
                    "duration (the PR 3 _H2G2 / PR 4 reservoir class)",
                )
            )
    return findings


def rule_lock_order(
    modules: list[ModuleInfo], table: dict[str, FuncInfo] | None = None
) -> list[Finding]:
    graph = build_lock_graph(modules, table)
    findings: list[Finding] = []
    for comp in find_cycles(graph["edges"]):
        sites: list[str] = []
        first_loc: tuple[str, int] | None = None
        for (a, b), locs in sorted(graph["edges"].items()):
            if a in comp and b in comp:
                sites.append(f"{a}->{b} at {locs[0][0]}:{locs[0][1]}")
                if first_loc is None:
                    first_loc = locs[0]
        path, line = first_loc if first_loc else ("?", 0)
        findings.append(
            Finding(
                "lock-order",
                path,
                line,
                "+".join(comp),
                "potential deadlock: lock-acquisition cycle "
                + " | ".join(sites),
            )
        )
    return findings


_JIT_WRAPPERS = {"jit", "vmap", "pmap", "shard_map"}


def _is_jit_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr in _JIT_WRAPPERS
    if isinstance(node, ast.Name):
        return node.id in _JIT_WRAPPERS
    if isinstance(node, ast.Call):
        # partial(jax.jit, ...) / functools.partial(jax.jit, ...)
        fn = node.func
        is_partial = (isinstance(fn, ast.Name) and fn.id == "partial") or (
            isinstance(fn, ast.Attribute) and fn.attr == "partial"
        )
        if is_partial and node.args:
            return _is_jit_expr(node.args[0])
        return _is_jit_expr(fn)
    return False


def _jit_root_names(mi: ModuleInfo) -> dict[str, int]:
    """Function names in this module wrapped by jax.jit/vmap — via
    decorator, ``jax.jit(f)`` call, or ``partial(jax.jit, ...)(f)``."""
    roots: dict[str, int] = {}
    for node in mi.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    roots[node.name] = node.lineno
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Call) and _is_jit_expr(node.func):
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    roots.setdefault(arg.id, node.lineno)
    return roots


def _nested_defs(mi: ModuleInfo) -> dict[str, ast.AST]:
    """FunctionDefs NOT at module/class level (the shard_map-closure
    factories' `local` pattern), by name — reachable only through the
    wrap sites, so outside the module-level root scan."""
    top: set[int] = set()
    for node in mi.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            top.add(id(node))
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    top.add(id(sub))
    out: dict[str, ast.AST] = {}
    for node in ast.walk(mi.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if id(node) not in top:
                out[node.name] = node
    return out


def _shard_map_closures(
    mi: ModuleInfo, table: dict[str, FuncInfo]
) -> tuple[list[Finding], set[str]]:
    """Traced bodies reachable ONLY through a wrap site (PR 8's
    shard_map idiom): lambdas passed to jit/vmap/shard_map, and nested
    function defs referenced by name. Returns the purity findings inside
    those bodies plus the module-level functions they call — extra
    reachability roots for :func:`rule_jit_purity`. Bare-name calls
    resolve through the import map first (``from ops.x import f`` then
    ``shard_map(lambda v: f(v), ...)`` roots ``ops.x.f``)."""
    nested = _nested_defs(mi)
    roots: set[str] = set()
    findings: list[Finding] = []
    visited: set[int] = set()

    def visit(node: ast.AST, label: str) -> None:
        if id(node) in visited:
            return
        visited.add(id(node))
        for lineno, what in _purity_violations(mi, node, None):
            findings.append(
                Finding(
                    "jit-purity",
                    mi.relpath,
                    lineno,
                    f"{label}:{what.split()[0]}",
                    f"{mi.modname}.{label} is traced through a "
                    f"jit/vmap/shard_map wrap site and {what}: the value is "
                    "read ONCE at trace time and baked into every later "
                    "execution of the compiled program",
                )
            )
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            if isinstance(fn, ast.Name):
                target = mi.import_map.get(fn.id)
                qual = target if target is not None else f"{mi.modname}.{fn.id}"
                if qual in table:
                    roots.add(qual)
                elif fn.id in nested:
                    visit(nested[fn.id], fn.id)
            elif isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
                target_mod = mi.import_map.get(fn.value.id)
                if target_mod is not None and f"{target_mod}.{fn.attr}" in table:
                    roots.add(f"{target_mod}.{fn.attr}")

    for node in ast.walk(mi.tree):
        if not (isinstance(node, ast.Call) and _is_jit_expr(node.func)):
            continue
        for arg in node.args:
            if isinstance(arg, ast.Lambda):
                visit(arg, "<lambda>")
            elif (
                isinstance(arg, ast.Name)
                and arg.id in nested
                and f"{mi.modname}.{arg.id}" not in table
            ):
                visit(nested[arg.id], arg.id)
    return findings, roots


def _purity_violations(mi: ModuleInfo, fn: ast.AST, cls: str | None) -> list[tuple[int, str]]:
    out: list[tuple[int, str]] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and node.attr == "environ":
            if isinstance(node.value, ast.Name) and node.value.id == "os":
                out.append((node.lineno, "reads os.environ"))
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                base, attr = f.value.id, f.attr
                if base == "os" and attr == "getenv":
                    out.append((node.lineno, "reads os.environ (os.getenv)"))
                elif base == "time" and attr in (
                    "time", "monotonic", "perf_counter", "sleep", "time_ns",
                ):
                    out.append((node.lineno, f"calls time.{attr}"))
                elif base == "random" and "random" not in mi.import_map:
                    out.append((node.lineno, f"calls stdlib random.{attr}"))
                elif base == "obs" and attr in (
                    "count", "event", "gauge", "observe", "span", "bytes_moved",
                ):
                    out.append((node.lineno, f"touches obs.{attr}"))
        elif isinstance(node, ast.With):
            for item in node.items:
                if _lockish(mi, item.context_expr, cls):
                    out.append((node.lineno, "acquires a lock"))
    return out


def rule_jit_purity(
    modules: list[ModuleInfo], table: dict[str, FuncInfo] | None = None
) -> list[Finding]:
    if table is None:
        table = build_function_table(modules)
    roots: dict[str, int] = {}
    closure_findings: list[Finding] = []
    for mi in modules:
        for name, lineno in _jit_root_names(mi).items():
            qual = f"{mi.modname}.{name}"
            if qual in table:
                roots[qual] = lineno
        # shard_map/jit wrap sites whose traced body is a lambda or a
        # nested def (the PR 8 sharded-kernel factories): the body is
        # purity-checked directly and the module-level functions it
        # calls join the root set
        extra_findings, extra_roots = _shard_map_closures(mi, table)
        closure_findings.extend(extra_findings)
        for qual in extra_roots:
            roots.setdefault(qual, 0)
    # reachability over intra-package call edges
    reachable: set[str] = set()
    frontier = list(roots)
    while frontier:
        q = frontier.pop()
        if q in reachable:
            continue
        reachable.add(q)
        frontier.extend(table[q].calls - reachable)
    by_mod = {mi.modname: mi for mi in modules}
    findings: list[Finding] = list(closure_findings)
    for qual in sorted(reachable):
        fi = table[qual]
        mi = by_mod[fi.modname]
        cls = qual.rsplit(".", 2)[-2] if qual.count(".") >= 2 and qual.rsplit(
            ".", 2
        )[-2][0:1].isupper() else None
        for lineno, what in _purity_violations(mi, fi.node, cls):
            findings.append(
                Finding(
                    "jit-purity",
                    mi.relpath,
                    lineno,
                    f"{qual.rsplit('.', 1)[-1]}:{what.split()[0]}",
                    f"{qual} is reachable from a jax.jit/vmap wrap site and "
                    f"{what}: the value is read ONCE at trace time and baked "
                    "into every later execution of the compiled program",
                )
            )
    return findings


_METRIC_METHODS = {"count", "gauge", "observe", "span", "bytes_moved"}
_METRIC_KIND = {
    "count": "counter",
    "gauge": "gauge",
    "observe": "histogram",
    "span": "span",
    "bytes_moved": "counter",
}


def _literal_name(node: ast.AST) -> str | None:
    """A str constant, f-string (placeholders -> '*'), or conditional of
    constants; None when dynamic beyond that."""
    names = _literal_names(node)
    return names[0] if names else None


def _literal_names(node: ast.AST) -> list[str]:
    """Every name a metric/site argument can statically evaluate to —
    a conditional expression contributes BOTH branches (the router's
    ``"...affinity" if k == 0 else "...fallback"`` idiom)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            parts.append(v.value if isinstance(v, ast.Constant) else "*")
        return ["".join(parts)]
    if isinstance(node, ast.IfExp):
        return _literal_names(node.body) + _literal_names(node.orelse)
    return []


# helpers that EMIT a derived metric family: calling them is emitting.
# observe_compile_ms(op, ...) / first_dispatch(op, *dims) record into the
# serve.compile_ms.<op> histograms (serve/buckets.py) — before this scan
# those call sites were invisible to the catalog check (a PR 5 gap: the
# metric literal lives in the helper, the FAMILY key at the call site)
# waterfall.leg(name) is a span of that name whose milliseconds land in
# serve.stage_ms.device.<name>, and names xla.compile_ms.<name>
_DERIVED_EMITTERS = {
    "observe_compile_ms": (("histogram", "serve.compile_ms.{}"),),
    "first_dispatch": (("histogram", "serve.compile_ms.{}"),),
    "leg": (
        ("span", "{}"),
        ("histogram", "serve.stage_ms.device.{}"),
        ("histogram", "xla.compile_ms.{}"),
    ),
}


def rule_obs_discipline(mi: ModuleInfo, catalog) -> list[Finding]:
    if mi.modname in ("obs.catalog",):
        return []
    findings: list[Finding] = []
    emitting_bases = {"obs", "reg", "registry"}
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        attr = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None
        )
        if attr in _DERIVED_EMITTERS and (attr == "leg" or mi.modname != "serve.buckets"):
            # serve.buckets itself is the compile helpers' home: its internal
            # obs.observe(...) literals are scanned by the branch below
            names = _literal_names(node.args[0]) if node.args else []
            for (kind, template), op in itertools.product(_DERIVED_EMITTERS[attr], names):
                name = template.format(op)
                if not _METRIC_GRAMMAR_RE.match(name):
                    findings.append(
                        Finding(
                            "obs-discipline",
                            mi.relpath,
                            node.lineno,
                            f"grammar:{name}",
                            f"derived metric name {name!r} (via {attr}) "
                            "violates the grammar "
                            "[a-z][a-z0-9_]*(.[a-z0-9_]+)* — it would "
                            "collapse lossily in the Prometheus exposition",
                        )
                    )
                elif catalog is not None and not catalog.declared(kind, name):
                    findings.append(
                        Finding(
                            "obs-discipline",
                            mi.relpath,
                            node.lineno,
                            f"undeclared:{name}",
                            f"{kind} {name!r} (emitted through {attr}) is not "
                            "declared in obs/catalog.py — families keyed at "
                            "the call site must be visible to exposition "
                            "consumers too",
                        )
                    )
            continue
        if not (
            isinstance(fn, ast.Attribute)
            and fn.attr in _METRIC_METHODS
            and isinstance(fn.value, ast.Name)
            and fn.value.id in emitting_bases
        ):
            continue
        if not node.args:
            continue
        kind = _METRIC_KIND[fn.attr]
        # a conditional name contributes every branch; fully dynamic
        # names (bare variables) are the delta/merge plumbing — skipped
        for name in _literal_names(node.args[0]):
            if fn.attr == "bytes_moved":
                name = f"{name}.bytes_moved"
            if not _METRIC_GRAMMAR_RE.match(name):
                findings.append(
                    Finding(
                        "obs-discipline",
                        mi.relpath,
                        node.lineno,
                        f"grammar:{name}",
                        f"metric name {name!r} violates the grammar "
                        "[a-z][a-z0-9_]*(.[a-z0-9_]+)* — it would collapse "
                        "lossily in the Prometheus exposition",
                    )
                )
            elif catalog is not None and not catalog.declared(kind, name):
                findings.append(
                    Finding(
                        "obs-discipline",
                        mi.relpath,
                        node.lineno,
                        f"undeclared:{name}",
                        f"{kind} {name!r} is not declared in obs/catalog.py — "
                        "exposition consumers (dashboards, SLOs, "
                        "validate_text) can't see undeclared drift",
                    )
                )
    # device-timed spans must declare work_bytes: `with obs.span(...) as
    # sp:` whose body assigns sp.result gets a roofline verdict ONLY when
    # the span call passed work_bytes
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            call = item.context_expr
            if not (
                isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr == "span"
                and isinstance(call.func.value, ast.Name)
                and call.func.value.id in emitting_bases
                and isinstance(item.optional_vars, ast.Name)
            ):
                continue
            sp = item.optional_vars.id
            assigns_result = any(
                isinstance(sub, ast.Assign)
                and any(
                    isinstance(t, ast.Attribute)
                    and t.attr == "result"
                    and isinstance(t.value, ast.Name)
                    and t.value.id == sp
                    for t in sub.targets
                )
                for stmt in node.body
                for sub in ast.walk(stmt)
            )
            has_work_bytes = any(kw.arg == "work_bytes" for kw in call.keywords)
            name = _literal_name(call.args[0]) if call.args else "?"
            if assigns_result and not has_work_bytes:
                findings.append(
                    Finding(
                        "obs-discipline",
                        mi.relpath,
                        node.lineno,
                        f"no-work-bytes:{name}",
                        f"span {name!r} blocks on a device result "
                        f"({sp}.result) but declares no work_bytes — no "
                        "roofline verdict, the exact blind spot that let "
                        "878 Ghash/s ship",
                    )
                )
    return findings


def rule_env_registry(mi: ModuleInfo, declared_env: set[str]) -> list[Finding]:
    if mi.modname in ("envreg",):
        return []
    findings: list[Finding] = []
    for node in ast.walk(mi.tree):
        var = None
        if isinstance(node, ast.Call):
            fn = node.func
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr == "get"
                and isinstance(fn.value, ast.Attribute)
                and fn.value.attr == "environ"
            ) or (
                isinstance(fn, ast.Attribute)
                and fn.attr == "getenv"
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "os"
            ):
                if node.args and isinstance(node.args[0], ast.Constant):
                    var = node.args[0].value
        elif isinstance(node, ast.Subscript):
            if (
                isinstance(node.value, ast.Attribute)
                and node.value.attr == "environ"
                and isinstance(node.slice, ast.Constant)
            ):
                var = node.slice.value
        if (
            isinstance(var, str)
            and var.startswith("ETH_SPECS_")
            and var not in declared_env
        ):
            findings.append(
                Finding(
                    "env-registry",
                    mi.relpath,
                    node.lineno,
                    var,
                    f"{var} is read here but not declared in envreg.py — "
                    "undeclared knobs never reach docs/env-reference.md and "
                    "rot out of the operator's view",
                )
            )
    return findings


def rule_fault_site_registry(
    mi: ModuleInfo, declared_sites: set[str]
) -> list[Finding]:
    if mi.modname in ("fault.sites", "fault.spec"):
        return []
    findings: list[Finding] = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        is_fault_call = (
            isinstance(fn, ast.Attribute)
            and fn.attr in ("check", "corrupt")
            and isinstance(fn.value, ast.Name)
            and fn.value.id == "fault"
        )
        site_arg = None
        if is_fault_call and node.args:
            site_arg = node.args[0]
        else:
            for kw in node.keywords:
                if kw.arg == "site":
                    site_arg = kw.value
        if site_arg is None:
            continue
        sites: list[str] = _literal_names(site_arg)
        if isinstance(site_arg, ast.Name):
            const = mi.str_consts.get(site_arg.id)
            if const is not None:
                sites = [const]
        for site in sites:
            if "*" in site:
                continue
            if site not in declared_sites:
                findings.append(
                    Finding(
                        "fault-site-registry",
                        mi.relpath,
                        node.lineno,
                        site,
                        f"fault site {site!r} is not declared in "
                        "fault/sites.py — undeclared sites are invisible to "
                        "the chaos grammar docs and nothing proves a test "
                        "ever injects them",
                    )
                )
    return findings


def check_site_references(repo_root: str, sites: dict) -> list[Finding]:
    """Project-level completeness: every declared fault site must appear
    in a chaos test (tests/) or the docs failure matrix (docs/)."""
    corpus: list[str] = []
    for base, exts in (("tests", (".py",)), ("docs", (".md",)), ("scripts", (".py",))):
        root = os.path.join(repo_root, base)
        for dirpath, _, files in os.walk(root):
            for f in files:
                if f.endswith(exts):
                    try:
                        with open(os.path.join(dirpath, f), encoding="utf-8") as fh:
                            corpus.append(fh.read())
                    except OSError:
                        pass
    blob = "\n".join(corpus)
    findings = []
    for site in sorted(sites):
        if site not in blob:
            findings.append(
                Finding(
                    "fault-site-registry",
                    f"{PACKAGE}/fault/sites.py",
                    1,
                    f"unreferenced:{site}",
                    f"declared fault site {site!r} is referenced by no chaos "
                    "test and no docs failure-matrix entry — an injection "
                    "point nothing exercises is a dead invariant",
                )
            )
    return findings


def check_env_stale(modules: list[ModuleInfo], declared_env: set[str],
                    repo_root: str) -> list[Finding]:
    """Declared env vars nothing reads anywhere in the repo are stale."""
    read: set[str] = set()
    scan_roots = [os.path.join(repo_root, d) for d in (PACKAGE, "scripts", "tests")]
    pat = re.compile(r"ETH_SPECS_[A-Z0-9_]+")
    for root in scan_roots:
        paths = []
        if os.path.isfile(root):
            paths = [root]
        else:
            for dirpath, _, files in os.walk(root):
                paths.extend(
                    os.path.join(dirpath, f) for f in files if f.endswith(".py")
                )
        for p in paths:
            if p.endswith("envreg.py"):
                # the registry's own declaration strings must not count
                # as reads — they would satisfy the stale check for
                # every declared var, making it unable to ever fire
                continue
            try:
                with open(p, encoding="utf-8") as fh:
                    read.update(pat.findall(fh.read()))
            except OSError:
                pass
    return [
        Finding(
            "env-registry",
            f"{PACKAGE}/envreg.py",
            1,
            f"stale:{var}",
            f"{var} is declared in envreg.py but nothing in the repo reads "
            "it — stale declarations teach operators knobs that do nothing",
        )
        for var in sorted(declared_env - read)
    ]


# ------------------------------------------------------------------ engine --


def _suppressed(finding: Finding, mi: ModuleInfo | None) -> bool:
    if mi is None:
        return False
    for line in (finding.line, finding.line - 1):
        rules = mi.suppressions.get(line)
        if rules and (finding.rule in rules or "all" in rules):
            return True
    return False


def collect_modules(repo_root: str, paths: list[str] | None = None) -> list[ModuleInfo]:
    package_root = os.path.join(repo_root, PACKAGE)
    roots = paths or [package_root]
    out: list[ModuleInfo] = []
    for root in roots:
        if os.path.isfile(root):
            mi = load_module(root, repo_root, package_root)
            if mi:
                out.append(mi)
            continue
        for dirpath, dirnames, files in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    mi = load_module(os.path.join(dirpath, f), repo_root, package_root)
                    if mi:
                        out.append(mi)
    return out


def run(
    repo_root: str,
    paths: list[str] | None = None,
    rules: set[str] | None = None,
    catalog=None,
    declared_env: set[str] | None = None,
    declared_sites: dict | None = None,
    project_checks: bool = True,
) -> list[Finding]:
    """Run the selected rules; returns unsuppressed findings sorted by
    (path, line). The registry arguments default to the live project
    catalogs; tests pass their own to lint fixtures hermetically."""
    rules = set(rules) if rules is not None else set(ALL_RULES)
    modules = collect_modules(repo_root, paths)
    by_path = {mi.relpath: mi for mi in modules}

    if catalog is None and ("obs-discipline" in rules):
        from eth_consensus_specs_tpu.obs import catalog as catalog_mod

        catalog = catalog_mod
    if declared_env is None and "env-registry" in rules:
        from eth_consensus_specs_tpu import envreg

        declared_env = {v.name for v in envreg.ENV_VARS}
    if declared_sites is None and "fault-site-registry" in rules:
        from eth_consensus_specs_tpu.fault import sites as sites_mod

        declared_sites = dict(sites_mod.SITES)

    # one function-table build (the expensive held-stack walk) feeds the
    # three rules that need call/lock structure
    table: dict[str, FuncInfo] | None = None
    if rules & {"blocking-under-lock", "lock-order", "jit-purity"}:
        table = build_function_table(modules)

    findings: list[Finding] = []
    for mi in modules:
        if "fork-safety" in rules:
            findings.extend(rule_fork_safety(mi))
        if "obs-discipline" in rules:
            findings.extend(rule_obs_discipline(mi, catalog))
        if "env-registry" in rules:
            findings.extend(rule_env_registry(mi, declared_env or set()))
        if "fault-site-registry" in rules:
            findings.extend(rule_fault_site_registry(mi, set(declared_sites or ())))
    if "blocking-under-lock" in rules:
        findings.extend(rule_blocking_under_lock(modules, table))
    if "lock-order" in rules:
        findings.extend(rule_lock_order(modules, table))
    if "jit-purity" in rules:
        findings.extend(rule_jit_purity(modules, table))
    if project_checks:
        if "fault-site-registry" in rules and declared_sites:
            findings.extend(check_site_references(repo_root, declared_sites))
        if "env-registry" in rules and declared_env:
            findings.extend(check_env_stale(modules, declared_env, repo_root))

    findings = [f for f in findings if not _suppressed(f, by_path.get(f.path))]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule, f.symbol))


# ---------------------------------------------------------------- baseline --


def load_baseline(path: str) -> dict[str, int]:
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
        return {str(k): int(v) for k, v in data.get("findings", {}).items()}
    except (OSError, ValueError):
        return {}


def baseline_diff(findings: list[Finding], baseline: dict[str, int]) -> dict:
    """Split findings into baselined and new; report stale baseline
    entries (fixed findings whose fingerprint should be ratcheted out)."""
    counts: dict[str, int] = {}
    for f in findings:
        counts[f.fingerprint] = counts.get(f.fingerprint, 0) + 1
    new: list[Finding] = []
    budget = dict(baseline)
    for f in findings:
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
        else:
            new.append(f)
    stale = sorted(fp for fp, n in baseline.items() if counts.get(fp, 0) < n)
    return {"new": new, "stale": stale, "counts": counts}


def write_baseline(path: str, findings: list[Finding], *, force: bool = False) -> dict:
    """Ratcheting write: per rule, the new count may only DECREASE
    relative to the existing baseline (force overrides, for bootstrap).
    Raises ValueError on a would-grow rule."""
    old = load_baseline(path)
    old_by_rule: dict[str, int] = {}
    for fp, n in old.items():
        rule = fp.split("::")[1] if fp.count("::") >= 2 else "?"
        old_by_rule[rule] = old_by_rule.get(rule, 0) + n
    new_by_rule: dict[str, int] = {}
    for f in findings:
        new_by_rule[f.rule] = new_by_rule.get(f.rule, 0) + 1
    if not force and os.path.exists(path):
        grew = {
            r: (old_by_rule.get(r, 0), n)
            for r, n in new_by_rule.items()
            if n > old_by_rule.get(r, 0)
        }
        if grew:
            raise ValueError(
                "baseline ratchet: these rules would GROW, fix the findings "
                f"instead of baselining them: {grew}"
            )
    counts: dict[str, int] = {}
    for f in findings:
        counts[f.fingerprint] = counts.get(f.fingerprint, 0) + 1
    payload = {"version": 1, "findings": dict(sorted(counts.items()))}
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return payload

"""dltpu-check v2: concurrency auditor for the serving/elastic thread fleet.

The repo now runs a real thread fleet — zoo loader threads, the batcher
dispatch loop, heartbeat/metrics/fleet-scrape daemons, prefetch workers,
supervisor watchers — and every one of the lock-discipline rules that
keeps them honest lived only in code review. This module is the third
analysis layer (after ``lint.py``'s DLT1xx policy rules and
``jaxpr_audit.py``'s structural audits): six concurrency rules over the
stdlib ``ast``, sharing ``lint.py``'s Finding/pragma/ratchet machinery
so ``tools/check.py --ci`` gates them identically:

  DLT200  shared mutable ``self.X`` written from a thread-entry function
          (any ``Thread(target=...)`` / ``obs_threads.spawn(...)``
          callee, resolved transitively one level) AND written from a
          public method without holding the class's lock.
  DLT201  lock acquired in inconsistent order across functions: the
          static lock-order graph (``with``-nesting plus ``acquire()``
          sequencing per scope) contains a cycle — a potential deadlock.
  DLT202  indefinite blocking call (``queue.get()`` / ``.join()`` /
          ``.acquire()`` / ``.wait()`` without timeout) while holding a
          lock.
  DLT203  non-daemon thread with no ``join()`` in its spawn scope (and
          no pragma naming the stop-flag protocol that retires it).
  DLT204  ``threading.Thread`` constructed outside the
          ``obs/threads.py`` spawn registry — unregistered threads are
          invisible to the inventory and the sanitizer.
  DLT205  time-of-check/time-of-use: ``if k in self.d`` and the
          ``self.d[k]`` use sit in different lock regions, so the state
          can change between them.

Suppression and ratchet are byte-compatible with DLT1xx: append
``# dltpu: allow(DLT200)`` to the line (or the line above), and
``analysis/baseline.json`` budgets both rule families per (file, rule).

Lock identity — the static/runtime join: every lock this module tracks
is keyed by the file:line of its ``threading.Lock()`` / ``RLock()``
creation site. ``lock_order_graph()`` exports nodes and edges under
that key, and ``analysis/threadsan.py``'s instrumented locks record the
same creator file:line at runtime, so the sanitizer can seed its
order-consistency check from the static graph.

Standalone-loadable: imports nothing heavy. When loaded by path (the
``tools/check.py`` / ``tools/obs_report.py`` pattern) it resolves
``lint.py`` from ``sys.modules`` or loads the adjacent file directly.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple


def _lint_mod():
    """The DLT1xx module, however this one was loaded.

    In-package: a plain relative import. Standalone (loaded by file
    path, no package parent): reuse whichever alias check.py or
    obs_report.py already registered, else load the adjacent lint.py.
    """
    try:
        from . import lint as _lint  # type: ignore[no-redef]
        return _lint
    except ImportError:
        pass
    for name in ("deeplearning_tpu.analysis.lint", "_dltpu_lint",
                 "_dltpu_lint_report"):
        mod = sys.modules.get(name)
        if mod is not None:
            return mod
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint.py")
    spec = importlib.util.spec_from_file_location("_dltpu_lint", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


_lint = _lint_mod()
Finding = _lint.Finding
_qualname = _lint._qualname
_call_kw = _lint._call_kw
_Index = _lint._Index
_scope_walk = _lint._scope_walk
_PRAGMA = _lint._PRAGMA

__all__ = [
    "RULES", "Finding", "lint_source", "lint_file", "lint_tree",
    "lock_order_graph", "ratchet_status", "DEFAULT_SCAN",
    "DEFAULT_BASELINE", "THREAD_REGISTRY",
]

RULES: Dict[str, str] = {
    "DLT200": "shared attribute written from thread and from public "
              "method without the class's lock",
    "DLT201": "inconsistent lock acquisition order (potential deadlock "
              "cycle)",
    "DLT202": "indefinite blocking call while holding a lock",
    "DLT203": "non-daemon thread with no join() in its spawn scope",
    "DLT204": "threading.Thread created outside the obs/threads.py "
              "spawn registry",
    "DLT205": "check-then-use on shared dict/list across lock regions",
}

# the one file allowed to call threading.Thread directly (DLT204)
THREAD_REGISTRY = "deeplearning_tpu/obs/threads.py"

DEFAULT_SCAN = _lint.DEFAULT_SCAN
DEFAULT_BASELINE = _lint.DEFAULT_BASELINE

# cheap substring gate: a file with no thread/lock vocabulary cannot
# trip any DLT2xx rule, so the tree scan parses only the fleet files
# and the combined --ci run stays inside its 3s budget
_PREFILTER = ("threading", "Thread(", ".spawn(", "Lock(", "_lock")


def _relevant(src: str) -> bool:
    return any(tok in src for tok in _PREFILTER)


# ---------------------------------------------------------- lock model
class _ThreadingAliases:
    """Names that resolve to the threading module / its Lock ctors."""

    def __init__(self, nodes: Iterable[ast.AST]):
        self.modules: Set[str] = set()      # import threading [as t]
        self.lock_ctors: Set[str] = set()   # from threading import Lock
        for node in nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "threading":
                        self.modules.add(a.asname or a.name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "threading":
                    for a in node.names:
                        if a.name in ("Lock", "RLock"):
                            self.lock_ctors.add(a.asname or a.name)

    def is_lock_ctor(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        q = _qualname(node.func)
        if q is None:
            return False
        if q in self.lock_ctors:
            return True
        head, _, tail = q.partition(".")
        return head in self.modules and tail in ("Lock", "RLock")


class _Locks:
    """Every lock declared in the file, keyed for the runtime join.

    - ``attrs[class_name][attr]`` = creation line of
      ``self.<attr> = threading.Lock()`` inside that class.
    - ``globals_[name]`` = creation line of a module-level
      ``NAME = threading.Lock()``.
    Lock ids are ``"<path>::<Class>.<attr>"`` / ``"<path>::<name>"``;
    ``line_of`` maps an id back to its creation line.
    """

    def __init__(self, idx: _Index, al: _ThreadingAliases, path: str):
        self.path = path
        self.attrs: Dict[str, Dict[str, int]] = {}
        self.globals_: Dict[str, int] = {}
        self.line_of: Dict[str, int] = {}
        class_of: Dict[ast.AST, str] = {}
        for node in idx.nodes:
            if isinstance(node, ast.ClassDef):
                for sub in ast.walk(node):
                    class_of[sub] = node.name
        for node in idx.nodes:
            if not isinstance(node, ast.Assign):
                continue
            if not al.is_lock_ctor(node.value):
                continue
            line = node.value.lineno
            for t in node.targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self" and node in class_of:
                    cls = class_of[node]
                    self.attrs.setdefault(cls, {})[t.attr] = line
                    self.line_of[f"{path}::{cls}.{t.attr}"] = line
                elif isinstance(t, ast.Name) and node not in class_of:
                    self.globals_[t.id] = line
                    self.line_of[f"{path}::{t.id}"] = line

    def ref(self, expr: ast.AST, class_name: Optional[str]
            ) -> Optional[str]:
        """Lock id for an expression naming a declared lock, else None."""
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self" and class_name:
            if expr.attr in self.attrs.get(class_name, {}):
                return f"{self.path}::{class_name}.{expr.attr}"
            return None
        if isinstance(expr, ast.Name) and expr.id in self.globals_:
            return f"{self.path}::{expr.id}"
        return None


# ------------------------------------------------------- file analysis
class _Analysis:
    """Shared per-file context for every DLT2xx pass."""

    def __init__(self, tree: ast.Module, path: str):
        self.path = path
        self.idx = _Index(tree)
        self.al = _ThreadingAliases(self.idx.nodes)
        self.locks = _Locks(self.idx, self.al, path)
        self.classes: Dict[str, ast.ClassDef] = {}
        self.methods: Dict[str, Dict[str, ast.AST]] = {}
        self.class_of: Dict[ast.AST, str] = {}
        for node in self.idx.nodes:
            if isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
                meths: Dict[str, ast.AST] = {}
                for st in node.body:
                    if isinstance(st, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                        meths[st.name] = st
                self.methods[node.name] = meths
                for sub in ast.walk(node):
                    self.class_of.setdefault(sub, node.name)
        # edges discovered by the DLT201 pass: (src, dst, line, func)
        self.edges: List[Tuple[str, str, int, str]] = []

    def enclosing_func(self, node: ast.AST) -> Optional[ast.AST]:
        up = self.idx.parents.get(node)
        while up is not None:
            if isinstance(up, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return up
            up = self.idx.parents.get(up)
        return None

    def enclosing_class(self, node: ast.AST) -> Optional[str]:
        return self.class_of.get(node)

    # ------------------------------------------------- spawn targets
    def thread_calls(self) -> List[Tuple[ast.Call, str]]:
        """Every Thread(...) / spawn(...) call: (call, kind)."""
        out = []
        for call in self.idx.calls:
            q = _qualname(call.func) or ""
            last = q.rsplit(".", 1)[-1]
            if last == "Thread" and (
                    q in ("Thread", "threading.Thread")
                    or any(q == f"{m}.Thread"
                           for m in self.al.modules)):
                out.append((call, "Thread"))
            elif last == "spawn":
                out.append((call, "spawn"))
        return out

    def thread_entry_methods(self) -> Dict[str, Set[str]]:
        """{class_name: method names reachable from a thread entry},
        resolved transitively one level (an entry's direct self.*
        callees count too). Module-level targets land under ''."""
        entries: Dict[str, Set[str]] = {}

        def record(target: ast.AST, call: ast.Call) -> None:
            if isinstance(target, ast.Attribute) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id == "self":
                cls = self.enclosing_class(call)
                if cls and target.attr in self.methods.get(cls, {}):
                    entries.setdefault(cls, set()).add(target.attr)
            elif isinstance(target, ast.Name):
                entries.setdefault("", set()).add(target.id)

        for call, kind in self.thread_calls():
            target = _call_kw(call, "target")
            if target is None and kind == "spawn" and call.args:
                target = call.args[0]
            if target is not None:
                record(target, call)

        # one level of transitive closure: self.foo() inside an entry
        for cls, names in list(entries.items()):
            if not cls:
                continue
            meths = self.methods.get(cls, {})
            for name in list(names):
                fn = meths.get(name)
                if fn is None:
                    continue
                for sub in _scope_walk(fn.body):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Attribute) and \
                            isinstance(sub.func.value, ast.Name) and \
                            sub.func.value.id == "self" and \
                            sub.func.attr in meths:
                        entries[cls].add(sub.func.attr)
        return entries

    # --------------------------------------------------- guardedness
    def write_guarded(self, node: ast.AST, func: ast.AST,
                      class_name: str) -> bool:
        """Is this write lexically under ``with self._lock`` (any class
        lock), or after a ``self._lock.acquire()`` in the same scope?"""
        up = self.idx.parents.get(node)
        while up is not None and up is not func:
            if isinstance(up, ast.With):
                for item in up.items:
                    if self.locks.ref(item.context_expr, class_name):
                        return True
            up = self.idx.parents.get(up)
        for sub in _scope_walk(func.body):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr == "acquire" and \
                    sub.lineno <= getattr(node, "lineno", 0) and \
                    self.locks.ref(sub.func.value, class_name):
                return True
        return False

    def self_writes(self, func: ast.AST) -> List[Tuple[str, ast.AST]]:
        """(attr, node) for every ``self.X = ...`` / ``self.X[...] =``
        / ``self.X += ...`` / ``del self.X[...]`` in the function."""
        out: List[Tuple[str, ast.AST]] = []

        def attr_of(t: ast.AST) -> Optional[str]:
            if isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and t.value.id == "self":
                return t.attr
            if isinstance(t, ast.Subscript):
                return attr_of(t.value)
            return None

        for node in _scope_walk(func.body):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = list(node.targets)
            for t in targets:
                attr = attr_of(t)
                if attr is not None:
                    out.append((attr, node))
        return out


# ------------------------------------------------------------- passes
def _rule_dlt200(an: _Analysis, add) -> None:
    entries = an.thread_entry_methods()
    for cls, entry_names in entries.items():
        if not cls:
            continue
        lock_attrs = an.locks.attrs.get(cls, {})
        if not lock_attrs:
            continue               # no lock to hold — not this rule's bug
        meths = an.methods.get(cls, {})
        thread_writes: Set[str] = set()
        for name in entry_names:
            fn = meths.get(name)
            if fn is None:
                continue
            for attr, _node in an.self_writes(fn):
                if attr not in lock_attrs:
                    thread_writes.add(attr)
        if not thread_writes:
            continue
        for name, fn in meths.items():
            if name.startswith("_") or name in entry_names:
                continue           # public, non-thread methods only
            for attr, node in an.self_writes(fn):
                if attr not in thread_writes:
                    continue
                if an.write_guarded(node, fn, cls):
                    continue
                add("DLT200", node,
                    f"'{cls}.{attr}' is written by thread entry "
                    f"{sorted(n for n in entry_names if n in meths)} "
                    f"and here in public '{name}()' without holding "
                    f"the class's lock")


def _lock_edges(an: _Analysis) -> None:
    """Populate an.edges: lock-order pairs from with-nesting and
    acquire()/release() sequencing, per function scope."""

    def visit_block(stmts: Sequence[ast.stmt], held: List[str],
                    cls: Optional[str], fname: str) -> None:
        held = list(held)
        for st in stmts:
            held = visit_stmt(st, held, cls, fname)

    def visit_stmt(st: ast.stmt, held: List[str],
                   cls: Optional[str], fname: str) -> List[str]:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return held
        if isinstance(st, ast.With):
            acquired = []
            for item in st.items:
                lk = an.locks.ref(item.context_expr, cls)
                if lk:
                    for h in held:
                        if h != lk:
                            an.edges.append((h, lk, st.lineno, fname))
                    acquired.append(lk)
            visit_block(st.body, held + acquired, cls, fname)
            return held
        if isinstance(st, ast.Expr) and isinstance(st.value, ast.Call):
            call = st.value
            if isinstance(call.func, ast.Attribute):
                lk = an.locks.ref(call.func.value, cls)
                if lk is not None:
                    if call.func.attr == "acquire":
                        for h in held:
                            if h != lk:
                                an.edges.append((h, lk, st.lineno,
                                                 fname))
                        return held + [lk]
                    if call.func.attr == "release":
                        return [h for h in held if h != lk]
        for _field, value in ast.iter_fields(st):
            if isinstance(value, list) and value and \
                    isinstance(value[0], ast.stmt):
                visit_block(value, held, cls, fname)
        return held

    visit_block(an.idx.tree.body, [], None, "<module>")
    for fn in an.idx.func_defs:
        cls = an.enclosing_class(fn)
        visit_block(fn.body, [], cls, fn.name)


def _find_cycles(edges: Iterable[Tuple[str, str]]) -> List[List[str]]:
    """Simple cycles in the lock-order graph, each reported once."""
    adj: Dict[str, Set[str]] = {}
    for src, dst in edges:
        adj.setdefault(src, set()).add(dst)
    cycles: List[List[str]] = []
    seen_cycles: Set[Tuple[str, ...]] = set()

    def dfs(node: str, path: List[str], on_path: Set[str],
            done: Set[str]) -> None:
        on_path.add(node)
        path.append(node)
        for nxt in sorted(adj.get(node, ())):
            if nxt in on_path:
                cyc = path[path.index(nxt):]
                # canonical rotation so each cycle dedups
                k = min(range(len(cyc)), key=lambda i: cyc[i])
                canon = tuple(cyc[k:] + cyc[:k])
                if canon not in seen_cycles:
                    seen_cycles.add(canon)
                    cycles.append(list(canon))
            elif nxt not in done:
                dfs(nxt, path, on_path, done)
        on_path.discard(node)
        path.pop()
        done.add(node)

    done: Set[str] = set()
    for node in sorted(adj):
        if node not in done:
            dfs(node, [], set(), done)
    return cycles


def _rule_dlt201(an: _Analysis, add) -> None:
    _lock_edges(an)
    cycles = _find_cycles((s, d) for s, d, _l, _f in an.edges)
    for cyc in cycles:
        # anchor the finding on the latest edge participating in the
        # cycle — by construction that edge closed it
        pairs = {(cyc[i], cyc[(i + 1) % len(cyc)])
                 for i in range(len(cyc))}
        where = max((e for e in an.edges if (e[0], e[1]) in pairs),
                    key=lambda e: e[2])
        display = " -> ".join(c.split("::", 1)[-1] for c in cyc)
        node = ast.stmt()
        node.lineno, node.col_offset = where[2], 0
        add("DLT201", node,
            f"lock order cycle {display} (edge taken in "
            f"'{where[3]}') — two threads interleaving these orders "
            "deadlock")


_BLOCKING_EXEMPT_KW = ("timeout",)


def _is_blocking_call(call: ast.Call) -> Optional[str]:
    """Name of the indefinitely-blocking method, else None."""
    if not isinstance(call.func, ast.Attribute):
        return None
    attr = call.func.attr
    if _call_kw(call, "timeout") is not None:
        return None
    if attr == "get":
        # dict.get(k[, default]) always has args; queue.get() has none
        blk = _call_kw(call, "block")
        if not call.args and (blk is None or not (
                isinstance(blk, ast.Constant) and blk.value is False)):
            return "get"
        return None
    if attr == "join" and not call.args:
        return "join"
    if attr == "acquire":
        blk = _call_kw(call, "blocking")
        if isinstance(blk, ast.Constant) and blk.value is False:
            return None
        if call.args and isinstance(call.args[0], ast.Constant) and \
                call.args[0].value is False:
            return None
        return "acquire"
    if attr in ("wait", "wait_for") and not call.args:
        return attr
    return None


def _rule_dlt202(an: _Analysis, add) -> None:
    for node in an.idx.nodes:
        if not isinstance(node, ast.With):
            continue
        cls = an.enclosing_class(node)
        held = [item.context_expr for item in node.items
                if an.locks.ref(item.context_expr, cls)]
        if not held:
            continue
        held_q = {_qualname(h) for h in held}
        for sub in _scope_walk(node.body):
            if not isinstance(sub, ast.Call):
                continue
            blocked = _is_blocking_call(sub)
            if blocked is None:
                continue
            recv = _qualname(sub.func.value) \
                if isinstance(sub.func, ast.Attribute) else None
            if blocked in ("wait", "wait_for") and recv in held_q:
                continue       # Condition.wait releases the held lock
            add("DLT202", sub,
                f"'.{blocked}()' with no timeout while holding "
                f"{sorted(q for q in held_q if q)} — a stuck peer "
                "wedges every waiter on this lock")


def _rule_dlt203(an: _Analysis, add) -> None:
    for call, kind in an.thread_calls():
        daemon = _call_kw(call, "daemon")
        if kind == "spawn":
            # registry default is daemon=True
            nondaemon = isinstance(daemon, ast.Constant) and \
                daemon.value is False
        else:
            # threading.Thread default is daemon=False
            nondaemon = daemon is None or (
                isinstance(daemon, ast.Constant) and
                daemon.value is False)
        if not nondaemon:
            continue
        func = an.enclosing_func(call)
        body = func.body if func is not None else an.idx.tree.body
        joined = any(
            isinstance(sub, ast.Call) and
            isinstance(sub.func, ast.Attribute) and
            sub.func.attr == "join"
            for sub in _scope_walk(body))
        if not joined:
            add("DLT203", call,
                "non-daemon thread is never join()ed in this scope — "
                "it outlives shutdown invisibly (join it, or pragma "
                "with the stop-flag that retires it)")


def _rule_dlt204(an: _Analysis, add) -> None:
    if an.path.endswith(THREAD_REGISTRY):
        return
    for call, kind in an.thread_calls():
        if kind != "Thread":
            continue
        add("DLT204", call,
            "threading.Thread outside obs/threads.py — route it "
            "through obs_threads.spawn() so the fleet inventory and "
            "thread sanitizer can see it")


def _rule_dlt205(an: _Analysis, add) -> None:
    def key_repr(node: ast.AST) -> Optional[str]:
        q = _qualname(node)
        if q is not None:
            return q
        if isinstance(node, ast.Constant):
            return repr(node.value)
        return None

    def region_of(node: ast.AST, func: ast.AST,
                  cls: Optional[str]) -> Optional[int]:
        up = an.idx.parents.get(node)
        while up is not None and up is not func:
            if isinstance(up, ast.With):
                for item in up.items:
                    if an.locks.ref(item.context_expr, cls):
                        return id(up)
            up = an.idx.parents.get(up)
        return None

    for fn in an.idx.func_defs:
        if fn.name == "__init__":
            continue
        cls = an.enclosing_class(fn)
        checks: List[Tuple[str, str, int, Optional[int]]] = []
        uses: List[Tuple[str, str, ast.AST, Optional[int]]] = []
        for node in _scope_walk(fn.body):
            if isinstance(node, ast.Compare) and len(node.ops) == 1 \
                    and isinstance(node.ops[0], (ast.In, ast.NotIn)):
                cont = _qualname(node.comparators[0])
                key = key_repr(node.left)
                if cont and cont.startswith("self.") and key:
                    checks.append((cont, key, node.lineno,
                                   region_of(node, fn, cls)))
            elif isinstance(node, ast.Subscript):
                cont = _qualname(node.value)
                key = key_repr(node.slice)
                if cont and cont.startswith("self.") and key:
                    uses.append((cont, key, node,
                                 region_of(node, fn, cls)))
        for cont, key, node, ureg in uses:
            line = node.lineno
            same = [c for c in checks
                    if c[0] == cont and c[1] == key and c[2] <= line]
            if not same:
                continue
            if any(c[3] == ureg and c[3] is not None for c in same):
                continue       # re-checked inside the use's own region
            stale = [c for c in same if c[3] != ureg]
            if stale:
                c = max(stale, key=lambda c: c[2])
                add("DLT205", node,
                    f"'{key} in {cont}' checked at line {c[2]} but "
                    f"'{cont}[{key}]' used here in a different lock "
                    "region — the entry can vanish in between")


_PASSES = (_rule_dlt200, _rule_dlt201, _rule_dlt202, _rule_dlt203,
           _rule_dlt204, _rule_dlt205)


# --------------------------------------------------------- public API
def lint_source(src: str, path: str = "<string>") -> List[Finding]:
    """Concurrency-audit one module's source (pragma-aware)."""
    path = path.replace(os.sep, "/")
    if not _relevant(src):
        return []
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("DLT000", path, e.lineno or 0, 0,
                        f"syntax error: {e.msg}")]
    an = _Analysis(tree, path)
    lines = src.splitlines()

    def allowed(rule: str, line: int) -> bool:
        for ln in (line, line - 1):
            if 1 <= ln <= len(lines):
                m = _PRAGMA.search(lines[ln - 1])
                if m:
                    allow = {t.strip() for t in m.group(1).split(",")}
                    if "*" in allow or rule in allow:
                        return True
        return False

    findings: List[Finding] = []
    dedup = set()

    def add(rule: str, node: ast.AST, msg: str) -> None:
        line = getattr(node, "lineno", 0)
        col = getattr(node, "col_offset", 0)
        key = (rule, line, col)
        if key in dedup or allowed(rule, line):
            return
        dedup.add(key)
        findings.append(Finding(rule, path, line, col, msg))

    for rule_pass in _PASSES:
        rule_pass(an, add)
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return findings


def lint_file(abspath: str, root: Optional[str] = None) -> List[Finding]:
    rel = os.path.relpath(abspath, root) if root else abspath
    with open(abspath, encoding="utf-8") as f:
        return lint_source(f.read(), rel)


def lint_tree(root: Optional[str] = None,
              scan: Sequence[str] = DEFAULT_SCAN
              ) -> Tuple[List[Finding], int]:
    """Audit the whole tree. Returns (findings, files_scanned) — the
    substring prefilter means only fleet files are actually parsed."""
    root = root or _lint.repo_root()
    findings: List[Finding] = []
    n_files = 0
    for path in _lint.iter_python_files(root, scan):
        n_files += 1
        findings.extend(lint_file(path, root))
    return findings, n_files


def lock_order_graph(root: Optional[str] = None,
                     scan: Sequence[str] = DEFAULT_SCAN
                     ) -> Dict[str, Any]:
    """The repo-wide static lock-order graph, keyed for the runtime
    join: every node carries the creation file:line that
    ``threadsan.InstrumentedLock`` also records, so the sanitizer can
    seed its order check from these edges."""
    root = root or _lint.repo_root()
    locks: Dict[str, Dict[str, Any]] = {}
    edges: List[Dict[str, Any]] = []
    spawns: List[Dict[str, Any]] = []
    edge_seen: Set[Tuple[str, str]] = set()
    for abspath in _lint.iter_python_files(root, scan):
        with open(abspath, encoding="utf-8") as f:
            src = f.read()
        if not _relevant(src):
            continue
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        an = _Analysis(tree, rel)
        for lock_id, line in an.locks.line_of.items():
            locks[lock_id] = {"path": rel, "line": line,
                              "name": lock_id.split("::", 1)[-1]}
        for call, kind in an.thread_calls():
            spawns.append({"path": rel, "line": call.lineno,
                           "kind": kind})
        _lock_edges(an)
        for src_id, dst_id, line, func in an.edges:
            if (src_id, dst_id) in edge_seen:
                continue
            edge_seen.add((src_id, dst_id))
            edges.append({"src": src_id, "dst": dst_id,
                          "path": rel, "line": line, "func": func})
    cycles = _find_cycles((e["src"], e["dst"]) for e in edges)
    return {"locks": locks, "edges": edges, "cycles": cycles,
            "spawn_sites": spawns}


def ratchet_status(root: Optional[str] = None,
                   baseline_path: str = DEFAULT_BASELINE
                   ) -> Dict[str, Any]:
    """Concurrency counterpart of ``lint.ratchet_status`` — DLT2xx
    findings vs the shared baseline. Feeds the obs_report posture
    line."""
    findings, n_files = lint_tree(root)
    baseline = _lint.load_baseline(baseline_path)
    new = _lint.new_findings(findings, baseline)
    b_counts = baseline.get("counts", {})
    b_total = sum(n for rules in b_counts.values()
                  for rule, n in rules.items()
                  if rule.startswith("DLT2"))
    return {
        "rules": len(RULES),
        "files_scanned": n_files,
        "findings": len(findings),
        "baseline_findings": b_total,
        "new_groups": len(new),
        "new": new,
        "clean": not new,
    }

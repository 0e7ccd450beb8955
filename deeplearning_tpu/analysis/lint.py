"""dltpu-check: a TPU-policy AST linter for the repo's hot-path invariants.

The repo's hard-won invariants — the sync-free hot loop, batch-buffer
donation, retrace discipline, signal-handler safety — each live in one
bespoke test and otherwise in README prose, while 190 sync-capable call
sites sit across 47 modules. This linter turns them into named, machine-
checkable rules over the Python AST (stdlib ``ast`` only — this module
must import neither jax nor anything else heavy, so ``tools/check.py
--ci`` and ``tools/obs_report.py`` can load it standalone in well under
a second):

  DLT100  host-sync call (``jax.device_get`` / ``.block_until_ready()``
          / ``np.asarray``) inside a hot-path module (``train/``,
          ``data/device_prefetch.py``, ``serve/batcher.py``,
          ``serve/engine.py``). One stray sync between log points undoes
          the PR 1 pipelining.
  DLT101  use-after-donate: a variable passed at a ``donate_argnums``
          position of a jitted call and read afterwards — XLA has
          already recycled that buffer.
  DLT102  retrace hazard: ``jax.jit`` over a closure on a Python scalar
          derived from ``.shape``/``len()``/``int()`` without
          ``static_argnums``, or a ``jax.jit`` call constructed inside a
          ``for``/``while`` body (a fresh cache per iteration).
  DLT103  non-async-signal-safe call (print/open/logging/sleep/
          subprocess) inside a handler registered via
          ``elastic.signals.subscribe`` or ``signal.signal``.
  DLT104  silent exception swallowing: a bare/broad ``except`` whose
          entire body is ``pass`` — the bug class that hid worker
          errors until PR 7.
  DLT105  blocking I/O or ``time.*`` inside a traced (jitted) function —
          it runs at trace time, not step time, and poisons the cache.

Suppression: append ``# dltpu: allow(DLT100)`` (comma-separate several,
or ``allow(*)``) to the offending line or the line above it.

Ratchet: ``baseline.json`` (checked in next to this file) records the
per-file per-rule finding counts at adoption time. ``new_findings``
flags only counts ABOVE the baseline, so the existing debt doesn't
block CI but no new violation can land. ``tools/check.py
--update-baseline`` re-records (tightening is always safe; loosening
shows up in the diff).
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "RULES", "HOT_PATH_MODULES", "Finding", "lint_source", "lint_file",
    "lint_tree", "counts", "load_baseline", "write_baseline",
    "new_findings", "ratchet_status", "DEFAULT_BASELINE", "DEFAULT_SCAN",
]

RULES: Dict[str, str] = {
    "DLT100": "host-sync call in a hot-path module",
    "DLT101": "use-after-donate: donated buffer read after the call",
    "DLT102": "retrace hazard: jit over python-scalar closure or in loop",
    "DLT103": "non-async-signal-safe call in a signal handler",
    "DLT104": "silent exception swallowing (broad except: pass)",
    "DLT105": "blocking I/O or time.* inside a traced function",
}

# modules where DLT100 applies — the proven sync-free surfaces
HOT_PATH_MODULES: Tuple[str, ...] = (
    "deeplearning_tpu/train/",
    "deeplearning_tpu/data/device_prefetch.py",
    "deeplearning_tpu/serve/batcher.py",
    "deeplearning_tpu/serve/engine.py",
    # multi-tenant residency manager: the warm-path request() is a dict
    # lookup on the submit thread, so it carries the same no-sync bar
    "deeplearning_tpu/serve/zoo.py",
    # fleet telemetry plane: instrumented hot paths call into these, so
    # they must be provably sync-free too (stdlib-only by construction)
    "deeplearning_tpu/obs/metrics.py",
    "deeplearning_tpu/obs/fleet.py",
    "deeplearning_tpu/fleet/",
)

# scan roots for lint_tree, relative to the repo root (tests/ is out by
# design: test code syncs on purpose, and seeded-violation fixtures for
# the unit tests live in tmp dirs)
DEFAULT_SCAN: Tuple[str, ...] = (
    "deeplearning_tpu", "tools", "chip_smoke.py", "__graft_entry__.py",
)

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")

_PRAGMA = re.compile(r"#\s*dltpu:\s*allow\(([^)]*)\)")

_LOGGING_METHODS = {"info", "warning", "error", "debug", "exception",
                    "critical", "log"}
_SIGNAL_UNSAFE_NAMES = {"print", "open", "input"}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    msg: str

    def __str__(self) -> str:  # "path:line:col: DLTnnn message"
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.msg}"


# --------------------------------------------------------------- helpers
def _qualname(node: ast.AST) -> Optional[str]:
    """Dotted name for Name/Attribute chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _qualname(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


class _Index:
    """One breadth-first walk of the module, shared by every rule pass.

    Each rule used to re-run ``ast.walk`` over the full tree (nine walks
    per file between the passes, alias scan, and parent map); on the
    190-file tree that dominated ``tools/check.py --ci`` wall time. The
    index walks once and buckets the node kinds the rules filter on."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.nodes: List[ast.AST] = []
        self.calls: List[ast.Call] = []
        self.func_defs: List[ast.AST] = []
        self.except_handlers: List[ast.ExceptHandler] = []
        self.parents: Dict[ast.AST, ast.AST] = {}
        todo: deque = deque([tree])
        while todo:
            node = todo.popleft()
            self.nodes.append(node)
            if isinstance(node, ast.Call):
                self.calls.append(node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.func_defs.append(node)
            elif isinstance(node, ast.ExceptHandler):
                self.except_handlers.append(node)
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
                todo.append(child)


class _Aliases:
    """Import aliases the rules need to resolve (np, jax, time, ...)."""

    def __init__(self, nodes: Iterable[ast.AST]):
        self.numpy: set = set()
        self.jax: set = set()
        self.time: set = set()
        self.subprocess: set = set()
        self.partial: set = set()      # functools.partial names
        self.functools: set = set()
        self.jax_names: set = set()    # from jax import jit, device_get
        for node in nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name
                    if a.name == "numpy":
                        self.numpy.add(name)
                    elif a.name == "jax":
                        self.jax.add(name)
                    elif a.name == "time":
                        self.time.add(name)
                    elif a.name == "subprocess":
                        self.subprocess.add(name)
                    elif a.name == "functools":
                        self.functools.add(name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "functools":
                    for a in node.names:
                        if a.name == "partial":
                            self.partial.add(a.asname or "partial")
                elif node.module == "jax":
                    for a in node.names:
                        self.jax_names.add(a.asname or a.name)


def _is_jit_ref(node: ast.AST, al: _Aliases) -> bool:
    """Does this expression refer to jax.jit / pjit?"""
    q = _qualname(node)
    if q is None:
        return False
    if q in al.jax_names and q in ("jit", "pjit", "pmap"):
        return True
    head, _, tail = q.partition(".")
    return head in al.jax and tail in ("jit", "pjit", "pmap")


def _is_jit_call(node: ast.AST, al: _Aliases) -> bool:
    """Call whose result is a jitted callable: ``jax.jit(...)`` or
    ``partial(jax.jit, ...)(...)``-style partials over jit."""
    if not isinstance(node, ast.Call):
        return False
    if _is_jit_ref(node.func, al):
        return True
    # partial(jax.jit, static_argnums=...) — decorator idiom
    fq = _qualname(node.func)
    if fq and (fq in al.partial
               or any(fq == f"{m}.partial" for m in al.functools)):
        return bool(node.args) and _is_jit_ref(node.args[0], al)
    return False


def _call_kw(node: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _int_tuple(node: Optional[ast.expr]) -> Optional[Tuple[int, ...]]:
    """Literal int / tuple-of-ints, else None (can't reason about it)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    return None


def _scope_walk(body: Sequence[ast.stmt]) -> Iterable[ast.AST]:
    """Walk a function body without descending into nested function /
    class scopes (their loads/stores execute at a different time)."""
    stack: deque = deque(body)
    while stack:
        node = stack.popleft()
        yield node
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                stack.append(child)


def _scopes(idx: _Index) -> Iterable[Sequence[ast.stmt]]:
    """Module body + every function body (the units DLT101/102 reason
    over)."""
    yield idx.tree.body
    for node in idx.func_defs:
        yield node.body


def _free_loads(fn: ast.AST) -> set:
    """Names a lambda/def loads but neither binds as a param nor stores
    locally — i.e. its closure reads."""
    if isinstance(fn, ast.Lambda):
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        body: List[ast.AST] = [fn.body]
    elif isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        if fn.args.vararg:
            params.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            params.add(fn.args.kwarg.arg)
        body = list(fn.body)
    else:
        return set()
    loads, stores = set(), set(params)
    for node in body:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                if isinstance(sub.ctx, ast.Load):
                    loads.add(sub.id)
                else:
                    stores.add(sub.id)
    return loads - stores


# ------------------------------------------------------------ rule passes
def _rule_dlt100(idx, al, path, add) -> None:
    if not any(h in path for h in HOT_PATH_MODULES):
        return
    for node in idx.calls:
        q = _qualname(node.func)
        if q is None:
            continue
        head, _, tail = q.partition(".")
        if tail == "device_get" and head in al.jax:
            add("DLT100", node, "jax.device_get syncs the dispatch queue")
        elif q == "device_get" and "device_get" in al.jax_names:
            add("DLT100", node, "device_get syncs the dispatch queue")
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr == "block_until_ready":
            add("DLT100", node, ".block_until_ready() stalls the host")
        elif head in al.numpy and tail in ("asarray", "array"):
            add("DLT100", node,
                f"{q}() on a device value forces a D2H transfer")


def _rule_dlt101(idx, al, path, add) -> None:
    for body in _scopes(idx):
        donating: Dict[str, Tuple[int, ...]] = {}
        donations: List[Tuple[str, int]] = []   # (var, line)
        stores: List[Tuple[str, int]] = []
        loads: List[Tuple[str, int, ast.Name]] = []

        def donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
            """Positions donated by this call, when it IS a donating
            call (directly jitted-with-donate or a name bound to one)."""
            if isinstance(call.func, ast.Name) and \
                    call.func.id in donating:
                return donating[call.func.id]
            if _is_jit_call(call.func, al):     # jax.jit(f, ...)(args)
                pos = _int_tuple(_call_kw(call.func, "donate_argnums"))
                return pos
            return None

        for node in _scope_walk(body):
            if isinstance(node, ast.Assign) and \
                    _is_jit_call(node.value, al):
                pos = _int_tuple(_call_kw(node.value, "donate_argnums"))
                if pos:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            donating[t.id] = pos
            if isinstance(node, ast.Call):
                pos = donate_positions(node)
                if pos:
                    for p in pos:
                        if p < len(node.args) and \
                                isinstance(node.args[p], ast.Name):
                            donations.append((node.args[p].id,
                                              node.lineno))
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    loads.append((node.id, node.lineno, node))
                else:
                    stores.append((node.id, node.lineno))

        for var, dline in donations:
            for name, lline, lnode in loads:
                if name != var or lline <= dline:
                    continue
                # a rebinding between donation and load clears it —
                # including `state, m = step(state, ...)` same-line
                if any(s == var and dline <= sline <= lline
                       for s, sline in stores):
                    continue
                add("DLT101", lnode,
                    f"'{var}' was donated at line {dline}; its buffer "
                    "is already recycled")
                break          # one finding per donation is enough


def _rule_dlt102(idx, al, path, add) -> None:
    # (a) jit over a closure on scalar-derived locals, no static_argnums
    local_defs: Dict[str, ast.AST] = {}
    for node in idx.func_defs:
        local_defs[node.name] = node

    def scalar_derived_names(body) -> set:
        out = set()
        for node in _scope_walk(body):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            is_scalar = (
                (isinstance(v, ast.Subscript) and
                 isinstance(v.value, ast.Attribute) and
                 v.value.attr == "shape") or
                (isinstance(v, ast.Call) and
                 isinstance(v.func, ast.Name) and
                 v.func.id in ("len", "int")))
            if is_scalar:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        return out

    for body in _scopes(idx):
        scalars = scalar_derived_names(body)
        if not scalars:
            continue
        for node in _scope_walk(body):
            if not (isinstance(node, ast.Call) and
                    _is_jit_ref(node.func, al) and node.args):
                continue
            if _call_kw(node, "static_argnums") is not None or \
                    _call_kw(node, "static_argnames") is not None:
                continue
            target = node.args[0]
            if isinstance(target, ast.Name):
                target = local_defs.get(target.id)
            if target is None or not isinstance(
                    target, (ast.Lambda, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
                continue
            hazard = _free_loads(target) & scalars
            if hazard:
                add("DLT102", node,
                    f"jit closes over python scalar(s) "
                    f"{sorted(hazard)} without static_argnums — every "
                    "new value retraces")

    # (b) jit construction inside a loop body (fresh cache/trace per
    # iteration); the nearest enclosing scope boundary wins
    parents = idx.parents
    for node in idx.calls:
        if not _is_jit_ref(node.func, al):
            continue
        up = parents.get(node)
        while up is not None:
            if isinstance(up, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda, ast.Module)):
                break
            if isinstance(up, (ast.For, ast.While)):
                add("DLT102", node,
                    "jax.jit constructed inside a loop: a fresh jit "
                    "cache (and trace) per iteration")
                break
            up = parents.get(up)


def _rule_dlt103(idx, al, path, add) -> None:
    defs_by_name: Dict[str, ast.AST] = {}
    for node in idx.func_defs:
        defs_by_name[node.name] = node

    handlers: List[ast.AST] = []
    for node in idx.calls:
        q = _qualname(node.func) or ""
        is_subscribe = q == "subscribe" or q.endswith(".subscribe")
        is_signal = q == "signal.signal" or q.endswith("signal.signal")
        if not (is_subscribe or is_signal):
            continue
        fn_arg = node.args[1] if len(node.args) > 1 else \
            _call_kw(node, "fn")
        if fn_arg is None:
            continue
        if isinstance(fn_arg, ast.Name) and fn_arg.id in defs_by_name:
            handlers.append(defs_by_name[fn_arg.id])
        elif isinstance(fn_arg, ast.Attribute) and \
                fn_arg.attr in defs_by_name:
            handlers.append(defs_by_name[fn_arg.attr])
        elif isinstance(fn_arg, ast.Lambda):
            handlers.append(fn_arg)

    # one level of callee resolution: a handler that merely delegates
    # (``def _on_term(...): _do_dump()``) used to hide its I/O from
    # this rule — any same-module function/method the handler body
    # calls is scanned with it
    for h in list(handlers):
        body = h.body if isinstance(h.body, list) else [h.body]
        for node in body:
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                callee = None
                if isinstance(sub.func, ast.Name):
                    callee = defs_by_name.get(sub.func.id)
                elif isinstance(sub.func, ast.Attribute):
                    callee = defs_by_name.get(sub.func.attr)
                if callee is not None:
                    handlers.append(callee)

    seen = set()
    for h in handlers:
        if id(h) in seen:
            continue
        seen.add(id(h))
        body = h.body if isinstance(h.body, list) else [h.body]
        for node in body:
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                q = _qualname(sub.func) or ""
                head, _, tail = q.partition(".")
                unsafe = (
                    q in _SIGNAL_UNSAFE_NAMES
                    or (head in al.time and tail == "sleep")
                    or q in ("os.system",)
                    or head in al.subprocess
                    or (isinstance(sub.func, ast.Attribute) and
                        sub.func.attr in _LOGGING_METHODS and
                        "log" in (_qualname(sub.func.value) or "").lower())
                )
                if unsafe:
                    add("DLT103", sub,
                        f"'{q or sub.func.attr}' is not async-signal-"
                        "safe inside a registered signal handler")


def _rule_dlt104(idx, al, path, add) -> None:
    broad = {"Exception", "BaseException"}
    for node in idx.except_handlers:
        if not (len(node.body) == 1 and isinstance(node.body[0], ast.Pass)):
            continue
        t = node.type
        is_broad = (
            t is None
            or (isinstance(t, ast.Name) and t.id in broad)
            or (isinstance(t, ast.Tuple) and any(
                isinstance(e, ast.Name) and e.id in broad
                for e in t.elts)))
        if is_broad:
            add("DLT104", node,
                "broad except whose body is only 'pass' swallows real "
                "failures silently")


def _rule_dlt105(idx, al, path, add) -> None:
    local_defs: Dict[str, ast.AST] = {}
    for node in idx.func_defs:
        local_defs[node.name] = node

    traced: List[ast.AST] = []
    for node in idx.func_defs:
        for dec in node.decorator_list:
            if _is_jit_ref(dec, al) or _is_jit_call(dec, al):
                traced.append(node)
                break
    for node in idx.calls:
        if _is_jit_ref(node.func, al) and node.args:
            target = node.args[0]
            if isinstance(target, ast.Name):
                target = local_defs.get(target.id)
            if isinstance(target, (ast.Lambda, ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                traced.append(target)

    seen = set()
    for fn in traced:
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for node in body:
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                q = _qualname(sub.func) or ""
                head, _, tail = q.partition(".")
                if q in ("open", "print") or head in al.time:
                    add("DLT105", sub,
                        f"'{q}' inside a traced function runs at TRACE "
                        "time only (and blocks it)")


_PASSES = (_rule_dlt100, _rule_dlt101, _rule_dlt102, _rule_dlt103,
           _rule_dlt104, _rule_dlt105)


# ------------------------------------------------------------- public API
def lint_source(src: str, path: str = "<string>") -> List[Finding]:
    """Lint one module's source. ``path`` decides hot-path scoping and
    is echoed into findings (repo-relative, forward slashes)."""
    path = path.replace(os.sep, "/")
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("DLT000", path, e.lineno or 0, 0,
                        f"syntax error: {e.msg}")]
    idx = _Index(tree)
    al = _Aliases(idx.nodes)
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
        rule_pass(idx, al, path, add)
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return findings


def lint_file(abspath: str, root: Optional[str] = None) -> List[Finding]:
    rel = os.path.relpath(abspath, root) if root else abspath
    with open(abspath, encoding="utf-8") as f:
        return lint_source(f.read(), rel)


def repo_root() -> str:
    """The checkout root (two levels above this file's package dir)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def iter_python_files(root: str,
                      scan: Sequence[str] = DEFAULT_SCAN
                      ) -> Iterable[str]:
    for entry in scan:
        full = os.path.join(root, entry)
        if os.path.isfile(full):
            yield full
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git",
                                            "runs", ".jax_cache")]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def lint_tree(root: Optional[str] = None,
              scan: Sequence[str] = DEFAULT_SCAN
              ) -> Tuple[List[Finding], int]:
    """Lint the whole tree. Returns (findings, files_scanned)."""
    root = root or repo_root()
    findings: List[Finding] = []
    n_files = 0
    for path in iter_python_files(root, scan):
        n_files += 1
        findings.extend(lint_file(path, root))
    return findings, n_files


# ---------------------------------------------------------------- ratchet
def counts(findings: Iterable[Finding]) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {}
    for f in findings:
        out.setdefault(f.path, {})
        out[f.path][f.rule] = out[f.path].get(f.rule, 0) + 1
    return {p: dict(sorted(r.items())) for p, r in sorted(out.items())}


def load_baseline(path: str = DEFAULT_BASELINE) -> Dict[str, Any]:
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {"version": 1, "counts": {}}
    data.setdefault("counts", {})
    return data


def write_baseline(findings: Iterable[Finding],
                   path: str = DEFAULT_BASELINE) -> Dict[str, Any]:
    data = {"version": 1, "rules": sorted(RULES),
            "counts": counts(findings)}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


def new_findings(findings: Sequence[Finding],
                 baseline: Optional[Dict[str, Any]] = None
                 ) -> List[Dict[str, Any]]:
    """Groups whose count exceeds the baseline budget. Each entry names
    the file, rule, budget, count, and every finding in the group (line
    numbers move, so the RATCHET is per-(file, rule) count — any finding
    in an over-budget group might be the new one)."""
    if baseline is None:
        baseline = load_baseline()
    budget = baseline.get("counts", {})
    groups: Dict[Tuple[str, str], List[Finding]] = {}
    for f in findings:
        groups.setdefault((f.path, f.rule), []).append(f)
    out = []
    for (path, rule), fs in sorted(groups.items()):
        allowed = int(budget.get(path, {}).get(rule, 0))
        if len(fs) > allowed:
            out.append({"path": path, "rule": rule, "count": len(fs),
                        "budget": allowed,
                        "findings": [str(f) for f in fs]})
    return out


def ratchet_status(root: Optional[str] = None,
                   baseline_path: str = DEFAULT_BASELINE
                   ) -> Dict[str, Any]:
    """One-call summary for obs_report.py and the tests: scan + compare."""
    findings, n_files = lint_tree(root)
    baseline = load_baseline(baseline_path)
    new = new_findings(findings, baseline)
    b_counts = baseline.get("counts", {})
    return {
        "rules": len(RULES),
        "files_scanned": n_files,
        "findings": len(findings),
        "baseline_findings": sum(sum(r.values())
                                 for r in b_counts.values()),
        "baseline_files": len(b_counts),
        "new_groups": len(new),
        "new": new,
        "clean": not new,
    }

"""Source-discipline lints: dead imports, unreachable statements, and
host-thread lock discipline (graftcheck layer 1).

Stdlib-only — see `rules.py`.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Set

from .rules import SourceFile, Violation, rule


def dotted(node: ast.AST) -> Optional[str]:
    """`jax.lax.psum` -> "jax.lax.psum" for Name/Attribute chains."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ------------------------------------------------------------ unused-import --

@rule("unused-import",
      "imported name never referenced in the module",
      "dead imports accumulated across PR 1-10 sweeps; each one is a "
      "startup cost and a false dependency edge the next refactor trips on")
def check_unused_import(src: SourceFile) -> List[Violation]:
    if os.path.basename(src.path) == "__init__.py":
        return []        # __init__ imports are the re-export surface
    imported: dict = {}  # local name -> (lineno, display)
    # honour the ecosystem convention for side-effect imports: a line
    # carrying `# noqa` (bare, or naming F401) is deliberate
    noqa_lines = set()
    for i, line in enumerate(src.text.splitlines(), 1):
        if "# noqa" in line:
            tail = line.split("# noqa", 1)[1]
            if not tail.strip().startswith(":") or "F401" in tail:
                noqa_lines.add(i)
    in_try: Set[int] = set()
    for node in src.nodes:
        if isinstance(node, ast.Try):
            for sub in ast.walk(node):
                in_try.add(id(sub))
    for node in src.nodes:
        if id(node) in in_try:
            continue     # compat-style gated imports are deliberate
        if isinstance(node, ast.Import):
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                imported[local] = (node.lineno, a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                local = a.asname or a.name
                imported[local] = (node.lineno, a.name)
    if not imported:
        return []
    used: Set[str] = set()
    for node in src.nodes:
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            d = dotted(node)
            if d:
                used.add(d.split(".")[0])
    # names in __all__ are exports, not uses-in-module, but keep them
    for node in src.nodes:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "__all__"
                        for t in node.targets)):
            try:
                used |= set(ast.literal_eval(node.value))
            except ValueError:
                pass
    # string annotations ("Model") reference names invisibly to the walk
    for node in src.nodes:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            used |= {w for w in imported
                     if w in node.value and len(w) > 2}
    out = []
    for local, (lineno, display) in sorted(imported.items(),
                                           key=lambda kv: kv[1][0]):
        if lineno in noqa_lines:
            continue
        if local not in used and not local.startswith("_"):
            out.append(Violation(
                "unused-import", src.path, lineno,
                f"'{display}' imported but never used"))
    return out


# ---------------------------------------------------------- unreachable-code --

_TERMINAL = (ast.Return, ast.Raise, ast.Break, ast.Continue)


@rule("unreachable-code",
      "statements after an unconditional return/raise/break/continue",
      "dead branches left by the PR 5-9 engine refactors: unreachable "
      "code reads as load-bearing and rots silently")
def check_unreachable(src: SourceFile) -> List[Violation]:
    out = []
    for node in src.nodes:
        for field in ("body", "orelse", "finalbody"):
            stmts = getattr(node, field, None)
            if not isinstance(stmts, list):
                continue
            for i, stmt in enumerate(stmts[:-1]):
                if isinstance(stmt, _TERMINAL):
                    nxt = stmts[i + 1]
                    out.append(Violation(
                        "unreachable-code", src.path, nxt.lineno,
                        f"statement is unreachable (follows "
                        f"{type(stmt).__name__.lower()} on line "
                        f"{stmt.lineno})"))
                    break
    return out


# ------------------------------------------------------ profiler-discipline --

#: the one module allowed to start/stop jax.profiler traces: it owns the
#: window mechanics (ProfilerTrace / AnomalyProfiler / DutyCycleProfiler)
_PROFILER_OWNER = "training/metrics.py"
_PROFILER_CALLS = {"jax.profiler.start_trace", "jax.profiler.stop_trace"}


@rule("profiler-discipline",
      "jax.profiler.start_trace/stop_trace outside training/metrics.py",
      "the device profiler is one-capture-at-a-time: a scattered "
      "start/stop races the ProfilerTrace/AnomalyProfiler/"
      "DutyCycleProfiler window mechanics (training/metrics.py), so a "
      "stop fires mid-window and the capture truncates unreadably — the "
      "exact failure the obs-v4 measured plane cannot tolerate, since "
      "every capture now parses into a profile_attribution event")
def check_profiler_discipline(src: SourceFile) -> List[Violation]:
    if src.path.replace(os.sep, "/").endswith(_PROFILER_OWNER):
        return []
    out: List[Violation] = []
    for node in src.nodes:
        if isinstance(node, ast.Attribute):
            name = dotted(node)
            if name in _PROFILER_CALLS:
                out.append(Violation(
                    "profiler-discipline", src.path, node.lineno,
                    f"{name} outside training/metrics.py breaks the "
                    f"one-capture-at-a-time window mechanics — drive "
                    f"captures through ProfilerTrace / AnomalyProfiler / "
                    f"DutyCycleProfiler instead"))
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "") == "jax.profiler" and any(
                    a.name in ("start_trace", "stop_trace")
                    for a in node.names):
                out.append(Violation(
                    "profiler-discipline", src.path, node.lineno,
                    "importing start_trace/stop_trace from jax.profiler "
                    "outside training/metrics.py — drive captures "
                    "through the ProfilerTrace owners"))
    return out


# ---------------------------------------------------- controller-discipline --

#: the control plane's owner modules: the advisor/controller INTERNALS may
#: touch actuation freely (they are the mechanism the rule protects)
_CONTROL_OWNERS = ("obs/control.py", "serving/controller.py")
_ACTUATION_CALLS = {"apply_decisions", "actuate"}
_SAFE_POINT_DECO = "control_safe_point"


def _deco_tail(d: ast.AST) -> str:
    """`@control_safe_point` / `@control.control_safe_point` -> the bare
    decorator name (calls unwrap to their func)."""
    if isinstance(d, ast.Call):
        d = d.func
    return (dotted(d) or "").split(".")[-1]


@rule("controller-discipline",
      "controller/advisor actuation outside a control_safe_point function",
      "the obs-v5 control plane mutates live engine knobs "
      "(pages_per_block, prefill chunk, speculation K); an actuation "
      "from an arbitrary call site lands mid-capture-window or inside a "
      "traced function, which tears the measurement the decision was "
      "based on — actuation is only legal at registered safe points "
      "(engine init boundaries, the host-side control tick, between "
      "duty-cycle capture windows)")
def check_controller_discipline(src: SourceFile) -> List[Violation]:
    path = src.path.replace(os.sep, "/")
    if any(path.endswith(owner) for owner in _CONTROL_OWNERS):
        return []
    # every node living inside a @control_safe_point function is blessed
    safe_ids: set = set()
    for node in src.nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_deco_tail(d) == _SAFE_POINT_DECO
                   for d in node.decorator_list):
                for sub in ast.walk(node):
                    safe_ids.add(id(sub))
    out: List[Violation] = []
    for node in src.nodes:
        if not isinstance(node, ast.Call) or id(node) in safe_ids:
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if name in _ACTUATION_CALLS:
            out.append(Violation(
                "controller-discipline", src.path, node.lineno,
                f"{name}() outside a @control_safe_point function — "
                f"knob actuation from an arbitrary call site can land "
                f"mid-capture-window or inside a traced function; move "
                f"the call into a registered safe point (the engine's "
                f"control tick, a duty-profiler on_attribution hook, or "
                f"an init boundary)"))
    return out


# ----------------------------------------------- host-gather-in-reshard --

@rule("host-gather-in-reshard",
      "whole-tree host materialisation on a reshard path",
      "the reshard subsystem's (ISSUE 20) one law: leaves cross the host "
      "ONE AT A TIME, peak host bytes bounded by the largest single leaf "
      "— a 45M-param tree that fits sharded on 8 chips does not fit "
      "unsharded in one host buffer. A whole-tree jax.device_get or an "
      "eager dict(np.load(...)) on a reshard path is exactly the "
      "one-shot materialisation reshard/apply.py's streaming executors "
      "exist to eliminate")
def check_host_gather_in_reshard(src: SourceFile) -> List[Violation]:
    path = src.path.replace(os.sep, "/")
    if "/reshard/" in path or path.startswith("reshard/"):
        scoped = list(src.nodes)
    else:
        # outside the subsystem the rule guards functions that CLAIM to
        # reshard (serve_fleet's restart, train's elastic resume, bench)
        scoped, seen = [], set()
        for node in src.nodes:
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and "reshard" in node.name):
                for sub in ast.walk(node):
                    if id(sub) not in seen:
                        seen.add(id(sub))
                        scoped.append(sub)
    if not scoped:
        return []
    # device_get inside a Lambda is the streamed per-leaf idiom (a
    # jax.tree.map leaf callback) — the tree-at-once call is the hazard
    in_lambda = set()
    for node in scoped:
        if isinstance(node, ast.Lambda):
            for sub in ast.walk(node):
                in_lambda.add(id(sub))
    out: List[Violation] = []
    for node in scoped:
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func) or ""
        if (name.split(".")[-1] == "device_get"
                and id(node) not in in_lambda):
            out.append(Violation(
                "host-gather-in-reshard", src.path, node.lineno,
                "whole-tree jax.device_get on a reshard path — stream "
                "leaves one at a time (a per-leaf tree.map callback, or "
                "reshard/apply.py's executors); peak host bytes must "
                "stay bounded by the largest single leaf"))
        if (isinstance(node.func, ast.Name) and node.func.id == "dict"
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Call)):
            inner = dotted(node.args[0].func) or ""
            if (inner.split(".")[-1] == "load"
                    and inner.split(".")[0] in ("np", "numpy")):
                out.append(Violation(
                    "host-gather-in-reshard", src.path, node.lineno,
                    "dict(np.load(...)) materialises every shard member "
                    "at once on a reshard path — read members lazily "
                    "(NpzFile is lazy per key; reshard/apply.py streams "
                    "payload bytes member-by-member)"))
    return out


# ---------------------------------------------------------- lock-discipline --

_LOCK_CTORS = {"threading.Lock", "threading.RLock", "threading.Condition",
               "Lock", "RLock", "Condition"}
_MUTATORS = {"append", "appendleft", "extend", "pop", "popleft", "add",
             "remove", "discard", "insert", "clear", "update", "setdefault",
             "popitem"}


def _self_attr(node: ast.AST) -> Optional[str]:
    """self.<attr> -> attr (only depth-1: self.x, not self.x.y)."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _stmt_mutations(stmt, holding: bool, sink, lock_attrs):
    """Collect (attr, lineno, holding_lock) for every `self.<attr>`
    mutation under `stmt`, tracking `with self.<lock>:` context (only
    attrs in `lock_attrs` count as locks — `with self._span(...)` is a
    tracer, not a guard)."""
    if isinstance(stmt, (ast.Assign, ast.AugAssign)):
        targets = stmt.targets if isinstance(stmt, ast.Assign) else [
            stmt.target]
        for t in targets:
            attr = _self_attr(t)
            if attr is None and isinstance(t, ast.Subscript):
                attr = _self_attr(t.value)
            if attr is None and isinstance(t, ast.Tuple):
                for el in t.elts:
                    a = _self_attr(el)
                    if a:
                        sink.append((a, stmt.lineno, holding))
            if attr:
                sink.append((attr, stmt.lineno, holding))
    if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
        f = stmt.value.func
        if isinstance(f, ast.Attribute) and f.attr in _MUTATORS:
            attr = _self_attr(f.value)
            if attr:
                sink.append((attr, stmt.lineno, holding))
    # recurse into compound statements, preserving lock context
    inner = holding
    if isinstance(stmt, ast.With):
        for item in stmt.items:
            ctx = item.context_expr
            if _self_attr(ctx) in lock_attrs:
                inner = True
    for field in ("body", "orelse", "finalbody", "handlers"):
        sub = getattr(stmt, field, None)
        if isinstance(sub, list):
            for s in sub:
                if isinstance(s, ast.ExceptHandler):
                    for ss in s.body:
                        _stmt_mutations(ss, inner, sink, lock_attrs)
                else:
                    _stmt_mutations(s, inner, sink, lock_attrs)


@rule("lock-discipline",
      "attribute guarded by the class lock mutated without holding it",
      "the obs/flight + prefetch + ckpt-writer host threads share state "
      "with the main loop; an unlocked mutation is a torn dump / lost "
      "heartbeat under exactly the anomaly the recorder exists to capture")
def check_lock_discipline(src: SourceFile) -> List[Violation]:
    out = []
    for cls in src.nodes:
        if not isinstance(cls, ast.ClassDef):
            continue
        # does this class own a lock? (self._lock = threading.Lock() ...)
        lock_attrs = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Call):
                ctor = dotted(node.value.func)
                if ctor in _LOCK_CTORS:
                    for t in node.targets:
                        a = _self_attr(t)
                        if a:
                            lock_attrs.add(a)
        if not lock_attrs:
            continue
        # first pass: which attrs are EVER mutated under the lock
        per_method: dict = {}
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            sink: list = []
            for stmt in fn.body:
                _stmt_mutations(stmt, False, sink, lock_attrs)
            per_method[fn.name] = sink
        guarded = {attr for sink in per_method.values()
                   for attr, _, locked in sink if locked}
        guarded -= lock_attrs
        if not guarded:
            continue
        # second pass: mutations of guarded attrs with the lock NOT held
        for name, sink in per_method.items():
            if name == "__init__":
                continue   # construction precedes sharing
            for attr, lineno, locked in sink:
                if attr in guarded and not locked:
                    out.append(Violation(
                        "lock-discipline", src.path, lineno,
                        f"self.{attr} is mutated under the class lock "
                        f"elsewhere but written here without holding it "
                        f"({cls.name}.{name}) — a host thread racing this "
                        f"write tears the shared state"))
    return out

"""graftcheck core: rule registry, violations, pragmas, and the file runner.

Layer 1 of the static checker (ISSUE 11). Everything in this module — and
in the lint modules it drives (`lints_source.py`, `lints_traced.py`) — is
STDLIB-ONLY: no jax, no package imports. The rules must be runnable from a
standalone `scripts/graftcheck.py` invocation that never pays the jax
import. Layer 2 (the trace contracts in `contracts.py`)
is the only part that imports jax, and only lazily.

Every rule is the static form of a bug this repo actually shipped or
narrowly caught — the catalog (with the historical incident per rule) lives
in docs/ANALYSIS.md. Suppression is per-line via an inline pragma:

    x = legacy_call()  # graftcheck: disable=use-after-donate

or for a whole file (first 10 lines):

    # graftcheck: disable-file=unused-import
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, List, Optional, Sequence

#: bump when the report's field contract changes incompatibly
#: (obs/schema.py-style versioning; consumers check before rendering)
GRAFTCHECK_SCHEMA_VERSION = 1


@dataclasses.dataclass
class Rule:
    id: str                 # kebab-case, the pragma/CLI name
    summary: str            # one line: what it catches
    history: str            # the historical bug it would have caught


@dataclasses.dataclass
class Violation:
    rule: str
    path: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def asdict(self) -> dict:
        return dataclasses.asdict(self)


RULES: Dict[str, Rule] = {}
_CHECKERS: List[Callable] = []


def rule(id: str, summary: str, history: str):
    """Register a rule id (decorating the checker that emits it). A checker
    may own several rule ids; registration is what the report's rule
    catalog and the pragma validator enumerate."""
    RULES[id] = Rule(id, summary, history)

    def deco(fn):
        if fn not in _CHECKERS:
            _CHECKERS.append(fn)
        return fn

    return deco


# ----------------------------------------------------------------- pragmas --

_PRAGMA = re.compile(r"#\s*graftcheck:\s*disable=([\w,\-]+)")
_FILE_PRAGMA = re.compile(r"#\s*graftcheck:\s*disable-file=([\w,\-]+)")


def _line_pragmas(text: str) -> Dict[int, set]:
    """lineno -> set of rule ids disabled on that line ('all' wildcards)."""
    out: Dict[int, set] = {}
    for i, line in enumerate(text.splitlines(), 1):
        m = _PRAGMA.search(line)
        if m:
            out[i] = set(m.group(1).split(","))
    return out


def _file_pragmas(text: str) -> set:
    out: set = set()
    for line in text.splitlines()[:10]:
        m = _FILE_PRAGMA.search(line)
        if m:
            out |= set(m.group(1).split(","))
    return out


@dataclasses.dataclass
class SourceFile:
    """One parsed file handed to every checker (parse once, lint many)."""

    path: str               # as reported in violations (repo-relative)
    text: str
    tree: ast.AST
    in_package: bool        # under distributed_pytorch_from_scratch_tpu/
    _nodes: Optional[list] = None

    @property
    def nodes(self) -> list:
        """`ast.walk(tree)` materialised ONCE — every checker iterates
        this instead of re-walking (the sweep's hot path)."""
        if self._nodes is None:
            self._nodes = list(ast.walk(self.tree))
        return self._nodes


def parse_source(path: str, text: Optional[str] = None,
                 in_package: Optional[bool] = None) -> SourceFile:
    if text is None:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
    if in_package is None:
        in_package = "distributed_pytorch_from_scratch_tpu" in \
            path.replace(os.sep, "/")
    tree = ast.parse(text, filename=path)
    return SourceFile(path=path, text=text, tree=tree, in_package=in_package)


def lint_source(src: SourceFile,
                only: Optional[Sequence[str]] = None) -> List[Violation]:
    """Run every registered checker over one parsed file, honouring
    pragmas. `only` filters to a subset of rule ids (CLI --rules)."""
    disabled_file = _file_pragmas(src.text)
    disabled_line = _line_pragmas(src.text)
    out: List[Violation] = []
    for checker in _CHECKERS:
        for v in checker(src):
            if only and v.rule not in only:
                continue
            if v.rule in disabled_file or "all" in disabled_file:
                continue
            on_line = disabled_line.get(v.line, ())
            if v.rule in on_line or "all" in on_line:
                continue
            out.append(v)
    out.sort(key=lambda v: (v.path, v.line, v.rule))
    return out


def lint_file(path: str, text: Optional[str] = None,
              only: Optional[Sequence[str]] = None,
              report_path: Optional[str] = None) -> List[Violation]:
    """Lint one file; `report_path` overrides the path stamped into
    violations (fixture tests lint snippets under synthetic names)."""
    src = parse_source(path, text)
    if report_path is not None:
        src = dataclasses.replace(src, path=report_path)
    return lint_source(src, only=only)


# ------------------------------------------------------------- file walker --

#: directories never swept: caches, VCS, run artifacts, the deliberately-
#: violating fixture corpus, and data/work dirs recipe.sh creates
EXCLUDE_DIRS = {"__pycache__", ".git", "runs", "work", "serve_logs",
                "graftcheck_fixtures", "csrc", "tokenizer", ".claude"}


def iter_python_files(root: str) -> List[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d not in EXCLUDE_DIRS)
        for name in sorted(filenames):
            if name.endswith(".py"):
                out.append(os.path.join(dirpath, name))
    return out


def lint_paths(paths: Sequence[str],
               only: Optional[Sequence[str]] = None,
               root: Optional[str] = None) -> "tuple[List[Violation], int]":
    """Lint files and/or directory trees. Returns (violations, files
    scanned). Paths in violations are relative to `root` when given (the
    stable form the JSON report and the clean-repo test pin)."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(iter_python_files(p))
        else:
            files.append(p)
    out: List[Violation] = []
    for f in files:
        rel = os.path.relpath(f, root) if root else f
        try:
            src = parse_source(f)
        except SyntaxError as e:
            out.append(Violation("syntax-error", rel, e.lineno or 0,
                                 f"unparseable python: {e.msg}"))
            continue
        src = dataclasses.replace(src, path=rel)
        out.extend(lint_source(src, only=only))
    out.sort(key=lambda v: (v.path, v.line, v.rule))
    return out, len(files)


# the checkers self-register on import; import order fixes report order
from . import lints_source  # noqa: E402,F401  (registration side effect)
from . import lints_traced  # noqa: E402,F401  (registration side effect)

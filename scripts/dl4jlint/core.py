"""dl4jlint core: the rule API, finding model, suppressions, file walk.

Everything here is stdlib-only and never imports the package under
analysis (and therefore never imports jax) — the whole suite is pure
``ast`` source analysis, same discipline as ``check_metrics_docs.py``
before it, so a full-repo run stays well under the 5-second budget and
works on a machine with no accelerator stack installed.

Vocabulary:

- A ``Rule`` inspects parsed sources and yields ``Finding``s.  Per-file
  analysis goes in ``check(ctx)``; rules that need the whole corpus (or
  non-Python inputs, like the metrics-docs table) implement
  ``finalize(ctxs)`` instead (or additionally).
- A ``Finding`` is keyed ``(rule, path, symbol)`` for baseline matching
  — deliberately NOT by line number, so unrelated edits shifting a file
  don't invalidate the committed baseline.
- Suppressions are source comments::

      x = y.item()   # dl4jlint: disable=host-sync-in-hot-path -- why
      # dl4jlint: disable-next-line=lock-discipline -- single writer
      # dl4jlint: disable-file=rng-key-reuse -- fixture corpus

  ``disable=all`` silences every rule for the scope.  The ``-- why``
  trailer is conventionally required by review, not enforced here.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PACKAGE_DIR = os.path.join(REPO, "deeplearning4j_tpu")

ERROR = "error"
WARNING = "warning"

_SUPPRESS_RE = re.compile(
    r"#\s*dl4jlint:\s*(disable|disable-next-line|disable-file)"
    r"\s*=\s*([A-Za-z0-9_,\- ]+)")


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str          # repo-relative, forward slashes
    line: int
    symbol: str        # enclosing ``Class.method`` / ``<module>`` / family
    message: str
    severity: str = ERROR

    @property
    def key(self) -> Tuple[str, str, str]:
        """Baseline identity — line numbers intentionally excluded."""
        return (self.rule, self.path, self.symbol)

    def format(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] "
                f"{self.symbol}: {self.message}")

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "symbol": self.symbol, "message": self.message,
                "severity": self.severity}


class Rule:
    """Base class for all dl4jlint rules.

    Subclasses set ``name`` (stable kebab-case id used in baselines and
    suppression comments), ``description`` (one line for --list-rules),
    and override ``check`` and/or ``finalize``."""

    name: str = ""
    description: str = ""
    severity: str = ERROR

    def check(self, ctx: "FileContext") -> Iterable[Finding]:
        return ()

    def finalize(self, ctxs: Sequence["FileContext"]) -> Iterable[Finding]:
        return ()

    # -------------------------------------------------------------- helpers
    def finding(self, ctx: "FileContext", line: int, message: str,
                symbol: Optional[str] = None,
                severity: Optional[str] = None) -> Finding:
        return Finding(self.name, ctx.rel, line,
                       symbol if symbol is not None else ctx.symbol_at(line),
                       message, severity or self.severity)


class FileContext:
    """One parsed source file plus the lookups every rule needs."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self._suppress_file: Set[str] = set()
        self._suppress_line: Dict[int, Set[str]] = {}
        self._parse_suppressions()
        self._scopes = self._collect_scopes()
        self._nodes: Optional[List[ast.AST]] = None
        self._parent: Optional[Dict[ast.AST, ast.AST]] = None
        self.cache: Dict[str, object] = {}   # per-file rule scratch

    @property
    def nodes(self) -> List[ast.AST]:
        """Flat node list, computed lazily once — rules iterate this
        instead of re-walking subtrees (keeps the suite O(nodes)), and
        tree-only consumers (the metrics-docs shim) never pay for it."""
        if self._nodes is None:
            self._nodes = list(ast.walk(self.tree))
        return self._nodes

    @property
    def parent(self) -> Dict[ast.AST, ast.AST]:
        if self._parent is None:
            self._parent = {}
            for node in self.nodes:
                for child in ast.iter_child_nodes(node):
                    self._parent[child] = node
        return self._parent

    def ancestors(self, node: ast.AST):
        parent = self.parent
        while node in parent:
            node = parent[node]
            yield node

    # --------------------------------------------------------- suppressions
    def _parse_suppressions(self) -> None:
        for i, text in enumerate(self.lines, start=1):
            for kind, names in _SUPPRESS_RE.findall(text):
                # the ``-- why`` trailer is prose (may contain commas):
                # strip it before splitting the rule list
                names = names.split("--")[0]
                rules = {n.strip() for n in names.split(",") if n.strip()}
                if kind == "disable-file":
                    self._suppress_file |= rules
                elif kind == "disable-next-line":
                    self._suppress_line.setdefault(i + 1, set()).update(rules)
                else:
                    self._suppress_line.setdefault(i, set()).update(rules)

    def is_suppressed(self, rule: str, line: int) -> bool:
        for scope in (self._suppress_file,
                      self._suppress_line.get(line, ())):
            if rule in scope or "all" in scope:
                return True
        return False

    # --------------------------------------------------------------- scopes
    def _collect_scopes(self) -> List[Tuple[int, int, str]]:
        """(start, end, qualified name) for every function/class, sorted
        outermost-first so the LAST containing interval is innermost."""
        out: List[Tuple[int, int, str]] = []

        def visit(node, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    qual = f"{prefix}.{child.name}" if prefix else child.name
                    out.append((child.lineno,
                                child.end_lineno or child.lineno, qual))
                    visit(child, qual)
                else:
                    visit(child, prefix)

        visit(self.tree, "")
        out.sort()
        return out

    def symbol_at(self, line: int) -> str:
        best = "<module>"
        for start, end, qual in self._scopes:
            if start <= line <= end:
                best = qual
        return best


# ------------------------------------------------------------------ running
def iter_source_files(paths: Optional[Sequence[str]] = None) -> List[str]:
    """Default scan scope: the whole ``deeplearning4j_tpu`` package.
    Explicit ``paths`` (files or directories) override it."""
    if paths:
        out: List[str] = []
        for p in paths:
            p = os.path.abspath(p)
            if os.path.isdir(p):
                for root, _dirs, files in os.walk(p):
                    out.extend(os.path.join(root, f) for f in sorted(files)
                               if f.endswith(".py"))
            else:
                out.append(p)
        return out
    out = []
    for root, _dirs, files in os.walk(PACKAGE_DIR):
        out.extend(os.path.join(root, f) for f in sorted(files)
                   if f.endswith(".py"))
    return sorted(out)


def load_contexts(files: Sequence[str]) -> Tuple[List[FileContext], List[str]]:
    """Parse every file once; unparsable files are reported, not fatal
    (they would fail the test suite on their own)."""
    ctxs: List[FileContext] = []
    errors: List[str] = []
    for path in files:
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
            ctxs.append(FileContext(path, rel, src))
        except (OSError, SyntaxError, ValueError) as e:
            errors.append(f"{rel}: unparsable: {e}")
    return ctxs, errors


@dataclass
class RunResult:
    findings: List[Finding] = field(default_factory=list)
    suppressed: int = 0
    files: int = 0
    errors: List[str] = field(default_factory=list)


def run_rules(rules: Sequence[Rule], ctxs: Sequence[FileContext],
              errors: Optional[List[str]] = None) -> RunResult:
    res = RunResult(files=len(ctxs), errors=list(errors or ()))
    raw: List[Finding] = []
    for rule in rules:
        for ctx in ctxs:
            raw.extend(rule.check(ctx))
        raw.extend(rule.finalize(ctxs))
    by_path = {c.rel: c for c in ctxs}
    for f in raw:
        ctx = by_path.get(f.path)
        if ctx is not None and ctx.is_suppressed(f.rule, f.line):
            res.suppressed += 1
        else:
            res.findings.append(f)
    res.findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return res


# ------------------------------------------------------------ AST utilities
def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def is_call_to(node: ast.AST, *names: str) -> bool:
    """True when ``node`` is a Call whose function's dotted name is one of
    ``names`` (exact match on the dotted string)."""
    return (isinstance(node, ast.Call)
            and dotted_name(node.func) in names)

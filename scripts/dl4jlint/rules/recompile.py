"""recompile-hazard: patterns that defeat XLA's compile cache.

The repo's serving contract assumes a CLOSED shape set and zero
steady-state compiles (PR-2's bucket policy; the benchmark's
``window_compiles`` reads 0 in every cell).  Three statically detectable ways
code breaks that:

1. **fresh-jit-invoked-immediately** — ``jax.jit(f)(x)``: the jitted
   callable is born, compiled, and thrown away; every call pays a full
   trace+compile.
2. **jit-inside-a-loop** — a ``jax.jit(...)`` call in a For/While body
   builds a new callable (new cache) per iteration.  Legit one-off
   setups (one jit per pipeline stage, reused for the whole run) are
   expected findings: baseline them with a ``why``.
3. **shape-derived argument without static_argnums** — a call through a
   symbol bound to ``jax.jit(f)`` (no ``static_argnums``/
   ``static_argnames``) passing ``len(...)``, ``x.shape``/``x.shape[i]``
   or ``x.ndim``: a Python int that varies with the data retraces on
   every new value.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from scripts.dl4jlint.core import FileContext, Finding, Rule, dotted_name
from scripts.dl4jlint import jitscan


def _is_shape_derived(node: ast.AST) -> bool:
    """len(...), x.shape, x.shape[i], x.ndim — per-call Python ints."""
    if isinstance(node, ast.Call) and dotted_name(node.func) == "len":
        return True
    if isinstance(node, ast.Attribute) and node.attr in ("shape", "ndim"):
        return True
    if isinstance(node, ast.Subscript):
        return _is_shape_derived(node.value)
    return False


class RecompileHazardRule(Rule):
    name = "recompile-hazard"
    description = ("jax.jit created per call/iteration, or a jitted "
                   "callable fed per-call Python shapes without "
                   "static_argnums")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        scan = jitscan.scan(ctx)
        findings: List[Finding] = []
        seen: set = set()

        def emit(line: int, msg: str) -> None:
            if (line, msg[:20]) in seen:
                return
            seen.add((line, msg[:20]))
            findings.append(self.finding(ctx, line, msg))

        # one pass over the flat node list; placement via parent links
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            # 1) jax.jit(...)(...) — callable discarded after one call.
            # Direct form only: partial(jax.jit, kw)(fn) is the BINDING
            # idiom (construct once, reuse), not an immediate invocation.
            if jitscan.is_direct_jit_call(node.func):
                emit(node.lineno,
                     "jax.jit(...) invoked immediately: the compiled "
                     "callable is discarded, so every call re-traces and "
                     "re-compiles — bind it once and reuse it")
            # 2) jax.jit inside a loop body
            if jitscan.is_jit_call(node) and any(
                    isinstance(a, (ast.For, ast.While))
                    for a in ctx.ancestors(node)):
                emit(node.lineno,
                     "jax.jit(...) inside a loop: a fresh callable "
                     "(fresh compile cache) per iteration — hoist it "
                     "out of the loop or memoise per static config")
            # 3) shape-derived args into a jitted symbol w/o static_argnums
            sym = scan.symbol_of_call(node)
            if sym is None or scan.jitted_symbols.get(sym):
                continue   # unknown symbol, or jit declared static args
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if _is_shape_derived(arg):
                    emit(node.lineno,
                         f"jitted callable {sym} fed a per-call Python "
                         f"shape/length without static_argnums: every new "
                         f"value triggers a re-trace and XLA re-compile")
                    break
        return findings

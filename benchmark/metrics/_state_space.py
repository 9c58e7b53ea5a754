"""Device time of the state-space layers, from the programs' own tables.

``_layer_time.GROUPS`` sends the ``recurrent`` kind to ``rest`` (a
``benchmark`` issue may give it a row), so the readers of ``jamba2.serve-chat``
that want the kind's own time make a pass of their own over the same two
sources — ``observability.recompile.program_scopes`` for the tables, the
trace's outermost events on the first device — under ``_layer_time``'s rules:
an instruction's kind is the innermost known word of its scope path; a fusion
or a ``while`` goes to the kind that holds the most of what is fused into it,
matrix products and kernel calls first, then instructions, its own on a tie;
XLA's own instructions go to the kind of the instruction that consumes them.

Inside the kind, the layer's three inner scopes (``nn/layers/state_space.py``)
split it by the same rule: ``ssm_proj`` (the four products, the selection's
norms, the gate), ``ssm_conv`` (the convolution and its tail's rows),
``ssm_scan`` (the recurrence and its state's rows).

On a program without such scopes every function here returns None.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from benchmark.metrics import _layer_time
from benchmark.trace_reduce import short_name

KIND = "recurrent"
INNER = ("ssm_proj", "ssm_conv", "ssm_scan")
_KINDS = {**_layer_time.GROUPS, KIND: KIND, **{w: KIND for w in INNER}}


def _word(path, words):
    for word in reversed(path.split("/")):
        if word in words:
            return words[word] if isinstance(words, dict) else word
    return None


def _of_row(row, words):
    """``_layer_time.kind_of_row`` over another vocabulary."""
    own = _word(row.path, words)
    tally = defaultdict(lambda: [0, 0])
    for path, (count, matmuls) in row.fused.items():
        word = _word(path, words)
        if word is not None:
            tally[word][0] += matmuls
            tally[word][1] += count
    if not tally:
        return own
    return max(tally, key=lambda k: (tally[k], k == own))


def tables(ctx) -> dict:
    """``{module: {short name: inner scope or ""}}`` of the instructions of
    kind ``recurrent`` in every registered program; built once a run."""
    if "_state_space_tables" in ctx.obs:
        return ctx.obs["_state_space_tables"]
    from deeplearning4j_tpu.observability import recompile

    out = {}
    if hasattr(recompile, "program_scopes"):
        for name in recompile.registered_programs():
            scopes = recompile.program_scopes(name)
            kinds = {r.name: _of_row(r, _KINDS) for r in scopes.rows}
            inner = {r.name: _of_row(r, INNER) or "" for r in scopes.rows}
            table = out.setdefault(scopes.module, {})
            for r in scopes.rows:
                via = r.name if kinds[r.name] else r.consumer
                if kinds.get(via) == KIND:
                    table[short_name(f"%{r.name} = {r.shape} {r.opcode}(")] = (
                        inner.get(via, ""))
    ctx.obs["_state_space_tables"] = out
    return out


def seconds(ctx, program: str, inner=None):
    """``(seconds, executions)`` of the ``recurrent`` kind's outermost events
    (of the ``inner`` scopes only, if given) inside the executions of the
    programs whose module name contains ``program``; None without a table
    that holds the kind."""
    maps = {m: t for m, t in tables(ctx).items() if program in m and t}
    trace = ctx.trace
    ops = sorted(next(iter(trace.ops.values()), []),
                 key=lambda e: (e.start, -e.dur))
    mods = sorted((m for m in next(iter(trace.modules.values()), [])
                   if _layer_time.module_of(m.name) in maps),
                  key=lambda m: m.start)
    if not maps or not ops or not mods:
        return None
    starts, covered, total = [m.start for m in mods], float("-inf"), 0.0
    for e in ops:
        if e.start < covered:
            continue                      # inside an event already counted
        covered = e.end
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.start >= mods[i].end:
            continue
        scope = maps[_layer_time.module_of(mods[i].name)].get(e.name)
        if scope is not None and (inner is None or scope in inner):
            total += e.dur / 1e9
    return total, len(mods)

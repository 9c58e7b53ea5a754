"""Device time of the delta-rule (Gated DeltaNet) layers, from the programs'
own tables: ``_state_space``'s pass over the ``recurrent`` kind, with the
layer's own inner scopes (``nn/layers/delta_net.py``) in place of the Mamba
mixer's: ``gdn_proj`` (the six products, the gated norm and the gate),
``gdn_conv`` (the three convolutions and their tail's rows), ``gdn_chunk``
(the chunked delta rule of a prefill and its state's row), ``gdn_state``
(the decode step's delta rule on the state slots, in place).

On a program without such scopes every function here returns None.
"""

from __future__ import annotations

import bisect

from benchmark.metrics import _layer_time
from benchmark.metrics._state_space import KIND, _of_row
from benchmark.trace_reduce import short_name

INNER = ("gdn_proj", "gdn_conv", "gdn_chunk", "gdn_state")
_KINDS = {**_layer_time.GROUPS, KIND: KIND, **{w: KIND for w in INNER}}


def tables(ctx) -> dict:
    """``{module: {short name: inner scope or ""}}`` of the instructions of
    kind ``recurrent`` in every registered program; built once a run."""
    if "_linear_attention_tables" in ctx.obs:
        return ctx.obs["_linear_attention_tables"]
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
    ctx.obs["_linear_attention_tables"] = out
    return out


def seconds(ctx, program: str, inner=None):
    """``(seconds, executions)`` of the ``recurrent`` kind's outermost events
    (of the ``inner`` scopes only, if given) inside the executions of the
    programs whose module name contains ``program``; None without a table
    that holds the kind or without a delta-rule scope in it."""
    maps = {m: t for m, t in tables(ctx).items()
            if program in m and set(t.values()) & set(INNER)}
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

"""Device time by ``jax.named_scope``, for a trace whose ``XLA Ops`` events
carry no scope path (PERF.md section 7, row 9): at set-up a job keeps, for
each compute program, a map from a device operation's name as the trace
shows it (``trace_reduce.short_name``: instruction name and the shape it
produces) to the scope its ``op_name`` lies under in the program's own
compiled text; a reader then sums the trace's events by that map.

A fusion is one instruction made of several: it goes to the scope of its own
``op_name`` (its root's); where the instructions fused into it lie under
scopes of different families (``mhc_*`` against anything else) it is also
marked ``mixed``, and its time is what the attribution leaves ambiguous.
Programs are told apart by kind (``decode`` against ``prefill``, as the
trace's ``XLA Modules`` line names them); the buckets of one kind share a map,
and a name that two buckets put under different scopes is ``mixed`` too.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from benchmark.trace_reduce import short_name

SCOPES = ("mhc_coeffs", "mhc_sinkhorn", "mhc_mix", "mla_attention",
          "moe_router", "moe_experts", "moe_shared_expert", "sample")
KINDS = ("decode", "prefill")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")


def scope_of(op_name: str):
    """The innermost of ``SCOPES`` on an ``op_name`` path, else None."""
    found = None
    for part in op_name.split("/"):
        if part in SCOPES:
            found = part
    return found


def _family(scope):
    return "mhc" if scope and scope.startswith("mhc_") else "other"


def program_map(text: str) -> dict:
    """``{short name: (scope or None, mixed)}`` of every instruction of one
    compiled program's text."""
    bodies, current = defaultdict(list), None
    for raw in text.splitlines():
        line = raw.strip()
        head = _HEAD.match(line)
        if head and " = " not in line:
            current = head.group(1)
        elif line == "}":
            current = None
        elif current is not None and " = " in line:
            bodies[current].append(line[5:] if line.startswith("ROOT ")
                                   else line)
    own = {}                  # computation -> scopes of its instructions
    for comp, lines in bodies.items():
        own[comp] = {scope_of(m.group(1)) for m in
                     map(_OP_NAME.search, lines) if m}
    out = {}
    for lines in bodies.values():
        for line in lines:
            m = _OP_NAME.search(line)
            scope = scope_of(m.group(1)) if m else None
            called = _CALLS.search(line)
            inner = own.get(called.group(1), set()) if called else set()
            if scope is None and len(inner) == 1:
                scope = next(iter(inner))
            mixed = len({_family(s) for s in inner | {scope}}) > 1 \
                if inner else False
            out[short_name(line if line.startswith("%") else "%" + line)] = \
                (scope, mixed)
    return out


def of_programs(compiled_texts: dict) -> dict:
    """``{kind: {short name: (scope, mixed)}}`` from ``{program name:
    compiled text}``; ``prefill_<bucket>`` programs merge into ``prefill``."""
    maps = {k: {} for k in KINDS}
    for name, text in compiled_texts.items():
        kind = "decode" if name == "decode" else "prefill"
        for key, (scope, mixed) in program_map(text).items():
            if key in maps[kind] and maps[kind][key][0] != scope:
                old = maps[kind][key][0]
                mixed = mixed or _family(old) != _family(scope)
                scope = old
            maps[kind][key] = (scope, mixed or maps[kind].get(
                key, (None, False))[1])
    return maps


def seconds_by_scope(trace, maps: dict) -> dict:
    """Device seconds of the traced window by scope (first device): the
    ``SCOPES`` that ran, ``"no_scope"`` (mapped, under none of them),
    ``"mixed_mhc"`` (fusions that mix ``mhc_*`` with another family: counted
    under their root's scope AND here) and ``"unmapped"`` (events inside a
    compute program whose name the maps do not hold).  The line nests — a
    ``while`` is one event and every operation of its trips another — so
    only an outermost event counts, for everything it holds."""
    ops = sorted(next(iter(trace.ops.values()), []),
                 key=lambda e: (e.start, -e.dur))
    mods = sorted(next(iter(trace.modules.values()), []),
                  key=lambda m: m.start)
    starts = [m.start for m in mods]
    out, covered = defaultdict(float), float("-inf")
    for e in ops:
        if e.start < covered:
            continue                      # inside an event already counted
        covered = e.end
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.start >= mods[i].end:
            continue
        kind = next((k for k in KINDS if k in mods[i].name), None)
        if kind is None:
            continue
        scope, mixed = maps[kind].get(e.name, ("unmapped", False))
        out[scope or "no_scope"] += e.dur / 1e9
        if mixed:
            out["mixed_mhc"] += e.dur / 1e9
    return dict(out)

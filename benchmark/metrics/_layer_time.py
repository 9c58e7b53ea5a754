"""Device time by the program's own layers.

Every layer's device operations carry a *kind* scope (``attention``, ``ffn``,
``experts``, ``head``, ``norm``, ``embed``: ``deeplearning4j_tpu.nn.layers.
base.KINDS``) beside the program's own (``loss``, ``updater``,
``param_cast``, ``sample``, ``mhc_*``), and each compiled program publishes
its table from instruction to scope path (``observability.recompile.
program_scopes``).  After the window this helper asks for the table of every
registered program, keys it as the trace names a device operation
(``trace_reduce.short_name``), and sums the trace's events by kind, each
event against the table of the program it ran in (matched by the ``XLA
Modules`` event's name, so ``jit_prefill_512`` and ``jit_prefill_2048`` do
not share a map).

Rules, the same for every reader built on it:

* first device; the ``XLA Ops`` line nests (a ``while`` is one event and each
  operation of its trips another), so only an outermost event counts, for
  everything it holds;
* an instruction's kind is the innermost word of ``GROUPS`` on a scope path.
  One that calls computations (a fusion, a ``while``) goes to the kind that
  holds the most of what is fused into it, matrix products and kernel calls
  first, then instructions; to its own ``op_name``'s (its root's) on a tie;
* an instruction without an ``op_name`` of its own (XLA's: the ``copy-done``
  that waits for a prefetched weight, a layout ``copy``, a ``ConcatBitcast``)
  goes to the kind of the nearest instruction that consumes its result;
* ``rest`` of a program is its ``XLA Modules`` time less the four kinds the
  readers name, so the parts add up to the program's time: it holds the
  known others (norms, embedding, hyper-connections, a collective), the gaps
  between operations, and what ``device_time_unattributed_share`` counts:
  events under no kind and events no table holds.

On a library without ``program_scopes`` (the parent of the PR that brought
this file) every reader returns None.
"""

from __future__ import annotations

import bisect
import time
from collections import defaultdict

from benchmark.trace_reduce import short_name, union_ns

# scope word -> what the readers call it; "other" is known and is none of
# the kinds a reader names
GROUPS = {
    "attention": "attention", "mla_attention": "attention",
    "attention_core": "attention", "attn_gate": "attention",
    "experts": "experts", "moe_router": "experts", "moe_experts": "experts",
    "moe_shared_expert": "experts",
    "ffn": "ffn",
    "head": "head", "sample": "head",
    "loss": "loss", "updater": "updater", "param_cast": "param_cast",
    "norm": "other", "embed": "other", "conv": "other",
    "recurrent": "other", "mhc_coeffs": "other", "mhc_sinkhorn": "other",
    "mhc_mix": "other",
}
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
UNKNOWN = "unattributed"      # under no kind, or in no table


def kind_of_path(path: str):
    for word in reversed(path.split("/")):
        if word in GROUPS:
            return GROUPS[word]
    return None


def kind_of_row(row):
    """The kind of one ``ScopeRow`` (module docstring, second rule)."""
    if row.name.startswith(COLLECTIVES):
        return "other"                # XLA's own: known by its instruction
    own = kind_of_path(row.path)
    tally = defaultdict(lambda: [0, 0])       # kind -> [matmuls, instructions]
    for path, (count, matmuls) in row.fused.items():
        kind = kind_of_path(path)
        if kind is not None:
            tally[kind][0] += matmuls
            tally[kind][1] += count
    if not tally:
        return own
    return max(tally, key=lambda k: (tally[k], k == own))


def table_of(scopes) -> dict:
    """``{short name: kind or None}`` of one ``ProgramScopes``.  An
    instruction that XLA put in to move an operand (no ``op_name``: the
    ``copy-done`` of a prefetched weight, a ``ConcatBitcast``) goes to the
    kind of the instruction that consumes it."""
    kinds = {r.name: kind_of_row(r) for r in scopes.rows}
    return {short_name(f"%{r.name} = {r.shape} {r.opcode}("):
            kinds[r.name] or kinds.get(r.consumer)
            for r in scopes.rows}


def tables(ctx) -> dict:
    """``{module name: {short name: kind}}`` of every registered program,
    built once a run; ``{}`` on a library that publishes none."""
    if "_layer_tables" not in ctx.obs:
        from deeplearning4j_tpu.observability import recompile

        out, took = {}, {}
        if hasattr(recompile, "program_scopes"):
            for name in recompile.registered_programs():
                t0 = time.perf_counter()
                scopes = recompile.program_scopes(name)
                out.setdefault(scopes.module, {}).update(table_of(scopes))
                took[name] = round(time.perf_counter() - t0, 3)
            ctx.obs.setdefault("notes", {})["program_scopes_s"] = took
        ctx.obs["_layer_tables"] = out
    return ctx.obs["_layer_tables"]


def module_of(event_name: str) -> str:
    """``jit_prefill_512(1234567)`` -> ``jit_prefill_512``."""
    return event_name.split("(", 1)[0]


def split(ctx):
    """``{module name: {"runs": [seconds], "kinds": {kind: seconds},
    "lost": {short name: seconds}}}`` of the traced window (first device):
    each program's executions, its outermost events' time by kind, and the
    events of kind ``UNKNOWN`` by name; the seconds no module holds under
    module ``""``.  None without a table or without a device line."""
    if "_layer_split" in ctx.obs:
        return ctx.obs["_layer_split"]
    maps = tables(ctx)
    trace = ctx.trace
    ops = sorted(next(iter(trace.ops.values()), []),
                 key=lambda e: (e.start, -e.dur))
    mods = sorted(next(iter(trace.modules.values()), []),
                  key=lambda m: m.start)
    out = None
    if maps and ops and mods:
        out = defaultdict(lambda: {"runs": [], "kinds": defaultdict(float),
                                   "lost": defaultdict(float)})
        for m in mods:
            out[module_of(m.name)]["runs"].append(m.dur / 1e9)
        starts, covered = [m.start for m in mods], float("-inf")
        for e in ops:
            if e.start < covered:
                continue                  # inside an event already counted
            covered = e.end
            i = bisect.bisect_right(starts, e.start) - 1
            name = (module_of(mods[i].name)
                    if i >= 0 and e.start < mods[i].end else "")
            kind = maps.get(name, {}).get(e.name) or UNKNOWN
            out[name]["kinds"][kind] += e.dur / 1e9
            if kind is UNKNOWN:
                out[name]["lost"][e.name] += e.dur / 1e9
        out = {k: {key: (val if key == "runs" else dict(val))
                   for key, val in v.items()} for k, v in out.items()}
    ctx.obs["_layer_split"] = out
    return out


def _programs(ctx, contains):
    """The window's programs whose module name contains ``contains`` and
    that have a table: ``[(module name, entry of split())]``."""
    parts = split(ctx)
    if not parts:
        return []
    maps = tables(ctx)
    return [(name, entry) for name, entry in sorted(parts.items())
            if contains in name and name in maps and entry["runs"]]


def _part(programs, kind, named):
    """Seconds of ``kind`` over ``programs``; for ``"rest"``, their module
    time less the ``named`` kinds."""
    if kind != "rest":
        return sum(e["kinds"].get(kind, 0.0) for _, e in programs)
    return sum(sum(e["runs"]) - sum(e["kinds"].get(k, 0.0) for k in named)
               for _, e in programs)


SERVE_KINDS = ("attention", "experts", "ffn", "head")


def decode_step_ms(ctx, kind):
    """Device milliseconds of ``kind`` a decode execution, the mean over the
    window's executions; the five add up to the mean ``XLA Modules``
    duration of the decode program."""
    programs = _programs(ctx, "decode")
    runs = sum(len(e["runs"]) for _, e in programs)
    if not runs:
        return None
    return 1e3 * _part(programs, kind, SERVE_KINDS) / runs


def prefill_share(ctx, kind):
    """Percent of the window the prefill programs spent in ``kind``, all
    buckets together; the five add up to ``prefill_share_of_window``.  Also
    leaves ``prefill_ms_by_bucket`` in the line's notes."""
    programs = _programs(ctx, "prefill")
    if not programs:
        return None
    ctx.obs.setdefault("notes", {})["prefill_ms_by_bucket"] = {
        name: {"mean_ms": round(1e3 * sum(e["runs"]) / len(e["runs"]), 3),
               "count": len(e["runs"])} for name, e in programs}
    return 100.0 * _part(programs, kind, SERVE_KINDS) / ctx.trace.window_s


_TRAIN_SCOPES = {"attention": ("attention",), "ffn": ("ffn",),
                 "head_loss": ("head", "loss"), "optimizer": ("updater",),
                 "param_cast": ("param_cast",)}


def train_step_ms(ctx, kind):
    """Device milliseconds of ``kind`` a training step: the step is the
    registered program that took most of the window; the six add up to the
    window's busy time over its executions (``rest`` takes what the others
    leave, the feed's small programs included)."""
    programs = _programs(ctx, "")
    if not programs:
        return None
    _, step = max(programs, key=lambda p: sum(p[1]["runs"]))
    steps = len(step["runs"])
    named = {k: sum(step["kinds"].get(s, 0.0) for s in scopes)
             for k, scopes in _TRAIN_SCOPES.items()}
    if kind != "rest":
        return 1e3 * named[kind] / steps
    return 1e3 * (ctx.trace.busy_s - sum(named.values())) / steps


def unattributed_share(ctx):
    """Percent of the first device's busy time in events under no kind or in
    no table."""
    parts = split(ctx)
    if not parts:
        return None
    busy = union_ns(next(iter(ctx.trace.ops.values()))) / 1e9
    lost = sum(e["kinds"].get(UNKNOWN, 0.0) for e in parts.values())
    notes = ctx.obs.setdefault("notes", {})
    notes["unattributed_by_program"] = {
        name or "(no program)": round(
            100.0 * e["kinds"].get(UNKNOWN, 0.0) / busy, 3)
        for name, e in parts.items() if e["kinds"].get(UNKNOWN)}
    worst = sorted(((sec, f"{name}: {op}") for name, e in parts.items()
                    for op, sec in e["lost"].items()), reverse=True)[:8]
    notes["unattributed_top"] = [[op, round(100.0 * sec / busy, 3)]
                                 for sec, op in worst]
    return 100.0 * lost / busy

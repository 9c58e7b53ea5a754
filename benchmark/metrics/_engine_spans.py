"""The decode loop's own spans in the traced window, and the device's idle
time split among them.

``GenerationEngine`` enters ``generation_decode.<stage>.<phase>`` spans
(``PhaseTimers``; the table is in docs/observability.md, "Span tracing"),
all on its one decode thread, so at any instant the innermost one is unique.
Each idle interval of the first device, however short, is cut by
intersection among them: a gap that straddles two spans is shared out, a
child takes its part from the span around it, and the spans of other
threads (``client_submit``) and JAX's own events take no part.

The parts, as shares of the window, add up to ``Reduced.idle_share()``:
``admit`` (inside any ``admit.*``), ``decode_dispatch``
(``decode.jitted_step``), ``decode_harvest`` (``decode.sample_harvest``),
``decode_host`` (every other span: ``decode.stream_write`` with its children,
``loop.schedule``), ``unattributed`` (under no span of the loop) and ``wait``
(``loop.wait``: the device waiting for a request, no fault of the loop; no
metric of its own until an open-loop cell can read it).

A program without these spans (a parent commit) gives ``None`` everywhere.
"""

from collections import defaultdict

from benchmark.trace_reduce import clip, gaps_of

PREFIX = "generation_decode."
PARTS = ("admit", "decode_dispatch", "decode_harvest", "decode_host",
         "unattributed", "wait")


def part_of(name):
    """The part of the idle time that a span's share goes to."""
    stage, _, phase = name[len(PREFIX):].partition(".")
    if stage == "admit":
        return "admit"
    if (stage, phase) == ("decode", "jitted_step"):
        return "decode_dispatch"
    if (stage, phase) == ("decode", "sample_harvest"):
        return "decode_harvest"
    if (stage, phase) == ("loop", "wait"):
        return "wait"
    return "decode_host"


def spans(ctx):
    """The loop's spans that touch the traced window, clipped to it."""
    t = ctx.trace
    return clip([s for s in t.host_spans if s.name.startswith(PREFIX)],
                t.t0, t.t1)


def named(ctx, *phases):
    """The loop's spans in the window named ``<stage>.<phase>``."""
    want = {PREFIX + p for p in phases}
    return [s for s in spans(ctx) if s.name in want]


def innermost(events):
    """Disjoint ``(start, end, name)`` pieces, in order: at every instant
    the span that started last among those open (one thread's spans nest)."""
    pieces, stack, at = [], [], float("-inf")

    def close(upto):
        nonlocal at
        if upto <= at:
            return
        if stack:
            pieces.append((at, upto, stack[-1].name))
        at = upto

    for sp in sorted(events, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            close(stack[-1].end)
            stack.pop()
        close(sp.start)
        stack.append(sp)
    while stack:
        close(stack[-1].end)
        stack.pop()
    return pieces


def idle_shares(ctx):
    """``{part: share of the window}`` over ``PARTS``; None without a device
    operation or without the loop's spans in the trace."""
    t = ctx.trace
    ops = next(iter(t.ops.values()), None)
    pieces = innermost(spans(ctx))
    if not ops or not pieces:
        return None
    idle = defaultdict(float)
    i = 0
    for g0, g1 in gaps_of(ops, t.t0, t.t1):
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        rest, j = g1 - g0, i
        while j < len(pieces) and pieces[j][0] < g1:
            s, e, name = pieces[j]
            cut = min(e, g1) - max(s, g0)
            idle[part_of(name)] += cut
            rest -= cut
            j += 1
        idle["unattributed"] += rest
    return {p: idle[p] / (t.t1 - t.t0) for p in PARTS}


def idle_percent(ctx, part):
    shares = idle_shares(ctx)
    return None if shares is None else 100.0 * shares[part]

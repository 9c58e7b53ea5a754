"""Prefill dispatches per decode step in the traced window: the count of
``generation_decode.admit.jitted_step`` spans over the count of
``generation_decode.decode.jitted_step`` spans.  A count, from host spans
alone: how much admission work sits between two decode steps."""

from benchmark.metrics import _engine_spans


def read(ctx):
    steps = _engine_spans.named(ctx, "decode.jitted_step")
    if not steps:
        return None
    return len(_engine_spans.named(ctx, "admit.jitted_step")) / len(steps)

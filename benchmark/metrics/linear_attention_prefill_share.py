"""Percent of the traced window the ``prefill_<bucket>`` programs spend in
the delta-rule layers (the ``recurrent`` kind: products, convolutions, the
chunked delta rule, the gated norm), all buckets together, read from the
programs' ``program_scopes`` tables (``_linear_attention``).  The same time
also sits inside ``prefill_share.rest``.  The chunked form's own part goes
into the line's notes as ``delta_chunk_prefill_share``.  Silent on a program
without the scopes."""

from benchmark.metrics import _linear_attention


def read(ctx):
    timed = _linear_attention.seconds(ctx, "prefill")
    if not timed:
        return None
    chunk = _linear_attention.seconds(ctx, "prefill", ("gdn_chunk",))
    ctx.obs.setdefault("notes", {})["delta_chunk_prefill_share"] = round(
        100.0 * chunk[0] / ctx.trace.window_s, 3)
    return 100.0 * timed[0] / ctx.trace.window_s

"""Percent of the traced window the ``prefill_<bucket>`` programs spend in the
state-space layers (the ``recurrent`` kind: products, convolution, selection
and the chunked scan), all buckets together, read from the programs'
``program_scopes`` tables (``_state_space``).  The same time also sits inside
``prefill_share.rest``.  The scan's own part goes into the line's notes as
``state_space_scan_prefill_share``.  Silent on a program without the kind."""

from benchmark.metrics import _state_space


def read(ctx):
    timed = _state_space.seconds(ctx, "prefill")
    if not timed:
        return None
    scan = _state_space.seconds(ctx, "prefill", ("ssm_scan",))
    ctx.obs.setdefault("notes", {})["state_space_scan_prefill_share"] = round(
        100.0 * scan[0] / ctx.trace.window_s, 3)
    return 100.0 * timed[0] / ctx.trace.window_s

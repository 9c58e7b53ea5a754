"""Median device time of one execution of the decode program, from the
trace's ``XLA Modules`` line, in milliseconds."""

import statistics


def read(ctx):
    runs = ctx.trace.module_durations("decode")
    return 1e3 * statistics.median(runs) if runs else None

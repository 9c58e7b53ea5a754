"""Share of the traced window in which a ``prefill_<bucket>`` program ran
on the device (decode waits while it does), in percent."""


def read(ctx):
    runs = ctx.trace.module_durations("prefill")
    if not runs:
        return None
    return 100.0 * sum(runs) / ctx.trace.window_s

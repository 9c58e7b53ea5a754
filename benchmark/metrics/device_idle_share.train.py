"""Share of the traced window in which no operation ran on the device, in
percent: 1 - union of the device's op intervals / window."""


def read(ctx):
    idle = ctx.trace.idle_share()
    return None if idle is None else 100.0 * idle

"""Share of the traced window the first device's ``XLA Ops`` line spent in
collective operations, in percent: the summed time of the events whose
instruction is an ``all-reduce``, ``reduce-scatter``, ``all-gather`` or
``collective-permute`` (fused forms such as ``all-reduce-scatter`` too), over
the window.

What that line shows of a collective: the line is the core's own sequence of
operations, one at a time.  A synchronous collective is one event that lasts
until the data has arrived.  An asynchronous one is two: ``<op>-start``,
which only issues the transfer and is short, and ``<op>-done``, which lasts
from the moment the core has nothing else to run ahead of the result until
the transfer ends.  Whatever compute the scheduler put between the two ran
while the transfer was in flight and is not counted; so the sum of these
events is the part of the collectives that was NOT hidden behind compute —
the exposed share — and not their duration on the wire.  Silent where no
such event ran (one chip)."""

NAMES = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
         "all-to-all")


def read(ctx):
    ops = next(iter(ctx.trace.ops.values()), [])
    seconds = sum(e.dur for e in ops
                  if any(n in e.name.split(" ", 1)[0] for n in NAMES)) / 1e9
    if not seconds:
        return None
    return 100.0 * seconds / ctx.trace.window_s

"""Percent of the device's busy time in the traced window (first device) that
the layer split cannot place: events whose instruction lies under no kind
scope, events that the table of the program they ran in does not hold, and
events of programs that published no table (the feed's one-hot, page
transport).  The part of every ``*.rest`` that is ignorance and not norms; a
collective that XLA inserted is known by its instruction and is not counted.
What tells a later reader that a jax upgrade broke the map.  The share by
program goes into the line's notes (``_layer_time``).  The
serve cells' half of one quantity (``.train`` is the other: a metric moves
one end-to-end metric)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.unattributed_share(ctx)

"""Share of the traced window, in percent, in which the device was idle
under no ``generation_decode.*`` span at all: what the loop's spans fail to
cover (the lease, the ``busy_wall_s`` arithmetic, loop control, and whatever
other threads do with the interpreter lock when ``_step`` and ``_prefill``
free their arrays on return: PERF.md section 6, PR 27).  One part of
``device_idle_share.serve``, cut by intersection (``_engine_spans``)."""

from benchmark.metrics import _engine_spans


def read(ctx):
    return _engine_spans.idle_percent(ctx, "unattributed")

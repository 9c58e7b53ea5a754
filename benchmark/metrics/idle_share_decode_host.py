"""Share of the traced window, in percent, in which the device was idle
inside ``generation_decode.decode.stream_write`` (its children ``deliver``
and ``gauges`` included) or ``generation_decode.loop.schedule``: the loop's
own Python between two dispatches.  One part of ``device_idle_share.serve``,
cut by intersection (``_engine_spans``)."""

from benchmark.metrics import _engine_spans


def read(ctx):
    return _engine_spans.idle_percent(ctx, "decode_host")

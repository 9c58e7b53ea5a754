"""Share of the traced window, in percent, in which the device was idle
inside ``generation_decode.decode.jitted_step``, the dispatch of the decode
program.  One part of ``device_idle_share.serve``, cut by intersection
(``_engine_spans``)."""

from benchmark.metrics import _engine_spans


def read(ctx):
    return _engine_spans.idle_percent(ctx, "decode_dispatch")

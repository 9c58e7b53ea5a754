"""Share of the traced window, in percent, in which the device was idle
inside ``generation_decode.decode.sample_harvest``, the ``device_get`` of the
sampled ids.  One part of ``device_idle_share.serve``, cut by intersection
(``_engine_spans``)."""

from benchmark.metrics import _engine_spans


def read(ctx):
    return _engine_spans.idle_percent(ctx, "decode_harvest")

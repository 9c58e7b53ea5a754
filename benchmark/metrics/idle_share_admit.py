"""Share of the traced window, in percent, in which the device was idle
inside any ``generation_decode.admit.*`` span: admission's scheduling, page
gather, the prefill's dispatch and harvest, and the install.  One part of
``device_idle_share.serve``, cut by intersection (``_engine_spans``)."""

from benchmark.metrics import _engine_spans


def read(ctx):
    return _engine_spans.idle_percent(ctx, "admit")

"""Percent of the traced window the ``prefill_<bucket>`` programs spend in the
output head and the sampling epilogue (kind scope ``head`` and the program's
``sample``), all buckets together.  The five ``prefill_share.*`` add up to
``prefill_share_of_window``; ``prefill_ms_by_bucket`` goes into the line's
notes (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.prefill_share(ctx, "head")

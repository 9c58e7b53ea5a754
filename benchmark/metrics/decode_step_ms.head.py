"""Device milliseconds a decode execution spends in the output head and the
sampling epilogue (kind scope ``head`` and the program's ``sample``), the
mean over the traced window's executions.  The five ``decode_step_ms.*`` add
up to the mean ``XLA Modules`` duration of the decode program
(``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.decode_step_ms(ctx, "head")

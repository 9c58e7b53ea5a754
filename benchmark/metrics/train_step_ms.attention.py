"""Device milliseconds a training step spends in the attention layers, forward
and backward (kind scope ``attention`` with ``attention_core``: projections,
rotary, the flash kernels).  The six ``train_step_ms.*`` add up to the
traced window's busy time over its steps (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.train_step_ms(ctx, "attention")

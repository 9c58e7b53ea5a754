"""Device milliseconds a training step spends in the cast of the f32 parameters
to the compute dtype and of their gradients back (the ``param_cast`` scope
of ``models.common.cast_to_compute``).  The six ``train_step_ms.*`` add up
to the traced window's busy time over its steps (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.train_step_ms(ctx, "param_cast")

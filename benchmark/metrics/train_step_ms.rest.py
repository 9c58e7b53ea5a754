"""Device milliseconds a training step spends in everything else: norms, the
embedding, residual adds, the gradients' all-reduce across chips (whose own
metric is ``allreduce_exposed_share``), the feed's small programs, the gaps
between operations, and what no table or kind holds
(``device_time_unattributed_share``).  The six ``train_step_ms.*`` add up to
the traced window's busy time over its steps (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.train_step_ms(ctx, "rest")

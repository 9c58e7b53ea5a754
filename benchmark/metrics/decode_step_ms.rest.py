"""Device milliseconds a decode execution spends in everything else: norms, the
embedding, the hyper-connections, residual adds, the gaps between
operations, and what no table or kind holds
(``device_time_unattributed_share``), the mean over the traced window's
executions.  The five ``decode_step_ms.*`` add up to the mean ``XLA
Modules`` duration of the decode program (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.decode_step_ms(ctx, "rest")

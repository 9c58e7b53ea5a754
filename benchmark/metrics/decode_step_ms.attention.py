"""Device milliseconds a decode execution spends in the attention layers (kind
scope ``attention`` with the inner scopes ``mla_attention``,
``attention_core``, ``attn_gate``: projections, rotary, page writes, the
kernel or the gather, the per-head gate), the mean over the traced window's
executions.  The five ``decode_step_ms.*`` add up to the mean ``XLA
Modules`` duration of the decode program (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.decode_step_ms(ctx, "attention")

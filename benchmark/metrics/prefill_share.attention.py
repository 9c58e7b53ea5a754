"""Percent of the traced window the ``prefill_<bucket>`` programs spend in the
attention layers (kind scope ``attention`` with the inner scopes
``mla_attention``, ``attention_core``, ``attn_gate``: projections, rotary,
page writes, the kernel or the gather, the per-head gate), all buckets
together.  The five ``prefill_share.*`` add up to
``prefill_share_of_window``; ``prefill_ms_by_bucket`` goes into the line's
notes (``_layer_time``)."""

from benchmark.metrics import _layer_time


def read(ctx):
    return _layer_time.prefill_share(ctx, "attention")

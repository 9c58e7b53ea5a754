"""Device milliseconds a decode execution spends in the delta-rule layers
(the ``recurrent`` kind: the six products, the convolutions, the state step
on the slots, the gated norm and the output product), the mean over the
traced window's executions, read from the programs' ``program_scopes``
tables (``_linear_attention``).  The same time also sits inside
``decode_step_ms.rest``: ``_layer_time.GROUPS`` sends the kind there.  The
part of each inner scope goes into the line's notes as
``linear_attention_decode_ms``.  Silent on a program without the scopes."""

from benchmark.metrics import _linear_attention


def read(ctx):
    timed = _linear_attention.seconds(ctx, "decode")
    if not timed or not timed[1]:
        return None
    parts = {}
    for word in _linear_attention.INNER:
        part = _linear_attention.seconds(ctx, "decode", (word,))
        if part and part[0]:
            parts[word] = round(1e3 * part[0] / part[1], 4)
    ctx.obs.setdefault("notes", {})["linear_attention_decode_ms"] = parts
    return 1e3 * timed[0] / timed[1]

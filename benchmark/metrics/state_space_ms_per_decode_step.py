"""Device milliseconds a decode execution spends in the state-space layers
(the ``recurrent`` kind: the mixers' products, convolution, selection and
state step), the mean over the traced window's executions, read from the
programs' ``program_scopes`` tables (``_state_space``).  The same time also
sits inside ``decode_step_ms.rest``: ``_layer_time.GROUPS`` sends the kind
there.  Silent on a program without the kind."""

from benchmark.metrics import _state_space


def read(ctx):
    timed = _state_space.seconds(ctx, "decode")
    if not timed or not timed[1]:
        return None
    return 1e3 * timed[0] / timed[1]

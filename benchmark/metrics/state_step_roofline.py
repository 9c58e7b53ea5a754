"""The decode step's state traffic as a share of its roofline, in percent:
bytes of recurrent state read AND written for the lanes decoded in the traced
window (``flops_jamba.state_step_bytes``: every Mamba layer's float32 ``h``
and convolution tail, once each way a decoded token) over the HBM peak, over
the device time of the operations under ``ssm_scan`` and ``ssm_conv`` inside
executions of the decode program (``_state_space``).  Memory-bound: a step
does ~9 elementwise operations a state entry against 8 bytes moved.  It
cannot pass 100%: the time is that of every lane the program steps, idle
lanes and the one step a finished row runs on included, the bytes those of
delivered tokens only.  Silent on a program without the scopes."""

from benchmark import flops_jamba
from benchmark.metrics import _served, _state_space


def read(ctx):
    timed = _state_space.seconds(ctx, "decode", ("ssm_scan", "ssm_conv"))
    _, positions = _served.processed(ctx)
    if not timed or not timed[0] or not positions:
        return None
    nbytes = flops_jamba.state_step_bytes(ctx.config, len(positions))
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / timed[0]

"""The decode step's KDA state traffic as a share of its roofline, in
percent: bytes of float32 state read AND written for the lanes decoded in
the traced window (``flops_ling.kda_step_bytes``: every KDA layer's ``S``,
once each way a decoded token) over the HBM peak, over the device time of
the operations under ``gdn_state`` inside executions of the decode program
(``_linear_attention``).  Memory-bound: the step does 7 operations a state
entry against 8 bytes moved.  It cannot pass 100%: the time is that of
every lane the program steps, idle lanes and the one step a finished row
runs on included, the bytes those of delivered tokens only.  Silent on a
program without the scope."""

from benchmark import flops_ling
from benchmark.metrics import _linear_attention, _served


def read(ctx):
    timed = _linear_attention.seconds(ctx, "decode", ("gdn_state",))
    _, positions = _served.processed(ctx)
    if not timed or not timed[0] or not positions:
        return None
    nbytes = flops_ling.kda_step_bytes(ctx.config, len(positions))
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / timed[0]

"""The paged decode kernel's share of its roofline, in percent: bytes of
the resident K and V pages of the rows decoded in the traced window
(``flops.kv_bytes_read``, whole pages, bf16 pools) over the HBM peak, over
the summed device time of the ``fused_paged_attention`` events inside
executions of the decode program.  Memory-bound.  Silent when the kernel did
not run (gather path)."""

from benchmark import flops
from benchmark.metrics import _served


def read(ctx):
    seconds = ctx.trace.op_seconds(contains="fused_paged_attention",
                                   inside="decode")
    _, positions = _served.processed(ctx)
    if not seconds or not positions:
        return None
    nbytes = flops.kv_bytes_read(ctx.config, positions,
                                 ctx.traffic["engine"]["page_size"])
    return flops.roofline_share(0.0, nbytes, seconds, ctx.peaks)["share"]

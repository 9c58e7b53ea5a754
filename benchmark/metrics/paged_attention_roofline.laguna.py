"""The paged decode kernel's share of its roofline for ``laguna-s-2.1-ep8``,
in percent: bytes of K and V the rows decoded in the traced window must read
(``flops_laguna.kv_bytes_read``: every resident page on full layers, only the
pages that cover the window on sliding layers, so pages the kernel skips are
not counted as read) over the HBM peak, over the summed device time of the
``fused_paged_attention`` events inside executions of the decode program
(both layer kinds' calls).  Memory-bound.  Silent when the kernel did not run
(gather path)."""

from benchmark import flops, flops_laguna
from benchmark.metrics import _served


def read(ctx):
    seconds = ctx.trace.op_seconds(contains="fused_paged_attention",
                                   inside="decode")
    _, positions = _served.processed(ctx)
    if not seconds or not positions:
        return None
    nbytes = flops_laguna.kv_bytes_read(ctx.config, positions,
                                        ctx.traffic["engine"]["page_size"])
    return flops.roofline_share(0.0, nbytes, seconds, ctx.peaks)["share"]

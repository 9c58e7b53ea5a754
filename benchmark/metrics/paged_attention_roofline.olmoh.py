"""The paged decode kernel's share of its roofline for ``olmo-hybrid-7b-pp4``,
in percent: bytes of K and V the rows decoded in the traced window must read
(``flops_olmo_hybrid.kv_bytes_read``: every resident page, on the two full
layers only — the six linear layers keep state slots, not pages) over the HBM
peak, over the summed device time of the ``fused_paged_attention`` events
inside executions of the decode program.  Memory-bound: 30 kv heads at a
group of one query row each.  Silent when the kernel did not run."""

from benchmark import flops, flops_olmo_hybrid
from benchmark.metrics import _served


def read(ctx):
    seconds = ctx.trace.op_seconds(contains="fused_paged_attention",
                                   inside="decode")
    _, positions = _served.processed(ctx)
    if not seconds or not positions:
        return None
    nbytes = flops_olmo_hybrid.kv_bytes_read(
        ctx.config, positions, ctx.traffic["engine"]["page_size"])
    return flops.roofline_share(0.0, nbytes, seconds, ctx.peaks)["share"]

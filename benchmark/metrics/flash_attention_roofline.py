"""The flash attention kernels' share of their roofline, in percent: the
operations and bytes of causal attention forward and backward at the cell's
shapes (``flops.flash_attention_cost``) over the summed device time of the
``flash_attention_fwd`` / ``_dq`` / ``_dkv`` events.  Compute-bound at these
shapes (``flops.roofline_share`` says which peak bounds).  Silent when no
such kernel ran (the einsum path was taken)."""

from benchmark import flops


def read(ctx):
    seconds = ctx.trace.op_seconds(contains="flash_attention")
    steps = ctx.obs.get("steps")
    if not seconds or not steps:
        return None
    cost = flops.flash_attention_cost(
        ctx.config, ctx.traffic["seq_len"],
        ctx.traffic["sequences_per_step"] * steps)
    return flops.roofline_share(cost["flops"], cost["bytes"], seconds,
                                ctx.peaks)["share"]

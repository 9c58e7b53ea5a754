"""The serving loop's share of the chip's peak, in percent: forward
operations of every prompt and output token processed in the traced window
(``flops.serve_forward_flops``; bucket padding is not work) over the window
and the bf16 peak."""

from benchmark import flops
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops.serve_forward_flops(ctx.config, prompts, positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

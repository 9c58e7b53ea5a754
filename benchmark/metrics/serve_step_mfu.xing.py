"""The serving loop's share of the chip's peak for ``xing4.0-29b-a4b-pp6``,
in percent: forward operations of every prompt and output token processed in
the traced window (``flops_xing.serve_forward_flops``: MLA projections,
expanded attention for prompts and absorbed for decoded rows, dense and
shared FFN, all four routed experts a token, the hyper-connections of every
sub-layer, the head over the whole vocabulary; bucket padding is not work)
over the window and the bf16 peak."""

from benchmark import flops_xing
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops_xing.serve_forward_flops(ctx.config, prompts, positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

"""The serving loop's share of the chip's peak for ``kimi-k2.5-ep32``, in
percent: forward operations of every prompt and output token processed in
the traced window (``flops_k2.serve_forward_flops``: MLA projections,
expanded attention for prompts and absorbed for decoded rows, dense and
shared FFN, the held experts at their expected share of the assignments,
the head over the held vocabulary; bucket padding is not work) over the
window and the bf16 peak."""

from benchmark import flops_k2
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops_k2.serve_forward_flops(ctx.config, prompts, positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

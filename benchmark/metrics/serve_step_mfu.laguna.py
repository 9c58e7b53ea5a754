"""The serving loop's share of the chip's peak for ``laguna-s-2.1-ep8``, in
percent: forward operations of every prompt and output token processed in
the traced window (``flops_laguna.serve_forward_flops``: projections at 48 /
72 heads by layer kind, attention pairs banded on sliding layers, dense and
shared FFN, the held experts at their expected share of the assignments, the
head over the held vocabulary; bucket padding is not work) over the window
and the bf16 peak."""

from benchmark import flops_laguna
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops_laguna.serve_forward_flops(ctx.config, prompts, positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

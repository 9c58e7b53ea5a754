"""The serving loop's share of the chip's peak for ``jamba2-3b``, in percent:
forward operations of every prompt and output token processed in the traced
window (``flops_jamba.serve_forward_flops``: the Mamba mixers' four products
and the recurrences' elementwise operations, counted apart there, attention's
projections and its pairs, the FFNs, the head over the whole vocabulary;
bucket padding and idle lanes are not work) over the window and the bf16
peak: the share of the whole step."""

from benchmark import flops_jamba
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops_jamba.serve_forward_flops(ctx.config, prompts, positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

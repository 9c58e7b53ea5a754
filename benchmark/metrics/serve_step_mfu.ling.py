"""The serving loop's share of the chip's peak for ``ling-3.0-flash-ep8``, in
percent: forward operations of every prompt and output token processed in
the traced window (``flops_ling.serve_forward_flops``: the KDA mixers' seven
products and the KDA rule's operations in its recurrent form, counted apart
there, the MLA layer's projections and its pairs (expanded for prompts,
absorbed for decoded rows), the dense and shared FFNs, the router, the held
experts at their expected share of the assignments, the head over the held
vocabulary; bucket padding and idle lanes are not work) over the window and
the bf16 peak: the share of the whole step."""

from benchmark import flops_ling
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops_ling.serve_forward_flops(ctx.config, prompts, positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

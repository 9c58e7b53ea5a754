"""The serving loop's share of the chip's peak for ``olmo-hybrid-7b-pp4``, in
percent: forward operations of every prompt and output token processed in
the traced window (``flops_olmo_hybrid.serve_forward_flops``: the linear
mixers' six products and the delta rule's operations in its recurrent form,
counted apart there, the full layers' projections and their pairs, the
FFNs, the head over the whole vocabulary; bucket padding and idle lanes are
not work) over the window and the bf16 peak: the share of the whole step."""

from benchmark import flops_olmo_hybrid
from benchmark.metrics import _served


def read(ctx):
    prompts, positions = _served.processed(ctx)
    if not prompts and not positions:
        return None
    work = flops_olmo_hybrid.serve_forward_flops(ctx.config, prompts,
                                                 positions)
    return 100.0 * work / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]

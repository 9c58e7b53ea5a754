"""The whole training step's share of the chip's peak, in percent:
``flops.train_flops_per_token`` (6 x matmul parameters, head included, plus
causal attention; no recompute counted) times the tokens of the steps
finished in the traced window, over the window and the bf16 peak."""

from benchmark import flops


def read(ctx):
    tokens = ctx.obs.get("tokens")
    if not tokens:
        return None
    per_token = flops.train_flops_per_token(ctx.config,
                                            ctx.traffic["seq_len"])
    rate = per_token * tokens / ctx.trace.window_s
    return 100.0 * rate / (ctx.peaks["bf16_flops_per_s"] * len(ctx.devices))

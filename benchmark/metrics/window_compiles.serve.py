"""XLA compilations inside the measured window, from ``CompileLog`` (JAX's
monitoring events; a persistent-cache hit counts too).  Should read 0."""


def read(ctx):
    return float(ctx.obs["window_compiles"])

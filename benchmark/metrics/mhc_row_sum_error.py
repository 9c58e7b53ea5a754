"""How far ``H_res`` is from doubly stochastic: the largest reading of the
gauge ``dl4j_mhc_row_sum_error`` in the window (the largest ``|sum - 1|``
over the rows and columns of every hyper-connection block's mixing matrix,
over the real rows of a step; sampled by ``jobs/serve_hyper_moe.py``).  A
guard, not a lever: ~1e-3 after 20 Sinkhorn iterations, tenths if the loop
is cut short.  Silent on a program without the gauge."""


def read(ctx):
    return ctx.obs.get("mhc_row_sum_error")

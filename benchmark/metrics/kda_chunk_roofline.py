"""The chunked KDA rule of the prefills as a share of its roofline, in
percent: the larger of its operations over the bf16 peak and its bytes over
the HBM peak (``flops_ling.chunk_flops`` / ``chunk_bytes`` of every prompt
whose first token arrived in the traced window: the chunk's decayed
products, its triangular solve and the three state products; q, k, v, the
per-channel decay, beta and the output once, the state once a chunk), over
the device time of the operations under ``gdn_chunk`` inside executions of
the ``prefill_<bucket>`` programs (``_linear_attention``).  It cannot pass
100% but for the window's edges: bucket padding is not work, and a prefill
whose first token lands after the window is timed and not counted.  Which
bound holds goes into the line's notes as ``kda_chunk_bound``.  Silent on a
program without the scope."""

from benchmark import flops_ling as f
from benchmark.metrics import _linear_attention, _served


def read(ctx):
    timed = _linear_attention.seconds(ctx, "prefill", ("gdn_chunk",))
    prompts, _ = _served.processed(ctx)
    if not timed or not timed[0] or not prompts:
        return None
    compute = sum(f.chunk_flops(ctx.config, n)
                  for n in prompts) / ctx.peaks["bf16_flops_per_s"]
    memory = sum(f.chunk_bytes(ctx.config, n)
                 for n in prompts) / ctx.peaks["hbm_bytes_per_s"]
    ctx.obs.setdefault("notes", {})["kda_chunk_bound"] = (
        "compute" if compute > memory else "memory")
    return 100.0 * max(compute, memory) / timed[0]

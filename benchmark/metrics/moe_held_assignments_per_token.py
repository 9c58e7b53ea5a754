"""Token-to-expert assignments that fell on a held expert, per real token
routed, in the window: ``dl4j_moe_held_assignments_total`` over
``dl4j_moe_tokens_total`` (both summed over the expert layers, counted on
the device, bucket padding and idle slots excluded; bracketed by
``jobs/serve_latent_moe.py``).  Uniform routing gives ``top_k * held /
n_experts`` (8 * 12 / 384 = 0.25).  Silent on a program without the
counters."""


def read(ctx):
    counts = ctx.obs.get("moe_counts")
    if counts is None or not counts[0]:
        return None
    return float(counts[1:].sum() / counts[0])

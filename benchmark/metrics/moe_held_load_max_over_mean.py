"""The busiest held expert's assignments over the mean of the held
experts', in the window (``dl4j_moe_held_assignments_total`` by expert,
bracketed by ``jobs/serve_latent_moe.py``): 1 is an even load; the grouped
matrix product's time follows the mean, a deployment's step the maximum.
Silent on a program without the counters."""


def read(ctx):
    counts = ctx.obs.get("moe_counts")
    if counts is None or not counts[1:].sum():
        return None
    return float(counts[1:].max() / counts[1:].mean())

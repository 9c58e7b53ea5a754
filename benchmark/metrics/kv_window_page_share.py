"""Bytes of window-kind KV pages in use over the bytes of all KV pages in
use, mean over the window: ``dl4j_kv_pages_in_use{kind}`` times the layers
that have a pool of the kind, sampled by ``jobs/serve_window_moe.py`` (a
ratio, 0..1).  A guard, not a lever: about 0.35 at the cell's mean context,
0.75 if sliding layers kept every position.  Silent on a program without the
gauges."""


def read(ctx):
    return ctx.obs.get("kv_window_page_share")

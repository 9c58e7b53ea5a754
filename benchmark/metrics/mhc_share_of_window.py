"""Share of the traced window the device spent in the hyper-connections, in
percent: device time of the operations whose ``op_name`` in the programs'
own compiled text lies under a scope ``mhc_coeffs``, ``mhc_sinkhorn`` or
``mhc_mix`` (``_scopes``; the map is kept at set-up by
``jobs/serve_hyper_moe.py``), over the window.  A fusion that mixes scopes
goes to its root's; the share left ambiguous that way, and every scope's
share, go into the line's ``notes``.  Silent on a program without such
scopes, and on a run whose job kept no map."""

from benchmark.metrics import _scopes


def read(ctx):
    maps = ctx.obs.get("scope_maps")
    if not maps:
        return None
    seconds = _scopes.seconds_by_scope(ctx.trace, maps)
    window = ctx.trace.window_s
    ctx.obs["notes"]["scope_share_of_window"] = {
        k: round(100.0 * v / window, 3) for k, v in sorted(seconds.items())}
    mhc = sum(v for k, v in seconds.items() if k.startswith("mhc_"))
    return 100.0 * mhc / window if mhc else None

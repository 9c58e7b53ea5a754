"""Host milliseconds of the serving loop per decode iteration: the window's
total of the engine's ``schedule`` + ``page_gather`` + ``stream_write``
phases (``engine.phases``; admission's share of them included) over the
decode steps it dispatched.  ``sample_harvest`` is the ``device_get`` and
holds the device wait, so it is left out."""


def read(ctx):
    ph = ctx.obs.get("phases")
    if not ph or not ph.get("decode_steps"):
        return None
    host = sum(ph[k] for k in ("schedule", "page_gather", "stream_write"))
    return host / ph["decode_steps"]

"""Host milliseconds of admission per prefill: the summed duration of the
``generation_decode.admit.schedule`` + ``admit.page_gather`` +
``admit.stream_write`` spans in the traced window over the number of
``admit.jitted_step`` spans (prefill dispatches) in it.  Needs only host
spans.  The dispatch and the harvest hold the device wait and are left out,
as in ``engine_host_ms_per_step``."""

from benchmark.metrics import _engine_spans


def read(ctx):
    prefills = _engine_spans.named(ctx, "admit.jitted_step")
    if not prefills:
        return None
    host = _engine_spans.named(ctx, "admit.schedule", "admit.page_gather",
                               "admit.stream_write")
    return sum(s.dur for s in host) / 1e6 / len(prefills)

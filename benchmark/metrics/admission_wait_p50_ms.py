"""Median, over the requests submitted in the window, of submit -> picked
by ``next_admittable``, in milliseconds: the ``queue_wait_ms`` that the
engine puts on each ``generation_request`` span of ``get_tracer()`` (same
``perf_counter`` clock as the window's ``t0``/``t1``).  The waiting part of
TTFT; ``prefill_ms`` on the same span is the work."""

import statistics

from deeplearning4j_tpu.observability.tracing import get_tracer


def read(ctx):
    t0, t1 = ctx.obs["t0"], ctx.obs["t1"]
    waits = [s.attrs["queue_wait_ms"] for s in get_tracer().spans()
             if s.name == "generation_request"
             and t0 <= s.start_ns / 1e9 < t1
             and s.attrs.get("queue_wait_ms") is not None]
    return statistics.median(waits) if waits else None

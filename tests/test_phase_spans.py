"""The program's spans on the profiler's clock (``observability/phases.py``
and the decode loop's use of it).

- ``PhaseTimers``: ``phases`` keeps its schema and totals with stages in
  use; a child phase is under ``stages`` and in the trace, never in
  ``phases`` or the registry; disabled timers enter nothing; under
  ``jax.profiler.start_trace`` the annotation is in the host plane under
  ``<component>[.<stage>].<phase>``.
- ``GenerationEngine``: a toy model served under a CPU trace yields every
  span of docs/observability.md's table, each child inside its parent, all
  on one thread's timeline without partial overlap; ``generation_request``
  spans split TTFT into ``queue_wait_ms`` + ``prefill_ms``.
- Program names: the jitted programs that ``benchmark/metrics`` finds by
  substring lower to modules of those names.
"""

import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.generation import GenerationEngine
from deeplearning4j_tpu.models.zoo import transformer_char_lm
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability.phases import PhaseTimers
from deeplearning4j_tpu.observability.tracing import SpanTracer, set_tracer

VOCAB = 29
FIELDS = {"count", "total_ms", "mean_ms", "min_ms", "max_ms"}
# span -> the span it must lie inside (None: top of the loop's timeline)
ENGINE_SPANS = {
    "loop.schedule": None, "loop.wait": None, "admit.schedule": None,
    "admit.page_gather": None, "admit.base_key": "admit.page_gather",
    "admit.jitted_step": None, "admit.sample_harvest": None,
    "admit.stream_write": None, "decode.jitted_step": None,
    "decode.sample_harvest": None, "decode.stream_write": None,
    "decode.deliver": "decode.stream_write",
    "decode.gauges": "decode.stream_write",
}
CHILD_ONLY = {"loop.wait", "admit.base_key", "decode.deliver",
              "decode.gauges"}


def small_lm():
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128)


def traced(tmp_dir, body):
    """Run ``body`` under a CPU profiler session; the host plane's named
    events as ``{line name: [(name, start_ns, end_ns)]}``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if not e.name.startswith("$")]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
    return lines


def in_thread(fn):
    t = threading.Thread(target=fn, name="phase-spans-worker")
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()


# ------------------------------------------------------------ PhaseTimers
def test_phases_keep_schema_and_totals_with_stages():
    reg = MetricsRegistry()
    pt = PhaseTimers("unit", registry=reg)
    for stage, n in (("admit", 2), ("decode", 3)):
        for _ in range(n):
            with pt.phase("schedule", stage=stage):
                pass
    with pt.phase("plain"):
        pass
    d = pt.as_dict()
    assert set(d) == {"steps", "phases", "stages"}
    assert set(d["phases"]) == {"schedule", "plain"}
    assert set(d["phases"]["schedule"]) == FIELDS
    assert d["phases"]["schedule"]["count"] == 5
    assert set(d["stages"]) == {"admit", "decode"}     # no stage: no entry
    assert set(d["stages"]["admit"]["schedule"]) == FIELDS
    assert d["stages"]["admit"]["schedule"]["count"] == 2
    assert d["stages"]["decode"]["schedule"]["count"] == 3
    # total_ms is rounded to a microsecond, once per entry
    parts = sum(d["stages"][s]["schedule"]["total_ms"]
                for s in ("admit", "decode"))
    assert d["phases"]["schedule"]["total_ms"] == pytest.approx(parts,
                                                                abs=0.002)
    assert pt.totals().keys() == {"schedule", "plain"}
    # the registry family has no stage: one child per phase name
    fam = reg.get("dl4j_phase_seconds")
    assert fam.get(component="unit", phase="schedule").count == 5
    assert {dict(l)["phase"] for l, _ in fam.samples()} == {"schedule", "plain"}


def test_child_is_under_stages_and_not_in_phases_or_registry():
    reg = MetricsRegistry()
    pt = PhaseTimers("unit", registry=reg)
    with pt.phase("stream_write", stage="decode"):
        with pt.phase("deliver", stage="decode", child=True):
            pass
    d = pt.as_dict()
    assert set(d["phases"]) == {"stream_write"}
    assert set(d["stages"]["decode"]) == {"stream_write", "deliver"}
    assert d["stages"]["decode"]["deliver"]["count"] == 1
    assert (d["stages"]["decode"]["deliver"]["total_ms"]
            <= d["stages"]["decode"]["stream_write"]["total_ms"])
    fam = reg.get("dl4j_phase_seconds")
    assert {dict(l)["phase"] for l, _ in fam.samples()} == {"stream_write"}
    with pytest.raises(ValueError, match="needs a stage"):
        pt.phase("orphan", child=True)


def test_registry_swap_keeps_local_totals():
    first, second = MetricsRegistry(), MetricsRegistry()
    pt = PhaseTimers("unit", registry=first)
    with pt.phase("work", stage="a"):
        pass
    pt._registry = second
    with pt.phase("work", stage="a"):
        pass
    assert pt.as_dict()["phases"]["work"]["count"] == 2
    assert pt.as_dict()["stages"]["a"]["work"]["count"] == 2
    fam = second.get("dl4j_phase_seconds")
    assert fam.get(component="unit", phase="work").count == 1


def test_annotations_are_in_the_host_plane_under_their_names(tmp_path):
    on = PhaseTimers("traced_unit", registry=MetricsRegistry())
    off = PhaseTimers("silent_unit", enabled=False)

    def work():
        with on.phase("fetch"):
            pass
        with on.phase("outer", stage="s"):
            with on.phase("inner", stage="s", child=True):
                pass
        with off.phase("fetch"):
            pass
        with off.phase("outer", stage="s"):
            pass

    lines = traced(tmp_path, lambda: in_thread(work))
    line, = [evs for evs in lines.values()
             if any(n.startswith("traced_unit.") for n, _, _ in evs)]
    spans = {n: (s, e) for n, s, e in line if n.startswith("traced_unit.")}
    assert set(spans) == {"traced_unit.fetch", "traced_unit.s.outer",
                          "traced_unit.s.inner"}
    (o0, o1), (i0, i1) = spans["traced_unit.s.outer"], spans["traced_unit.s.inner"]
    assert o0 <= i0 <= i1 <= o1
    # enabled=False enters nothing
    assert not [n for evs in lines.values() for n, _, _ in evs
                if n.startswith("silent_unit")]
    assert off.as_dict() == {"steps": 0, "phases": {}, "stages": {}}


# ----------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A toy model served for a few requests under a CPU trace: the decode
    thread's events, the engine's stats and the tracer's request spans."""
    tracer = set_tracer(SpanTracer())
    eng = GenerationEngine(small_lm(), slots=2, page_size=4, max_context=32,
                           deadline_s=60.0).start()
    rs = np.random.RandomState(3)

    def body():
        handles = [eng.submit(rs.randint(0, VOCAB, 5).tolist(), 6,
                              trace_id=f"req-{i}") for i in range(5)]
        for h in handles:
            assert len(h.result(timeout=120)) == 6
        # idle long enough for one whole loop.wait (0.05 s) to be recorded
        threading.Event().wait(0.2)

    try:
        lines = traced(tmp_path_factory.mktemp("engine_trace"), body)
        stats = eng.stats()
    finally:
        eng.stop()
        set_tracer(None)
    ours = [evs for evs in lines.values()
            if any(n.startswith("generation_decode.") for n, _, _ in evs)]
    return {"lines": ours, "stats": stats,
            "requests": [s for s in tracer.spans()
                         if s.name == "generation_request"]}


def loop_events(served):
    line, = served["lines"]        # all on the one decode thread's line
    return sorted(((n[len("generation_decode."):], s, e) for n, s, e in line
                   if n.startswith("generation_decode.")),
                  key=lambda ev: (ev[1], -ev[2]))


@pytest.mark.parametrize("span", sorted(ENGINE_SPANS))
def test_engine_span_is_in_the_trace_inside_its_parent(served, span):
    events = loop_events(served)
    mine = [(s, e) for n, s, e in events if n == span]
    assert mine, f"no generation_decode.{span} in the trace"
    parent = ENGINE_SPANS[span]
    if parent is not None:
        around = [(s, e) for n, s, e in events if n == parent]
        for s, e in mine:
            assert any(ps <= s and e <= pe for ps, pe in around), span


def test_engine_spans_nest_without_partial_overlap(served):
    events = loop_events(served)
    assert {n for n, _, _ in events} == set(ENGINE_SPANS)
    stack = []
    for name, s, e in events:
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:       # opened inside the top span: must close inside it
            assert e <= stack[-1][2], (name, stack[-1][0])
            assert ENGINE_SPANS[name] == stack[-1][0], (name, stack[-1][0])
        else:
            assert ENGINE_SPANS[name] is None, name
        stack.append((name, s, e))


def test_engine_phases_keep_five_names_and_stages_split_them(served):
    ph = served["stats"]["phases"]
    assert set(ph["phases"]) == {"schedule", "page_gather", "jitted_step",
                                 "sample_harvest", "stream_write"}
    staged = {f"{stage}.{name}" for stage, names in ph["stages"].items()
              for name in names}
    assert staged == set(ENGINE_SPANS)
    for name, top in ph["phases"].items():
        parts = [names[name] for stage, names in ph["stages"].items()
                 if name in names and f"{stage}.{name}" not in CHILD_ONLY]
        assert top["count"] == sum(p["count"] for p in parts), name
        assert top["total_ms"] == pytest.approx(
            sum(p["total_ms"] for p in parts), abs=0.001 * len(parts) + 1e-9)
    # five prefills, one base key each
    assert ph["stages"]["admit"]["jitted_step"]["count"] == 5
    assert ph["stages"]["admit"]["base_key"]["count"] == 5


def test_request_spans_split_ttft_into_wait_and_prefill(served):
    reqs = {s.attrs["trace_id"]: s.attrs for s in served["requests"]}
    assert set(reqs) == {f"req-{i}" for i in range(5)}
    for attrs in reqs.values():
        assert attrs["queue_wait_ms"] >= 0.0 and attrs["prefill_ms"] > 0.0
        assert attrs["queue_wait_ms"] + attrs["prefill_ms"] == pytest.approx(
            attrs["ttft_ms"], abs=1.0)
    # two slots, five requests at once: the later ones waited for a slot
    assert max(a["queue_wait_ms"] for a in reqs.values()) > min(
        a["prefill_ms"] for a in reqs.values())


def test_request_that_never_ran_has_no_split():
    tracer = set_tracer(SpanTracer())
    eng = GenerationEngine(small_lm(), slots=1, page_size=4, max_context=32)
    try:        # never started: the request is shed from the queue at stop
        h = eng.submit([1, 2, 3], 4, trace_id="queued")
        eng.stop(drain=False)
        assert h.done.is_set()
        span, = tracer.spans_for_trace("queued")
        assert span.attrs["ttft_ms"] is None
        assert span.attrs["queue_wait_ms"] is None
        assert span.attrs["prefill_ms"] is None
    finally:
        set_tracer(None)


# ---------------------------------------------------------- program names
def module_name(lowered):
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def test_serving_programs_lower_to_the_names_the_readers_find():
    from deeplearning4j_tpu.generation.programs import GenerationPrograms

    progs = GenerationPrograms(small_lm(), slots=2, pages_per_slot=8,
                               page_size=4, num_pages=17,
                               prefill_buckets=(8, 16))
    names = {k: module_name(v) for k, v in progs.lowered().items()}
    # a bucket's program carries its bucket, so a trace tells them apart;
    # the readers ask for modules whose name CONTAINS "prefill" / "decode"
    assert names == {"decode": "jit_decode_step",
                     "prefill_8": "jit_prefill_8",
                     "prefill_16": "jit_prefill_16"}


def test_train_step_lowers_to_jit_step():
    net = small_lm()
    x = np.zeros((2, 8), np.int32)
    y = np.zeros((2, 8, VOCAB), np.float32)
    net.fit(x, y)           # builds the updater state the step takes
    lowered = net._get_train_step().lower(
        net.params, net.updater_state, net.net_state,
        jnp.asarray(0, jnp.float32), jnp.asarray(x), jnp.asarray(y),
        jax.random.PRNGKey(0), None, None, None)
    assert module_name(lowered) == "jit_step"


def test_scopes_name_the_device_operations_by_layer():
    """``jax.named_scope`` is metadata: the scope path is in each
    operation's location, where a trace reader can select by it."""
    from deeplearning4j_tpu.generation.programs import GenerationPrograms

    net = small_lm()
    progs = GenerationPrograms(net, slots=2, pages_per_slot=8, page_size=4,
                               num_pages=17, prefill_buckets=(8,))
    text = progs.lowered()["decode"].as_text(debug_info=True)
    for scope in ("attention_core", "sample", net.layers[0].name):
        assert re.search(rf'loc\("[^"]*\b{re.escape(scope)}\b', text), scope
    x = np.zeros((2, 8), np.int32)
    y = np.zeros((2, 8, VOCAB), np.float32)
    net.fit(x, y)
    text = net._get_train_step().lower(
        net.params, net.updater_state, net.net_state,
        jnp.asarray(0, jnp.float32), jnp.asarray(x), jnp.asarray(y),
        jax.random.PRNGKey(0), None, None, None).as_text(debug_info=True)
    for scope in ("attention_core", "loss", "updater"):
        assert re.search(rf'loc\("[^"]*\b{re.escape(scope)}\b', text), scope

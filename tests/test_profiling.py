"""Performance attribution layer: StepProfiler captures (step-N trigger,
straggler trigger in a real 4-replica ParallelWrapper run, watchdog
trigger), XLA cost analysis through the RecompileDetector seam, MFU /
roofline / step-flops gauges, recompile flight events with cost deltas,
memory attribution in flight dumps, and the capture disk budget."""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.dense import DenseLayer, OutputLayer
from deeplearning4j_tpu.observability import (
    FlightRecorder, MetricsRegistry, SpanTracer, StepProfiler, StepWatchdog,
    get_registry, get_tracer, set_flight_recorder, set_registry, set_tracer,
    step_guard,
)
from deeplearning4j_tpu.observability import profiling
from deeplearning4j_tpu.observability import flightrecorder as fr_mod
from deeplearning4j_tpu.observability.flightrecorder import (
    dump_flight_report, get_flight_recorder, read_flight_report,
)
from deeplearning4j_tpu.observability.recompile import instrument

pytestmark = pytest.mark.profiling


@pytest.fixture(autouse=True)
def fresh_telemetry():
    """Isolate registry/tracer/flight recorder AND the installed profiler
    per test."""
    old_reg = get_registry()
    old_tr = get_tracer()
    reg = set_registry(MetricsRegistry())
    set_tracer(SpanTracer())
    set_flight_recorder(FlightRecorder())
    yield reg
    prof = profiling.active_profiler()
    if prof is not None:
        prof.uninstall()
    wd = fr_mod.get_watchdog()
    if wd is not None:
        wd.uninstall()
    set_registry(old_reg)
    set_tracer(old_tr)
    set_flight_recorder(FlightRecorder())


def make_net(seed=7, n_in=8):
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(seed)
         .updater("sgd", learning_rate=0.1).list()
         .layer(DenseLayer(n_in=n_in, n_out=16))
         .layer(OutputLayer(n_in=16, n_out=4)).build())).init()


def make_batches(n, n_in=8, batch=16, seed=0):
    rs = np.random.RandomState(seed)
    return [(rs.rand(batch, n_in).astype(np.float32),
             np.eye(4, dtype=np.float32)[rs.randint(0, 4, batch)])
            for _ in range(n)]


def flight_events(kind):
    return [e.to_dict() for e in get_flight_recorder().events()
            if e.kind == kind]


# ----------------------------------------------------------- cost analysis

def test_jit_cost_analysis_abstract():
    """Cost analysis lowers at the abstract signature: flops/bytes come
    back positive and no concrete buffer is needed."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: (a @ b).sum())
    x = jnp.ones((64, 32))
    y = jnp.ones((32, 16))
    cost = profiling.jit_cost_analysis(f, (x, y), {})
    assert cost["flops"] > 0
    assert cost["bytes_accessed"] > 0


def test_peak_flops_table_and_cpu_estimate():
    peak, source = profiling.peak_flops_for()
    assert peak > 0
    assert source in ("table", "cpu-estimate")
    # every table entry is a plausible positive FLOP/s
    assert all(v > 1e12 for v in profiling.PEAK_FLOPS.values())


def test_v5e_peak_flops_equal_the_benchmarks():
    """The program's MFU gauge and the benchmark's ``*_step_mfu`` divide
    by the same peak.  The library keeps its own table (it must not import
    from ``benchmark/``) and the benchmark its own file; this reads both."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "peaks.json")
    with open(path) as f:
        peaks = json.load(f)
    kind = "TPU v5 lite"
    assert profiling.PEAK_FLOPS[kind] == peaks[kind]["bf16_flops_per_s"]


def test_cost_cached_per_signature(tmp_path):
    """The detector cost-analyzes once per NEW signature; repeat calls
    reuse the cache, and every dispatch counts into the flops counter."""
    import jax
    import jax.numpy as jnp

    calls = []
    orig = profiling.jit_cost_analysis

    def counting(fn, args, kwargs):
        calls.append(1)
        return orig(fn, args, kwargs)

    profiling.jit_cost_analysis, restore = counting, orig
    try:
        with StepProfiler(str(tmp_path)):
            f = instrument(jax.jit(lambda a: (a * 2.0).sum()), "unit.cached")
            x = jnp.ones((16, 4))
            for _ in range(3):
                f(x)
        assert len(calls) == 1          # one analysis for one signature
        flops1 = get_registry().get_value("dl4j_step_flops_total",
                                          fn="unit.cached")
        assert flops1 > 0
        per_call = f.detector.last_cost["flops"]
        assert flops1 == pytest.approx(3 * per_call)
    finally:
        profiling.jit_cost_analysis = restore


# -------------------------------------------- acceptance: fit-run capture

def test_fit_capture_step_and_mfu(tmp_path):
    """Acceptance: a fit run with StepProfiler(capture_step=3) produces a
    readable trace file and populates dl4j_model_flops_utilization with a
    finite value in (0, 1]."""
    prof = StepProfiler(str(tmp_path / "prof"), capture_step=3).install()
    net = make_net()
    net.fit(make_batches(5))

    mfu = get_registry().get_value("dl4j_model_flops_utilization",
                                   component="MultiLayerNetwork")
    assert mfu is not None and np.isfinite(mfu)
    assert 0.0 < mfu <= 1.0
    flops = get_registry().get_value("dl4j_step_flops_total",
                                     fn="MultiLayerNetwork.train_step")
    assert flops > 0
    bpf = get_registry().get_value("dl4j_step_bytes_per_flop",
                                   component="MultiLayerNetwork")
    assert bpf > 0

    # exactly one capture, named in the flight recorder
    caps = flight_events("profile_capture")
    assert len(caps) == 1
    assert caps[0]["reason"] == "step:3"
    assert caps[0]["step"] == "fit_step"
    cap_dir = caps[0]["path"]
    # readable Chrome-trace file with the step's host spans
    doc = json.load(open(os.path.join(cap_dir, "host_spans.trace.json")))
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert "fit_step" in names
    meta = json.load(open(os.path.join(cap_dir, "capture.json")))
    assert meta["flops"] > 0 and 0.0 < meta["mfu"] <= 1.0
    assert prof.capture_paths == [cap_dir]
    assert get_registry().get_value("dl4j_profile_captures_total",
                                    reason="step") == 1


def test_capture_disk_budget(tmp_path):
    """Oldest capture directories are deleted once the budget is
    exceeded; the newest capture always survives."""
    prof = StepProfiler(str(tmp_path), max_disk_bytes=1,
                        use_jax_profiler=False).install()
    for i in range(3):
        prof.request_capture(f"manual:{i}")
        with step_guard("fit_step", model="Unit", iteration=i):
            pass
    survivors = sorted(d for d in os.listdir(tmp_path)
                       if d.startswith("cap-"))
    assert survivors == ["cap-0003-manual-2"]
    assert len(prof.capture_paths) == 3   # all three were written


def test_watchdog_dump_arms_capture(tmp_path):
    """Capture-on-watchdog: a watchdog dump arms the profiler, and the
    next guarded step is captured with a watchdog reason."""
    prof = StepProfiler(str(tmp_path / "prof"),
                        use_jax_profiler=False).install()
    wd = StepWatchdog(deadline_s=60.0,
                      report_dir=str(tmp_path / "diag")).install()
    wd.dump("hang", step="fit_step")
    with step_guard("fit_step", model="Unit", iteration=9):
        pass
    caps = flight_events("profile_capture")
    assert len(caps) == 1
    assert caps[0]["reason"] == "watchdog:hang"
    wd.uninstall()


# ------------------------------- acceptance: straggler-triggered capture

def test_straggler_verdict_triggers_capture(tmp_path, monkeypatch):
    """Acceptance: a straggler verdict in a 4-replica ParallelWrapper run
    triggers an automatic capture named in the flight recorder."""
    import jax

    from deeplearning4j_tpu.backend import device as backend
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper

    K = 4
    real = ParallelWrapper._worker_step_times

    def slowed(self, losses, dispatch_s):
        times = real(self, losses, dispatch_s)
        times["2"] = times["2"] + 0.05   # worker 2 is 'slow'
        return times

    monkeypatch.setattr(ParallelWrapper, "_worker_step_times", slowed)
    prof = StepProfiler(str(tmp_path), use_jax_profiler=False,
                        cost_analysis=False).install()
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    net = make_net(n_in=6)
    rs = np.random.RandomState(1)
    batches = [DataSet(rs.rand(4, 6).astype(np.float32),
                       np.eye(4, dtype=np.float32)[rs.randint(0, 4, 4)])
               for _ in range(K * 8)]
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh,
                         collect_worker_stats=True)
    pw.fit(iter(batches))

    assert "2" in pw.straggler_detector.stragglers()
    caps = flight_events("profile_capture")
    assert caps, "straggler verdict did not trigger a capture"
    assert caps[0]["reason"] == "straggler:parallel_wrapper:2"
    assert caps[0]["step"] == "parallel_window"
    assert flight_events("profile_requested")
    assert get_registry().get_value("dl4j_profile_captures_total",
                                    reason="straggler") >= 1


# --------------------------------------------- recompile cost flight event

def test_unexpected_recompile_dumps_signature_and_cost(tmp_path):
    """Satellite: an unexpected recompile leaves a flight event with the
    new abstract signature and its flops/bytes delta vs the evicted
    signature — not just a counter bump."""
    import jax
    import jax.numpy as jnp

    with StepProfiler(str(tmp_path)):
        f = instrument(jax.jit(lambda a: (a @ a.T).sum()), "unit.recomp")
        f(jnp.ones((8, 8), jnp.float32))
        f(jnp.ones((16, 8), jnp.float32))   # unexpected shape change
    evs = flight_events("recompile")
    assert len(evs) == 1
    ev = evs[0]
    assert ev["fn"] == "unit.recomp"
    assert "f32[16,8]" in ev["signature"]
    assert "f32[8,8]" in ev["evicted_signature"]
    assert ev["flops"] > ev["evicted_flops"] > 0
    assert ev["flops_delta"] == pytest.approx(
        ev["flops"] - ev["evicted_flops"])
    assert ev["bytes_delta"] > 0


def test_recompile_event_without_profiler_still_names_signature():
    """Cost analysis is profiler-gated, but the signature dump is not."""
    import jax
    import jax.numpy as jnp

    f = instrument(jax.jit(lambda a: a.sum()), "unit.nocost")
    f(jnp.ones((4,), jnp.float32))
    f(jnp.ones((6,), jnp.float32))
    evs = flight_events("recompile")
    assert len(evs) == 1
    assert "f32[6]" in evs[0]["signature"]
    assert "flops" not in evs[0]


# -------------------------------------------------- memory attribution

def test_model_memory_breakdown():
    net = make_net()
    net.fit(make_batches(1))   # materialize updater state
    br = profiling.model_memory_breakdown(net)
    assert br["params_bytes"] > 0
    assert br["total_bytes"] >= br["params_bytes"]
    assert br["top_leaves"][0]["bytes"] >= br["top_leaves"][-1]["bytes"]
    paths = {l["path"] for l in br["top_leaves"]}
    assert any("w" in p or "W" in p for p in paths)


def test_live_buffer_snapshot():
    import jax.numpy as jnp

    keep = jnp.ones((128, 128))   # noqa: F841 — held live on purpose
    snap = profiling.live_buffer_snapshot()
    assert snap["total_bytes"] >= keep.nbytes
    assert snap["count"] >= 1
    assert snap["top"][0]["bytes"] > 0


def test_flight_dump_contains_memory_attribution(tmp_path):
    """Watchdog/crash dumps show WHAT held memory: live buffers plus the
    tracked model's per-leaf breakdown."""
    prof = StepProfiler(str(tmp_path / "prof"),
                        use_jax_profiler=False).install()
    net = make_net()
    net.fit(make_batches(2))
    path = str(tmp_path / "report.jsonl")
    dump_flight_report(path, "unit-test")
    records = read_flight_report(path)
    mem = [r for r in records if r["record"] == "memory_attribution"]
    assert len(mem) == 1
    assert mem[0]["live_buffers"]["total_bytes"] > 0
    assert mem[0]["models"]["MultiLayerNetwork"]["params_bytes"] > 0


def test_step_peak_memory_gauge_or_graceful(tmp_path):
    """On PJRT backends the per-step peak gauge fills; on CPU (no memory
    stats) it simply never appears — either way the step must not fail."""
    with StepProfiler(str(tmp_path)):
        net = make_net()
        net.fit(make_batches(2))
    fam = get_registry().get("dl4j_step_peak_memory_bytes")
    from deeplearning4j_tpu.observability.memory import device_memory_stats

    if device_memory_stats():
        assert fam is not None and fam.samples()
    # registered lazily only when stats exist; absence is the CPU case


# ------------------------------------------------------ chrome trace export

def test_chrome_trace_export_roundtrip(tmp_path):
    tracer = get_tracer()
    with tracer.span("outer", trace_id="t1"):
        with tracer.span("inner"):
            pass
    path = str(tmp_path / "trace.json")
    n = tracer.export_chrome_trace(path)
    assert n == 2
    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {"outer", "inner"}
    outer = next(e for e in evs if e["name"] == "outer")
    assert outer["args"]["trace_id"] == "t1"
    assert outer["dur"] >= 0
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "thread_name"

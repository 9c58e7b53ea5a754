"""Performance attribution: step profiling, XLA cost analysis, MFU gauges,
and memory attribution.

PR-1 told us *that* a step happened (``dl4j_fit_step_seconds``); PR-3 told
us *that* a worker was slow (straggler verdicts).  Neither says where the
time and bytes went.  This module closes that gap with the modern
equivalent of the reference's per-op ``StatsListener`` timing — measured at
the compiler seam instead of per layer (the cuDNN helper-seam argument:
measure the primitive, not just the loop):

- **XLA cost analysis** (``Compiled.cost_analysis()``): flops and bytes
  accessed per compiled signature, harvested once per compile through the
  PR-1 ``RecompileDetector`` seam (``recompile._InstrumentedJit``) so every
  fit loop, every parallel master, and the pipeline master report FLOPs
  without touching their hot loops.
- **MFU / roofline gauges**: ``dl4j_step_flops_total{fn=}``,
  ``dl4j_model_flops_utilization{component=}`` (step FLOP/s over the
  backend's peak — the per-backend table below; the CPU peak is a
  documented order-of-magnitude ESTIMATE, and MFU is clamped to 1.0 so an
  underestimated peak can never report an impossible > 1 utilization),
  and ``dl4j_step_bytes_per_flop{component=}`` (XLA bytes-accessed /
  flops: a roofline position — high means memory-bound).
- **On-demand / trigger-driven trace capture** (``StepProfiler``): capture
  step N, capture the next step after a straggler verdict (PR-3 detector)
  or a watchdog hang dump, or ``request_capture()`` manually.  Each
  capture wraps the step in ``jax.profiler`` (TensorBoard XPlane + the
  gzipped Chrome trace the plugin writes) AND exports the host-side span
  window as a plain Chrome-trace JSON (``host_spans.trace.json`` —
  loadable in ``chrome://tracing`` / Perfetto with no TensorBoard), under
  a bounded on-disk budget (oldest capture directories deleted first).
- **Memory attribution**: per-leaf param/updater/net-state byte breakdown
  of tracked models, live-buffer snapshots grouped by shape/dtype, and a
  per-step peak-allocation gauge — all surfaced in flight-recorder dumps
  so a watchdog/crash report shows *what held memory*.

Cost note: cost analysis lowers+compiles the step once more per NEW
signature (``jit.lower().compile()`` does not share the dispatch cache).
Steady-state training has a closed signature set, so this is a one-off
per-shape cost paid only while a profiler is installed.

Hot-loop cost while installed: one dict write per dispatch
(``note_dispatch``) and a few gauge stores per step; nothing here ever
forces a device->host sync.
"""

from __future__ import annotations

import itertools
import json
import os
import shutil
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

_FLOPS = "dl4j_step_flops_total"
_MFU = "dl4j_model_flops_utilization"
_BPF = "dl4j_step_bytes_per_flop"
_PEAK = "dl4j_backend_peak_flops"
_CAPTURES = "dl4j_profile_captures_total"
_STEP_PEAK_MEM = "dl4j_step_peak_memory_bytes"

# peak dense matmul throughput per chip, bf16 FLOP/s — the program's own
# table behind the MFU gauges (tests/test_profiling.py pins its v5e row
# to the benchmark's peaks.json).  Keyed by the EXACT
# ``device_kind`` string the runtime reports, so a chip nobody measured
# against can never inherit a neighbour's number.
PEAK_FLOPS = {
    # TPU v5e; reported by jax 0.9.0 / libtpu 0.0.34 on the chip machine.
    # 197 TFLOP/s: Google Cloud documentation, "TPU v5e"
    "TPU v5 lite": 197e12,
}

# ESTIMATE: one modern server socket sustains O(100) GFLOP/s fp32 through
# a single-threaded-ish XLA:CPU step.  Only order-of-magnitude accurate —
# every consumer labels CPU-derived MFU as an estimate, and MFU is
# clamped to 1.0 (docs/observability.md "MFU definition").
CPU_PEAK_FLOPS_ESTIMATE = 1e11


def peak_flops_for(device=None) -> Tuple[float, str]:
    """(peak FLOP/s, source) for a jax device (default: devices()[0]).
    source: ``"table"`` (spec-sheet number for that exact ``device_kind``)
    or ``"cpu-estimate"`` (documented estimate, see
    ``CPU_PEAK_FLOPS_ESTIMATE``).  An accelerator that is not in the
    table raises: add its ``device_kind`` and published peak, with the
    source, before computing utilization on it."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device.device_kind
    if kind in PEAK_FLOPS:
        return PEAK_FLOPS[kind], "table"
    if device.platform == "cpu":
        return CPU_PEAK_FLOPS_ESTIMATE, "cpu-estimate"
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {kind!r} (platform "
        f"{device.platform!r}); known: {sorted(PEAK_FLOPS)}")


# ------------------------------------------------------------ cost analysis
def jit_cost_analysis(fn, args: Tuple, kwargs: Dict) -> Dict[str, float]:
    """XLA cost analysis of ``fn`` (a jitted callable) at the ABSTRACT
    signature of ``args``/``kwargs``: every array leaf is replaced by a
    ``ShapeDtypeStruct`` before lowering (input shardings preserved), so
    the concrete buffers are never touched (safe with donated args) and
    nothing executes.  Returns ``{"flops": ..., "bytes_accessed": ...}``
    or ``{}`` when the backend does not support cost analysis.  Thin
    wrapper over ``shardstats.program_analysis`` — the ONE owner of the
    abstract-lowering recipe."""
    from deeplearning4j_tpu.observability import shardstats

    out = shardstats.program_analysis(fn, args, kwargs, memory=False,
                                      collectives=False)
    if "flops" not in out and "bytes_accessed" not in out:
        return {}
    return {"flops": out.get("flops", 0.0),
            "bytes_accessed": out.get("bytes_accessed", 0.0)}


# -------------------------------------------------------- memory attribution
def _leaf_bytes(leaf) -> int:
    n = getattr(leaf, "nbytes", None)
    if n is not None:
        return int(n)
    return 0


def model_memory_breakdown(net, top: int = 16) -> Dict[str, Any]:
    """Per-leaf byte breakdown of a model facade's params / updater state /
    net state — the "what holds the HBM" answer for a parked model.
    Returns section totals plus the ``top`` largest leaves with their
    tree paths."""
    import jax

    sections = {
        "params": getattr(net, "params", None),
        "updater_state": getattr(net, "updater_state", None),
        "net_state": getattr(net, "net_state", None),
    }
    totals: Dict[str, int] = {}
    leaves: List[Dict[str, Any]] = []
    for section, tree in sections.items():
        total = 0
        if tree:
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                b = _leaf_bytes(leaf)
                total += b
                leaves.append({
                    "section": section,
                    "path": jax.tree_util.keystr(path),
                    "bytes": b,
                    "shape": list(getattr(leaf, "shape", ()) or ()),
                    "dtype": str(getattr(leaf, "dtype", "")),
                })
        totals[f"{section}_bytes"] = total
    leaves.sort(key=lambda d: d["bytes"], reverse=True)
    return {
        **totals,
        "total_bytes": sum(totals.values()),
        "top_leaves": leaves[:top],
    }


def live_buffer_snapshot(top: int = 20) -> Dict[str, Any]:
    """All live jax arrays in the process, grouped by (shape, dtype) and
    sorted by total bytes — the "what is holding memory RIGHT NOW" view a
    crash/hang dump needs.  Cost is O(live arrays); called at capture and
    dump time, never per step."""
    import jax

    groups: Dict[Tuple, List[int]] = {}
    total = 0
    count = 0
    try:
        arrs = jax.live_arrays()
    except Exception:
        return {"total_bytes": 0, "count": 0, "top": [], "error": "unavailable"}
    for a in arrs:
        b = _leaf_bytes(a)
        total += b
        count += 1
        key = (tuple(getattr(a, "shape", ()) or ()),
               str(getattr(a, "dtype", "")))
        g = groups.setdefault(key, [0, 0])
        g[0] += 1
        g[1] += b
    ranked = sorted(groups.items(), key=lambda kv: kv[1][1], reverse=True)
    return {
        "total_bytes": total,
        "count": count,
        "top": [{"shape": list(shape), "dtype": dtype, "count": n,
                 "bytes": b} for (shape, dtype), (n, b) in ranked[:top]],
    }


def peak_memory_snapshot() -> Dict[str, Any]:
    """Per-device peak allocation (PJRT ``peak_bytes_in_use``); on backends
    without memory stats (CPU) falls back to the live-buffer total, labeled
    as the estimate it is."""
    from deeplearning4j_tpu.observability.memory import device_memory_stats

    stats = device_memory_stats()
    if stats:
        return {"source": "pjrt", "devices": stats,
                "peak_bytes": max((per.get("peak_bytes_in_use") or 0)
                                  for per in stats.values())}
    live = live_buffer_snapshot(top=0)
    return {"source": "live_buffers_estimate",
            "peak_bytes": live["total_bytes"]}


def memory_attribution() -> Dict[str, Any]:
    """The flight-dump memory section: live buffers plus the per-leaf
    breakdown of every model the active profiler tracks."""
    out: Dict[str, Any] = {"live_buffers": live_buffer_snapshot()}
    prof = _active
    if prof is not None:
        models = {}
        for kind, net in prof.tracked_models():
            try:
                models[kind] = model_memory_breakdown(net)
            except Exception as e:
                models[kind] = {"error": repr(e)}
        out["models"] = models
    return out


# --------------------------------------------------------------- profiler
class StepProfiler:
    """On-demand and trigger-driven step capture + MFU attribution.

    Usage::

        prof = StepProfiler("profiles", capture_step=3).install()
        net.fit(batches)          # step 3 is captured; MFU gauges filled
        prof.uninstall()

    or as a context manager (``with StepProfiler(...) as prof:``).

    Capture triggers (each capture is one step wrapped in
    ``jax.profiler.start_trace``/``stop_trace`` + a host-span Chrome-trace
    export, named in a ``profile_capture`` flight event):

    - ``capture_step=N`` / ``capture_steps=(...)``: the step whose
      ``step_guard`` ``iteration`` attr matches;
    - straggler verdict (``capture_on_straggler``): the PR-3
      ``StragglerDetector`` arms a one-shot capture of the next step;
    - watchdog dump (``capture_on_watchdog``): a hang report arms a
      capture of the next step that runs (the hung step itself never
      finishes — the next one shows what the recovered loop does);
    - ``request_capture(reason)``: manual one-shot.

    Disk budget: capture directories under ``profile_dir`` are deleted
    oldest-first once their total size exceeds ``max_disk_bytes`` (the
    newest capture is always kept).

    While installed, every ``instrument``-wrapped jitted function reports
    its per-signature XLA cost analysis through ``note_dispatch`` and the
    ``step_guard`` seam turns that into per-step MFU/roofline gauges —
    see the module docstring for the metric families.
    """

    def __init__(self, profile_dir: str = "profiles", *,
                 capture_step: Optional[int] = None,
                 capture_steps: Tuple[int, ...] = (),
                 capture_on_straggler: bool = True,
                 capture_on_watchdog: bool = True,
                 max_disk_bytes: int = 256 << 20,
                 use_jax_profiler: bool = True,
                 cost_analysis: bool = True,
                 peak_flops: Optional[float] = None,
                 registry=None):
        from deeplearning4j_tpu.observability.metrics import get_registry

        self.profile_dir = str(profile_dir)
        self.capture_step = capture_step
        self.capture_steps = tuple(capture_steps)
        self.capture_on_straggler = capture_on_straggler
        self.capture_on_watchdog = capture_on_watchdog
        self.max_disk_bytes = int(max_disk_bytes)
        self.use_jax_profiler = use_jax_profiler
        self.cost_analysis = cost_analysis
        if peak_flops is not None:
            self.peak_flops, self.peak_source = float(peak_flops), "override"
        else:
            self.peak_flops, self.peak_source = peak_flops_for()
        reg = registry if registry is not None else get_registry()
        self._registry = reg
        self._m_flops = reg.counter(
            _FLOPS, "FLOPs dispatched per jitted function (XLA cost "
            "analysis of the compiled signature, counted once per call)",
            labels=("fn",))
        self._m_mfu = reg.gauge(
            _MFU, "Model FLOPs utilization of the most recent step: step "
            "FLOPs / step seconds / backend peak FLOP/s (clamped to 1.0; "
            "CPU peak is a documented estimate)", labels=("component",))
        self._m_bpf = reg.gauge(
            _BPF, "Roofline position of the most recent step: XLA "
            "bytes-accessed / flops (high = memory-bound)",
            labels=("component",))
        self._m_peak = reg.gauge(
            _PEAK, "Peak FLOP/s assumed for MFU (spec-sheet table for "
            "TPUs; on CPU a documented order-of-magnitude estimate)",
            labels=("source",))
        self._m_caps = reg.counter(
            _CAPTURES, "Profiler trace captures written, by trigger",
            labels=("reason",))
        self._m_peak_mem = reg.gauge(
            _STEP_PEAK_MEM, "Peak device allocation observed at the end "
            "of the most recent step (PJRT peak_bytes_in_use; absent on "
            "backends without memory stats)", labels=("component", "device"))
        self._lock = threading.Lock()
        self._pending: Optional[str] = None
        self._tls = threading.local()
        self._cap_ids = itertools.count(1)
        self._models: "weakref.WeakValueDictionary[str, Any]" = (
            weakref.WeakValueDictionary())
        self.capture_paths: List[str] = []

    # ------------------------------------------------------------ lifecycle
    def install(self) -> "StepProfiler":
        global _active
        self._m_peak.set(self.peak_flops, source=self.peak_source)
        _active = self
        return self

    def uninstall(self) -> None:
        global _active
        if _active is self:
            _active = None

    def __enter__(self) -> "StepProfiler":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # ------------------------------------------------------------- triggers
    def request_capture(self, reason: str) -> None:
        """Arm a one-shot capture of the NEXT guarded step (thread-safe;
        a second request while one is pending is coalesced)."""
        from deeplearning4j_tpu.observability.flightrecorder import (
            get_flight_recorder,
        )

        with self._lock:
            if self._pending is not None:
                return
            self._pending = str(reason)
        get_flight_recorder().record("profile_requested", reason=reason)

    # -------------------------------------------------------- model tracking
    def track_model(self, net, kind: str) -> None:
        """Weakly register a model facade for memory attribution (fit
        loops call this; a dropped model unregisters itself)."""
        self._models[str(kind)] = net

    def tracked_models(self) -> List[Tuple[str, Any]]:
        return [(k, v) for k, v in self._models.items() if v is not None]

    # ------------------------------------------------------- step_guard seam
    def on_step_begin(self, name: str, attrs: Dict[str, Any]) -> Dict:
        """Called by ``step_guard`` on entry; returns the per-step frame
        that accumulates this step's dispatched cost."""
        reason = None
        with self._lock:
            if self._pending is not None:
                reason, self._pending = self._pending, None
        if reason is None:
            it = attrs.get("iteration")
            if it is not None and (it == self.capture_step
                                   or it in self.capture_steps):
                reason = f"step:{it}"
        frame = {"flops": 0.0, "bytes": 0.0, "capture": None}
        if reason is not None:
            try:
                frame["capture"] = self._begin_capture(name, attrs, reason)
            except Exception:
                frame["capture"] = None
        stack = getattr(self._tls, "frames", None)
        if stack is None:
            stack = self._tls.frames = []
        stack.append(frame)
        return frame

    def note_dispatch(self, fn_name: str, cost: Optional[Dict]) -> None:
        """Called by ``_InstrumentedJit`` per call with the dispatched
        signature's cached cost analysis; accumulates into the innermost
        active step frame on this thread."""
        if not cost:
            return
        flops = float(cost.get("flops") or 0.0)
        nbytes = float(cost.get("bytes_accessed") or 0.0)
        if flops > 0:
            self._m_flops.inc(flops, fn=fn_name)
        stack = getattr(self._tls, "frames", None)
        if stack:
            stack[-1]["flops"] += flops
            stack[-1]["bytes"] += nbytes

    def on_step_end(self, name: str, seconds: float, attrs: Dict[str, Any],
                    frame: Dict, error: Optional[BaseException] = None) -> None:
        stack = getattr(self._tls, "frames", None)
        if stack:
            # remove by IDENTITY: nested frames with equal contents (all
            # zeros before any dispatch) must not evict each other
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] is frame:
                    del stack[i]
                    break
        component = (attrs.get("model") or attrs.get("component") or name)
        flops, nbytes = frame["flops"], frame["bytes"]
        mfu = None
        if flops > 0 and seconds > 0:
            if self.peak_flops > 0:
                mfu = min(1.0, flops / seconds / self.peak_flops)
                self._m_mfu.set(mfu, component=component)
            self._m_bpf.set(nbytes / flops, component=component)
        self._sample_step_memory(component)
        cap = frame.get("capture")
        if cap is not None:
            self._finish_capture(cap, name, seconds, attrs, flops, nbytes,
                                 mfu, error)

    def _sample_step_memory(self, component: str) -> None:
        from deeplearning4j_tpu.observability.memory import (
            device_memory_stats,
        )

        try:
            for dev, per in device_memory_stats().items():
                peak = per.get("peak_bytes_in_use")
                if peak is not None:
                    self._m_peak_mem.set(peak, component=component,
                                         device=dev)
        except Exception:
            pass

    # --------------------------------------------------------------- capture
    def _begin_capture(self, name: str, attrs: Dict, reason: str) -> Dict:
        safe = "".join(c if (c.isalnum() or c in "._-") else "-"
                       for c in reason)[:48]
        cap_dir = os.path.join(self.profile_dir,
                               f"cap-{next(self._cap_ids):04d}-{safe}")
        os.makedirs(cap_dir, exist_ok=True)
        cap = {"reason": reason, "dir": cap_dir, "jax_started": False,
               "t0_ns": time.perf_counter_ns()}
        if self.use_jax_profiler:
            try:
                import jax

                jax.profiler.start_trace(cap_dir)
                cap["jax_started"] = True
            except Exception:
                cap["jax_started"] = False
        return cap

    def _finish_capture(self, cap: Dict, name: str, seconds: float,
                        attrs: Dict, flops: float, nbytes: float,
                        mfu: Optional[float],
                        error: Optional[BaseException]) -> None:
        from deeplearning4j_tpu.observability.flightrecorder import (
            get_flight_recorder,
        )
        from deeplearning4j_tpu.observability.tracing import get_tracer

        if cap["jax_started"]:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
        t1_ns = time.perf_counter_ns()
        tracer = get_tracer()
        span_path = os.path.join(cap["dir"], "host_spans.trace.json")
        spans = 0
        try:
            spans = tracer.export_chrome_trace(
                span_path, tracer.spans_between(cap["t0_ns"], t1_ns))
        except Exception:
            span_path = None
        meta = {
            "reason": cap["reason"],
            "step": name,
            "attrs": {k: v for k, v in attrs.items()
                      if isinstance(v, (str, int, float, bool, type(None)))},
            "seconds": seconds,
            "flops": flops,
            "bytes_accessed": nbytes,
            "mfu": mfu,
            "peak_flops": self.peak_flops,
            "peak_flops_source": self.peak_source,
            "host_spans": spans,
            "error": repr(error) if error is not None else None,
            "memory": None,
        }
        try:
            meta["memory"] = {**peak_memory_snapshot(),
                              "live_buffers": live_buffer_snapshot()}
        except Exception:
            pass
        try:
            with open(os.path.join(cap["dir"], "capture.json"), "w") as f:
                json.dump(meta, f, indent=1, default=str)
        except OSError:
            pass
        category = cap["reason"].split(":", 1)[0]
        self._m_caps.inc(reason=category)
        self.capture_paths.append(cap["dir"])
        get_flight_recorder().record(
            "profile_capture", reason=cap["reason"], step=name,
            path=cap["dir"], trace_file=span_path,
            seconds=round(seconds, 6), flops=flops,
            mfu=None if mfu is None else round(mfu, 6))
        self._enforce_budget()

    def _enforce_budget(self) -> None:
        """Delete oldest capture directories once the on-disk total
        exceeds ``max_disk_bytes`` (newest always kept)."""
        try:
            caps = []
            for entry in os.listdir(self.profile_dir):
                path = os.path.join(self.profile_dir, entry)
                if not (entry.startswith("cap-") and os.path.isdir(path)):
                    continue
                size = 0
                for root, _dirs, files in os.walk(path):
                    for fl in files:
                        try:
                            size += os.path.getsize(os.path.join(root, fl))
                        except OSError:
                            pass
                caps.append((os.path.getmtime(path), path, size))
            caps.sort()   # oldest first
            total = sum(s for _, _, s in caps)
            while total > self.max_disk_bytes and len(caps) > 1:
                _, path, size = caps.pop(0)
                shutil.rmtree(path, ignore_errors=True)
                total -= size
        except OSError:
            pass


# ------------------------------------------------------------ module seams
_active: Optional[StepProfiler] = None


def active_profiler() -> Optional[StepProfiler]:
    """The installed profiler, or None (lock-free read: module-global
    assignment is atomic)."""
    return _active


def notify_straggler(component: str, worker: str) -> None:
    """Straggler-verdict hook (called by ``health.StragglerDetector``):
    arms a one-shot capture of the next step so the trace shows what the
    degraded window actually did."""
    prof = _active
    if prof is not None and prof.capture_on_straggler:
        prof.request_capture(f"straggler:{component}:{worker}")


def notify_watchdog(reason: str) -> None:
    """Watchdog-dump hook (called by ``flightrecorder.StepWatchdog``)."""
    prof = _active
    if prof is not None and prof.capture_on_watchdog:
        prof.request_capture(f"watchdog:{reason}")

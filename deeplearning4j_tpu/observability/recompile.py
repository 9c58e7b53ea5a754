"""Recompile detection for jitted step functions.

On TPU the silent throughput killer is XLA retracing/recompilation from
shape churn — a ragged final batch, a TBPTT tail window, a mask appearing
mid-run — each costing seconds of compile against a millisecond step.
Nothing in the reference detects this (it has no compiler in the loop).

``instrument(jax.jit(step), "name")`` wraps the jitted callable: every call
fingerprints the *abstract* signature of the inputs (pytree structure +
shape/dtype/sharding per leaf — the things jit keys its cache on), counts
distinct signatures as compiles in the metrics registry, and logs ONE
warning per *new* signature after the first with the old→new delta, e.g.::

    recompile #2 of MultiLayerNetwork.train_step: args[4]:
    f32[128,784] -> f32[96,784]

The fingerprint is a few microseconds of host work per call (tuple of
shape/dtype ids per leaf); the paths needed for a readable delta are only
computed on a miss.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from deeplearning4j_tpu.observability import profiling, shardstats

logger = logging.getLogger("deeplearning4j_tpu.observability")

_COMPILES = "dl4j_compiles_total"
_RECOMPILES = "dl4j_recompiles_total"


def _leaf_sig(leaf: Any) -> Tuple:
    """Abstract signature of one pytree leaf: what jit keys its cache on.
    The sharding is kept as the OBJECT (hashable, cheap) — stringifying it
    per call was the dominant fingerprint cost on large pytrees."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        # non-array static-ish leaf (python scalar, string…): jit treats
        # python numbers as weak-typed 0-d arrays; keep the type
        return (type(leaf).__name__,)
    dtype = getattr(leaf, "dtype", None)
    return (tuple(shape), str(dtype), getattr(leaf, "sharding", None))


def _fmt_leaf_sig(sig: Tuple) -> str:
    if len(sig) == 1:
        return sig[0]
    shape, dtype, sharding = sig
    short = {"float32": "f32", "float64": "f64", "bfloat16": "bf16",
             "int32": "i32", "int64": "i64", "bool": "b1",
             "uint32": "u32"}.get(dtype, dtype)
    s = f"{short}[{','.join(str(d) for d in shape)}]"
    sh = "" if sharding is None else repr(sharding)
    if sh and "SingleDevice" not in sh:
        s += f"@{sh}"
    return s


def fingerprint(args: Tuple, kwargs: Dict) -> Tuple:
    """Hashable abstract signature of a call's inputs."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


def _fmt_signature(sig: Tuple, max_leaves: int = 12) -> str:
    """Readable one-line form of a fingerprint's leaf signatures
    (``f32[128,784], f32[128,10], …``) for flight-recorder records."""
    _treedef, leaves = sig
    parts = [_fmt_leaf_sig(s) for s in leaves[:max_leaves]]
    if len(leaves) > max_leaves:
        parts.append(f"… {len(leaves) - max_leaves} more")
    return ", ".join(parts)


def _leaf_paths(args: Tuple, kwargs: Dict) -> List[str]:
    """Human-readable path per leaf, same order as ``fingerprint``."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    out = []
    for path, _ in flat:
        label = jax.tree_util.keystr(path)
        # keystr renders "(0,)[4]['w']" style; trim the (args, kwargs) root
        if label.startswith("[0]"):
            label = "args" + label[3:]
        elif label.startswith("[1]"):
            label = "kwargs" + label[3:]
        out.append(label)
    return out


class RecompileDetector:
    """Tracks abstract input signatures of one jitted function."""

    def __init__(self, name: str, registry=None,
                 warn: Optional[Callable[[str], None]] = None):
        from deeplearning4j_tpu.observability.metrics import get_registry

        self.name = name
        self.warn = warn or logger.warning
        self._lock = threading.Lock()
        self._seen: Dict[Tuple, int] = {}   # signature -> compile ordinal
        self._last: Optional[Tuple] = None
        self.compile_count = 0
        self.recompile_count = 0  # new signatures after the first
        # signature -> XLA cost analysis (filled when a profiler is
        # installed; see check(cost_fn=)).  last_cost is the CURRENT
        # signature's entry — _InstrumentedJit reads it right after
        # check() to attribute the dispatch's FLOPs to the step.
        self._cost_by_sig: Dict[Tuple, Dict] = {}
        self.last_cost: Optional[Dict] = None
        reg = registry if registry is not None else get_registry()
        self._m_compiles = compile_counter(name, reg)
        self._m_recompiles = reg.counter(
            _RECOMPILES, "Signature changes after the first compile "
            "(shape/dtype/sharding churn)", labels=("fn",)
        ).labels(fn=name)

    def check(self, args: Any, kwargs: Dict, expected: bool = False,
              cost_fn: Optional[Callable[[], Dict]] = None) -> bool:
        """Record this call's signature (``args`` is any pytree — a tuple
        of positional args, or a position-keyed dict when the wrapper
        subsets by ``argnums``); returns True when it is new (i.e. this
        call compiles).  ``expected=True`` marks a PLANNED compile (e.g.
        serving AOT warmup sweeping its bucket shapes): it still counts
        in ``dl4j_compiles_total`` but does not warn or count as a
        recompile — those alert only on unplanned signature churn.

        ``cost_fn`` (profiler seam): called once per NEW signature to
        fetch its XLA cost analysis; the result is cached per signature,
        exposed as ``last_cost`` for every later call with that
        signature, and an UNEXPECTED recompile dumps the new abstract
        signature with its flops/bytes delta vs the evicted one into the
        flight recorder — not just a counter bump."""
        sig = fingerprint(args, kwargs)
        with self._lock:
            known = sig in self._seen
            if not known:
                self.compile_count += 1
                self._seen[sig] = self.compile_count
                self._m_compiles.inc()
            prev, self._last = self._last, sig
            if known:
                self.last_cost = self._cost_by_sig.get(sig)
                return False
        # cost analysis OUTSIDE the lock: it lowers + compiles
        cost: Optional[Dict] = None
        if cost_fn is not None:
            try:
                cost = cost_fn() or {}
            except Exception:
                cost = {}
            with self._lock:
                self._cost_by_sig[sig] = cost
        # dl4jlint: disable-next-line=lock-discipline -- GIL-atomic reference publish; readers are monitoring-grade and tolerate the brief pre-cost window
        self.last_cost = cost
        # compiles land in the flight record too: "what happened right
        # before the hang" is usually a compile or a shape change
        from deeplearning4j_tpu.observability.flightrecorder import (
            get_flight_recorder,
        )

        get_flight_recorder().record(
            # dl4jlint: disable-next-line=lock-discipline -- reads back the ordinal this same call just assigned under the lock; a concurrent compile only skews the label
            "compile", fn=self.name, ordinal=self.compile_count,
            expected=bool(expected))
        if prev is not None and not expected:
            self.recompile_count += 1
            self._m_recompiles.inc()
            msg = self._delta_message(prev, sig, args, kwargs)
            self.warn(msg)
            self._record_recompile_event(prev, sig, cost)
        return True

    def _record_recompile_event(self, prev: Tuple, new: Tuple,
                                cost: Optional[Dict]) -> None:
        """The satellite-grade recompile record: new abstract signature +
        cost-analysis summary (flops/bytes delta vs the evicted
        signature) into the flight recorder.  Cost fields appear when a
        profiler had analysis enabled for both signatures."""
        from deeplearning4j_tpu.observability.flightrecorder import (
            get_flight_recorder,
        )

        ev: Dict[str, Any] = {
            # dl4jlint: disable-next-line=lock-discipline -- flight-record label read; exactness not load-bearing
            "fn": self.name, "ordinal": self.compile_count,
            "signature": _fmt_signature(new),
            "evicted_signature": _fmt_signature(prev),
        }
        with self._lock:
            prev_cost = self._cost_by_sig.get(prev)
        if cost:
            ev["flops"] = cost.get("flops")
            ev["bytes_accessed"] = cost.get("bytes_accessed")
        if prev_cost:
            ev["evicted_flops"] = prev_cost.get("flops")
            ev["evicted_bytes_accessed"] = prev_cost.get("bytes_accessed")
        if cost and prev_cost:
            ev["flops_delta"] = ((cost.get("flops") or 0.0)
                                 - (prev_cost.get("flops") or 0.0))
            ev["bytes_delta"] = ((cost.get("bytes_accessed") or 0.0)
                                 - (prev_cost.get("bytes_accessed") or 0.0))
        get_flight_recorder().record("recompile", **ev)

    def _delta_message(self, old: Tuple, new: Tuple, args, kwargs) -> str:
        old_def, old_leaves = old
        new_def, new_leaves = new
        parts: List[str] = []
        if old_def != new_def:
            parts.append("pytree structure changed")
        if len(old_leaves) == len(new_leaves):
            try:
                paths = _leaf_paths(args, kwargs)
            except Exception:
                paths = [f"leaf[{i}]" for i in range(len(new_leaves))]
            for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
                if o != n:
                    parts.append(f"{paths[i]}: {_fmt_leaf_sig(o)} -> "
                                 f"{_fmt_leaf_sig(n)}")
        else:  # e.g. a mask appearing mid-run (None -> array)
            parts.append(f"leaf count {len(old_leaves)} -> "
                         f"{len(new_leaves)}")
        delta = "; ".join(parts[:8]) or "signature changed"
        if len(parts) > 8:
            delta += f"; … {len(parts) - 8} more"
        # dl4jlint: disable-next-line=lock-discipline -- warning-text label read; exactness not load-bearing
        return (f"recompile #{self.compile_count} of {self.name}: {delta} "
                f"(each new signature costs an XLA compilation; pad/bucket "
                f"inputs to stable shapes to avoid this)")


class _InstrumentedJit:
    """Transparent wrapper: ``__call__`` runs the detector then the jitted
    fn; everything else (``lower``, ``trace``, ``clear_cache``…) delegates,
    so AOT-compile workflows keep working on the wrapped object.

    ``argnums`` restricts the fingerprint to those positional args — the
    fit loops pass only the DATA argument positions (batch, labels, masks,
    carries), because the params/optimizer-state pytrees cannot change
    abstract shape between steps (each step's inputs are the previous
    step's outputs) and fingerprinting hundreds of param leaves every
    iteration is measurable host overhead.

    Profiler seam: while a ``StepProfiler`` with cost analysis is
    installed, each NEW signature is cost-analyzed (abstract lowering of
    the FULL argument list — safe with donation, nothing executes) and
    every call reports its signature's cached flops/bytes to the profiler
    (``note_dispatch``), which rolls them into the step's MFU/roofline
    gauges at the ``step_guard`` boundary."""

    __slots__ = ("_fn", "detector", "_argnums")

    def __init__(self, fn: Callable, detector: RecompileDetector,
                 argnums: Optional[Tuple[int, ...]] = None):
        self._fn = fn
        self.detector = detector
        self._argnums = argnums

    def __call__(self, *args, **kwargs):
        prof = profiling.active_profiler()
        coll = shardstats.active_collector()
        cost_fn = None
        fn = self._fn
        if coll is not None:
            # superset analysis: memory_analysis + collective census +
            # the same flops/bytes fields jit_cost_analysis returns, from
            # ONE lower+compile — an installed profiler reads it as-is
            cost_fn = lambda: shardstats.program_analysis(fn, args, kwargs)
        elif prof is not None and prof.cost_analysis:
            cost_fn = lambda: profiling.jit_cost_analysis(fn, args, kwargs)
        if self._argnums is None:
            self.detector.check(args, kwargs, cost_fn=cost_fn)
        else:
            # dict keyed by the ORIGINAL position so delta paths stay
            # meaningful ("args[4]: f32[32,8] -> f32[20,8]")
            sel = {i: args[i] for i in self._argnums if i < len(args)}
            self.detector.check(sel, kwargs, cost_fn=cost_fn)
        if prof is not None:
            prof.note_dispatch(self.detector.name, self.detector.last_cost)
        if coll is not None:
            coll.note_dispatch(self.detector.name, self.detector.last_cost)
        return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):
        return f"InstrumentedJit({self.detector.name})"


def instrument(fn: Callable, name: str, registry=None,
               warn: Optional[Callable[[str], None]] = None,
               argnums: Optional[Tuple[int, ...]] = None) -> _InstrumentedJit:
    """Wrap a jitted callable with a RecompileDetector (see module doc).
    ``argnums``: fingerprint only these positional args (hot-loop cost
    control; see ``_InstrumentedJit``)."""
    return _InstrumentedJit(fn, RecompileDetector(name, registry, warn),
                            None if argnums is None else tuple(argnums))


def compile_counter(fn_name: str, registry=None):
    """The shared ``dl4j_compiles_total{fn=}`` child for callers outside
    the detector (e.g. bench AOT compiles) — ONE owner for the family
    declaration, so label sets can never diverge."""
    from deeplearning4j_tpu.observability.metrics import get_registry

    reg = registry if registry is not None else get_registry()
    return reg.counter(
        _COMPILES, "Distinct abstract input signatures (≈ XLA "
        "compilations) per jitted function", labels=("fn",)).labels(
        fn=fn_name)

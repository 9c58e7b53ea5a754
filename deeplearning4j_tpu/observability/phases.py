"""Registry-backed phase timers — the ``PhaseStats`` successor.

≙ ``CommonSparkTrainingStats.java`` / ``ParameterAveragingTrainingMasterStats
.java``: the reference times count/split/repartition/mapPartitions/aggregate
per fit; here the phases are the TPU-native pipeline sections (fetch /
place / dispatch / device_sync, gradient compute vs all-reduce vs host
sync).

Each timed phase is recorded three times:

- into a per-instance ``Histogram`` so ``as_dict()`` keeps the exact
  ``PhaseStats`` schema (count/total_ms/mean_ms/min_ms/max_ms per phase)
  that ``training_stats()`` consumers and tests rely on;
- into the process-wide registry family
  ``dl4j_phase_seconds{component=..., phase=...}`` so /metrics scrapes and
  bench snapshots see phase timing without holding a master reference;
- into the profiler's trace, as a ``jax.profiler.TraceAnnotation`` named
  ``<component>.<stage>.<phase>`` (``<component>.<phase>`` without a stage):
  a no-op unless a profiler session is running, and then the one way the
  program's own spans reach the ``.xplane.pb``, on the device's clock.

A caller whose loop does the same phase for different reasons gives a
``stage`` (the decode loop: ``admit`` / ``decode`` / ``loop``).  ``phases``
sums over stages; ``as_dict()["stages"][stage][phase]`` keeps them apart.  A
``child`` phase is a part of the phase that encloses it (or time that is no
work at all, like a wait): it is in the trace and under ``stages``, never in
``phases`` or the registry, so no second is counted twice.

Migration from the old private ``PhaseStats``: the class below is a drop-in
(same ``phase()`` context manager, ``steps`` counter, ``enabled`` flag,
``as_dict()``), re-exported from ``parallel.training_master`` under the old
name.  See docs/observability.md.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

from jax.profiler import TraceAnnotation

from deeplearning4j_tpu.observability.metrics import (
    Histogram, MetricsRegistry, get_registry,
)

_FAMILY = "dl4j_phase_seconds"


class _NullTimer:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullTimer()


class _Timer:
    """One entry of a phase: the histograms it lands in and its trace name
    are looked up once per (stage, phase) by ``PhaseTimers.phase``."""

    __slots__ = ("_sinks", "_trace_name", "_annotation", "_t0")

    def __init__(self, sinks: tuple, trace_name: str):
        self._sinks = sinks
        self._trace_name = trace_name

    def __enter__(self):
        # a TraceMe starts when it is built, so it cannot be kept and reused
        self._annotation = TraceAnnotation(self._trace_name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        for h in self._sinks:
            h.observe(dt)
        return False


def _summary(h: Histogram) -> Dict[str, Any]:
    return {
        "count": h.count,
        "total_ms": round(h.sum * 1e3, 3),
        "mean_ms": round(h.sum / h.count * 1e3, 3),
        "min_ms": round(h.min * 1e3, 3),
        "max_ms": round(h.max * 1e3, 3),
    }


class PhaseTimers:
    """Phase-timed stats for one component instance (see module doc)."""

    def __init__(self, component: str, enabled: bool = True,
                 registry: Optional[MetricsRegistry] = None):
        self.component = component
        self.enabled = enabled
        self.steps = 0
        self._registry = registry
        self._local: Dict[str, Histogram] = {}
        self._staged: Dict[Tuple[str, str], Histogram] = {}
        # (stage, phase, child) -> (sinks, trace name), see _Timer
        self._entries: Dict[tuple, tuple] = {}
        self._shared_reg: Optional[MetricsRegistry] = None

    def phase(self, name: str, stage: Optional[str] = None,
              child: bool = False):
        if not self.enabled:
            return _NULL
        reg = (self._registry if self._registry is not None
               else get_registry())
        if reg is not self._shared_reg or reg.get(_FAMILY) is None:
            # registry swapped (set_registry) or wiped (reset()): drop the
            # shared children so timings land in the LIVE registry; the
            # per-instance aggregates (as_dict) carry on unbroken
            self._entries.clear()
            self._shared_reg = reg
        entry = self._entries.get((stage, name, child))
        if entry is None:
            entry = self._entries[(stage, name, child)] = self._entry(
                reg, name, stage, child)
        return _Timer(*entry)

    def _entry(self, reg: MetricsRegistry, name: str, stage: Optional[str],
               child: bool) -> tuple:
        sinks = []
        if stage is not None:
            sinks.append(self._staged.setdefault((stage, name), Histogram()))
        elif child:
            raise ValueError(f"child phase {name!r} needs a stage: it is "
                             "reported under as_dict()['stages'] only")
        if not child:
            sinks.append(self._local.setdefault(name, Histogram()))
            sinks.append(reg.histogram(
                _FAMILY, "Per-phase wall time of distributed-training and "
                "pipeline components", labels=("component", "phase"),
            ).labels(component=self.component, phase=name))
        parts = (self.component, name) if stage is None else (
            self.component, stage, name)
        return tuple(sinks), ".".join(parts)

    def totals(self) -> Dict[str, float]:
        """Cumulative seconds per phase — cheap enough to snapshot before/
        after a batch for per-batch phase deltas (pipeline per-stage
        attribution)."""
        return {name: h.sum for name, h in self._local.items()}

    def as_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"steps": self.steps, "phases": {},
                               "stages": {}}
        for name, h in self._local.items():
            if h.count:
                out["phases"][name] = _summary(h)
        for (stage, name), h in self._staged.items():
            if h.count:
                out["stages"].setdefault(stage, {})[name] = _summary(h)
        return out

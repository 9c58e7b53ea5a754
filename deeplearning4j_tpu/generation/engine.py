"""GenerationEngine: continuous-batching autoregressive serving.

Ties the pieces together into the decode analog of the PR-2
``ServingEngine``:

- a ``PagedKVCache`` + ``DecodeScheduler`` (iteration-level batching:
  requests join/leave the RUNNING batch every step; prefix sharing;
  admission control with 429/503/504 instead of hangs),
- a ``ModelRegistry`` (named/versioned models; ``deploy`` is a
  zero-drop hot-swap BETWEEN decode steps — in-flight streams keep
  their KV and continue under the new weights, which is the standard
  weight-only-update serving semantic),
- per-version ``GenerationPrograms`` (bucketed prefill + one decode
  step, AOT-warmed through the version's RecompileDetector before it
  serves: zero steady-state compiles),
- a ``GenerationMetrics`` bundle and ``step_guard`` spans (decode steps
  are visible to the StepProfiler/watchdog like any train step).

One background decode thread owns the device pools, the slot arrays,
and the page allocator; clients only touch the admission queue and
their own request handles, so ``submit``/``stream`` are thread-safe.

THE LOOP RUNS ONE STEP AHEAD OF THE DEVICE.  It never waits for a
program's result before it issues the next program: the sampled ids stay
on the device (``_ids`` [slots]: a decode step's output is the next
step's ``tokens``, a prefill writes its first token into its lane), and
everything else a dispatch needs the host already knows (pages are all
allocated at admission; a finish by length is a count).  One iteration
(``_iterate``): dispatch the prefill of every admittable request, dispatch
decode step N+1, and only then harvest step N — ``device_get`` its ids,
deliver them, retire what ended — and the first tokens of the requests
just admitted, all while the device runs what was dispatched.  The depth
is one step, fixed.  What follows from it:

- a stop token, a cancel or a deadline is seen at a harvest, when the next
  step is already in flight: the row runs AT MOST ONE STEP MORE, whose id
  is dropped at its harvest (``dl4j_decode_discarded_rows_total``); nothing
  past a stop token is delivered;
- pages are freed at the harvest that ends a request and may be reused at
  once: the device executes programs in the order they were dispatched,
  so whatever reuses a page, or a lane of ``_ids``, runs after the last
  program that read or wrote it;
- the lease is per iteration: the step in flight finishes under the
  weights it was dispatched with, the next dispatch takes the new ones;
- an error surfaces where the host next waits: the step in flight is
  dropped, the batch evicted, pools and ids reseeded;
- ``stop(drain=True)`` harvests what is in flight before the thread exits,
  ``stop(drain=False)`` drops it and evicts its rows.

Minimal use::

    engine = GenerationEngine(net, slots=8, page_size=16,
                              max_context=128)
    engine.start()                      # AOT-warms every program
    h = engine.submit([1, 2, 3], max_new_tokens=16)
    for tok in h.stream(): ...          # tokens as they decode
    engine.deploy("default", new_net)   # hot-swap between steps
    engine.stop()
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np

from deeplearning4j_tpu.generation.paged_cache import PagedKVCache
from deeplearning4j_tpu.generation.prefix_cache import (
    PrefixCache, PrefixCacheConfig,
)
from deeplearning4j_tpu.generation.programs import (
    GenerationPrograms, has_state_pools, sampled_ids, window_pool_pages,
    window_ring_pages,
)
from deeplearning4j_tpu.generation.scheduler import (
    DecodeScheduler, GenerationRequest,
)
from deeplearning4j_tpu.observability.flightrecorder import (
    get_flight_recorder, step_guard,
)
from deeplearning4j_tpu.observability.fleet import SLOTracker
from deeplearning4j_tpu.observability.phases import PhaseTimers
from deeplearning4j_tpu.observability.servingmetrics import GenerationMetrics
from deeplearning4j_tpu.observability.tracing import get_tracer
from deeplearning4j_tpu.serving.admission import ModelNotFoundError
from deeplearning4j_tpu.serving.buckets import _pow2_buckets
from deeplearning4j_tpu.serving.registry import ModelRegistry, ModelVersion
from deeplearning4j_tpu.utils.sampling import SAMPLING_PATHS, sampling_path

logger = logging.getLogger("deeplearning4j_tpu.generation")

DEFAULT_MODEL = "default"
# harvests of an expert net's routing counts summed on the host between
# two increments of the registry's counters (_take_moe_counts)
MOE_FLUSH_EVERY = 32

# finish reasons that count as a successful completion
_OK_REASONS = ("length", "stop")


class _Step:
    """A decode step in flight: dispatched, its ids not yet harvested."""

    __slots__ = ("sampled", "rows", "model")

    def __init__(self, sampled, rows, model: str):
        self.sampled = sampled      # device: ids, or (ids, counts)
        self.rows = rows            # (lane, slot) as dispatched
        self.model = model


class GenerationEngine:
    """See module docstring."""

    def __init__(self, model=None, *, slots: int = 8, page_size: int = 16,
                 max_context: int = 256, num_pages: Optional[int] = None,
                 max_queue: int = 64, deadline_s: float = 60.0,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 models: Optional[ModelRegistry] = None, registry=None,
                 default_model: str = DEFAULT_MODEL,
                 prefix_cache=None, slo_targets: Optional[dict] = None,
                 decode_step_floor_s: float = 0.0):
        if max_context < 2:
            raise ValueError(f"max_context={max_context} must be >= 2")
        pages_per_slot = -(-int(max_context) // int(page_size))
        if num_pages is None:
            # default: full occupancy of every slot fits (+ trash page),
            # so admission only ever sheds on the queue budget
            num_pages = slots * pages_per_slot + 1
        self.metrics = GenerationMetrics(registry)
        # decode SLO attribution: TTFT/ITL attainment + goodput against
        # configurable targets (slo_targets={"ttft_target_s": ...,
        # "itl_target_s": ...}), federated via fleet_publisher()
        self.slo = SLOTracker(registry=self.metrics.registry,
                              engine_id=self.metrics.engine_id,
                              **(slo_targets or {}))
        # per-iteration phase breakdown of the decode loop (schedule /
        # page_gather / jitted_step / sample_harvest / stream_write), each
        # under the stage it ran for: loop / admit / decode.  The same spans
        # are in a profiler trace as generation_decode.<stage>.<phase>
        # (docs/observability.md, "Span tracing")
        self.phases = PhaseTimers("generation_decode",
                                  registry=self.metrics.registry)
        self.busy_wall_s = 0.0          # decode-loop wall time, non-wait
        self.models = models or ModelRegistry(
            metrics_registry=self.metrics.registry)
        self.default_model = default_model
        # pages by layer kind, learnt from the layers as the pools are: a
        # net with sliding-window attention layers holds a ring of
        # ceil(window / page) + 1 pages a slot in pools of their own kind
        served = self._served_net(model)
        ring = window_ring_pages(served, page_size) if served else 0
        # state slots, learnt the same way: a net with recurrent layers
        # holds one row of state a slot, begun anew at admission
        state = bool(served) and has_state_pools(served)
        if state and prefix_cache:
            raise ValueError(
                "prefix_cache cannot serve a net with recurrent state "
                "layers: a cached page skips its prefill, and the state "
                "that prefill would have built does not exist (in-flight "
                "prefix sharing is off for such a net for the same reason)")
        if ring and prefix_cache:
            raise ValueError(
                "prefix_cache cannot serve a net with sliding-window "
                "attention layers: a cached page skips its prefill, which "
                "would leave the window layers' rings unfilled (in-flight "
                "prefix sharing is off for such a net for the same reason)")
        self.cache = PagedKVCache(
            num_pages, page_size, pages_per_slot, window_pages_per_slot=ring,
            num_window_pages=window_pool_pages(slots, ring),
            state_slots=state)
        # persistent radix-tree prefix cache (opt-in retention policy):
        # prefix_cache=True for defaults, a PrefixCacheConfig for knobs,
        # None/False keeps PR-13 free-on-release behavior bit-identical
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            cfg = (prefix_cache if isinstance(prefix_cache,
                                              PrefixCacheConfig)
                   else PrefixCacheConfig())
            self.prefix_cache = PrefixCache(
                self.cache, host_budget_bytes=cfg.host_budget_bytes,
                metrics=self.metrics)
            self.cache.retention = self.prefix_cache
        self.scheduler = DecodeScheduler(
            self.cache, slots=slots, max_queue=max_queue,
            default_deadline_s=deadline_s, metrics=self.metrics)
        self.scheduler.on_finish = self._on_finish
        if prefill_buckets is None:
            prefill_buckets = _pow2_buckets(int(max_context))
        self.prefill_buckets = tuple(sorted(set(int(b)
                                                for b in prefill_buckets)))
        if model is not None:
            self.models.register(default_model, model)
        self._programs: "dict[str, GenerationPrograms]" = {}
        self._pools = None              # decode-thread-owned device state
        self._ids = None                # [slots] last sampled id a lane
        self._in_flight: Optional[_Step] = None   # dispatched, unharvested
        self._firsts: list = []         # prefills dispatched this iteration
        self._swap_lock = threading.Lock()
        self._stop_event = threading.Event()
        self._drain = True
        self._thread: Optional[threading.Thread] = None
        self.steady_deliveries = 0      # tokens delivered since start
        self._moe_pending = None        # expert-layer counts not yet in
        self._moe_harvests = 0          # the registry (_take_moe_counts)
        # pacing: a minimum wall time per decode step.  Its one user is
        # the fleet kill drill (tests/test_fleet_router.py), which slows
        # its CPU replicas so that a kill lands while a stream is still
        # being written; 0 disables and changes nothing.
        self.decode_step_floor_s = float(decode_step_floor_s)

    def _served_net(self, model):
        """The net this engine is built around (the one given, or the
        registry's active default); None when no model is registered yet."""
        if model is not None:
            return model
        try:
            return self.models.active(self.default_model).model
        except ModelNotFoundError:
            return None

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "GenerationEngine":
        """Build + AOT-warm the active version's programs (every prefill
        bucket and the decode step compile NOW, through the version's
        RecompileDetector), allocate the live page pools, start the
        decode thread."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("engine already started")
        mv = self.models.active(self.default_model)
        progs = self._build_programs(mv)
        self._reseed(progs)
        self._in_flight, self._firsts = None, []
        if self.prefix_cache is not None:
            # fresh pools mean every cached node points at garbage:
            # drop the tree, stamp the serving version, wire the page
            # transport + host-budget unit
            self.prefix_cache.invalidate("pool_reset")
            self.prefix_cache.set_version(mv.key)
            self.prefix_cache.attach(self,
                                     progs.page_nbytes(self._pools))
        self.scheduler.reopen()   # a restart re-arms admission
        self._stop_event.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="generation-decode")
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """With ``drain`` (default) every queued and running request is
        still served (bounded by ``timeout``); without, queued requests
        fail 503 now and running ones are evicted at the next step
        boundary.  Either way no waiter is left hanging."""
        self._drain = drain
        self.scheduler.begin_shutdown(drain_pending=drain)
        self._stop_event.set()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                logger.warning(
                    "decode thread still draining after %.1fs; failing "
                    "the remaining requests", timeout)
                self._drain = False
                self._thread.join(5.0)
        self._thread = None
        self.scheduler.evict_all("shutdown")
        # anything still queued after the drain window failed because the
        # ENGINE stopped, not because its own deadline passed: 503
        self.scheduler.begin_shutdown(drain_pending=False)
        self._refresh_gauges()

    # ---------------------------------------------------------------- submit
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32, *,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None, seed: int = 0,
               deadline_s: Optional[float] = None,
               stop_token: Optional[int] = None,
               trace_id: Optional[str] = None) -> GenerationRequest:
        """Thread-safe enqueue; returns the request handle (``stream()``
        for tokens as they decode, ``result()`` to block).  Raises
        ``QueueFullError`` (429) on a full queue, ``ShuttingDownError``
        (503) during shutdown, ``ValueError`` for a request that could
        never fit the page pool."""
        deadline = self.scheduler.admission.deadline_for(deadline_s)
        req = GenerationRequest(
            prompt, max_new_tokens, temperature=temperature, top_k=top_k,
            top_p=top_p, seed=seed, deadline_s=deadline,
            stop_token=stop_token, trace_id=trace_id)
        # worst case (no prefix shared) the WHOLE prompt prefills in one
        # bucket; reject here with a clean error instead of detonating a
        # ValueError on the decode thread mid-batch
        if len(req.prompt) > max(self.prefill_buckets):
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens exceeds the largest "
                f"prefill bucket {max(self.prefill_buckets)}")
        return self.scheduler.submit(req)

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 32,
                 **kw) -> np.ndarray:
        """Blocking convenience: submit + wait; returns the generated ids
        as a 1-D array."""
        req = self.submit(prompt, max_new_tokens, **kw)
        return np.asarray(req.result(), np.int32)

    # -------------------------------------------------------- prefix pinning
    def pin_prefix(self, prompt: Sequence[int]) -> int:
        """Pin ``prompt``'s cached prefix pages against offload and
        eviction (multi-turn sessions pin their history after each turn
        so the next turn only prefills the new tokens); returns a pin id
        for ``unpin_prefix``.  Thread-safe."""
        if self.prefix_cache is None:
            raise RuntimeError(
                "pin_prefix requires the persistent prefix cache "
                "(GenerationEngine(..., prefix_cache=True))")
        return self.prefix_cache.pin(prompt)

    def unpin_prefix(self, pin_id: int) -> None:
        """Release one pin; an unknown or already-released id raises
        ``KeyError``."""
        if self.prefix_cache is None:
            raise RuntimeError(
                "unpin_prefix requires the persistent prefix cache")
        self.prefix_cache.unpin(pin_id)

    # ------------------------------------------------- prefix-cache transport
    # PrefixCache calls these on the decode thread (inside admission,
    # which the engine's single decode loop drives), so reading and
    # replacing self._pools here is the owner thread acting.
    def cache_read_page(self, page: int):
        progs = self._programs[self.models.active(self.default_model).key]
        return progs.read_page(self._pools, page)

    def cache_write_page(self, page: int, payload) -> None:
        progs = self._programs[self.models.active(self.default_model).key]
        self._pools = progs.write_page(self._pools, page, payload)

    # ----------------------------------------------------------- model admin
    def deploy(self, name: str, model, *, retain_old: bool = False,
               drain_timeout: float = 30.0) -> ModelVersion:
        """Register ``model`` as the next version of ``name`` and hot-swap
        it in WITHOUT interrupting decode: the incoming version's
        programs are built and AOT-warmed first (a model that fails its
        warmup — or whose cache geometry differs from the live pools —
        aborts here with the old version intact), then the active
        pointer flips atomically; the decode loop leases per iteration,
        so the very next step runs the new weights while every in-flight
        stream keeps its slot, its pages, and its sampling state.  With
        ``retain_old`` the displaced version stays loaded as the
        ``rollback`` target."""
        if name != self.default_model:
            raise ValueError(
                f"generation engine serves one model name "
                f"({self.default_model!r}); decode batches cannot mix "
                f"models")
        with self._swap_lock:
            mv = self.models.new_version(name, model)
            self._build_programs(mv)   # raises -> swap aborted, old intact
            self._commit_locked(name, drain_timeout)
            old = self.models.activate(mv, retain=retain_old)
            get_flight_recorder().record(
                "generation_swap", model=name, version=mv.version,
                replaced=old.version if old else None,
                retained=bool(retain_old and old is not None))
            if old is not None:
                self.metrics.swaps.inc(model=name)
                if not retain_old:
                    self._retire(old, drain_timeout)
            logger.info("generation: %s now serving (replaced %s%s)",
                        mv.key, old.key if old else "nothing",
                        ", retained for rollback"
                        if retain_old and old else "")
            return mv

    def rollback(self, name: Optional[str] = None, *,
                 drain_timeout: float = 30.0) -> ModelVersion:
        """Undo the last retaining swap: flip back to the retained
        version between decode steps (its programs are still warm — a
        retained version's program set is only dropped at retire)."""
        name = name or self.default_model
        with self._swap_lock:
            restored, displaced = self.models.rollback(name)
            get_flight_recorder().record(
                "generation_rollback", model=name,
                restored=restored.version,
                displaced=displaced.version if displaced else None)
            self.metrics.swaps.inc(model=name)
            if displaced is not None:
                self._retire(displaced, drain_timeout)
            return restored

    def commit_swap(self, name: Optional[str] = None, *,
                    drain_timeout: float = 30.0) -> Optional[ModelVersion]:
        """Close the rollback window: retire the retained version."""
        with self._swap_lock:
            return self._commit_locked(name or self.default_model,
                                       drain_timeout)

    def _commit_locked(self, name: str, drain_timeout: float):
        mv = self.models.release_retained(name)
        if mv is not None:
            self._retire(mv, drain_timeout)
        return mv

    def _retire(self, mv: ModelVersion, timeout: float) -> None:
        if self.models.retire(mv, timeout=timeout):
            self._programs.pop(mv.key, None)   # drop its jit caches
        else:
            logger.warning("%s still leased after %.1fs; left un-retired",
                           mv.key, timeout)

    def _build_programs(self, mv: ModelVersion) -> GenerationPrograms:
        """Programs for one version, AOT-warmed on scratch pools, with
        the pool geometry validated against the live pools (a deploy
        whose architecture changes the KV shapes cannot share the
        in-flight cache and must be rejected)."""
        progs = GenerationPrograms(
            mv.model, slots=self.scheduler.num_slots,
            pages_per_slot=self.cache.pages_per_slot,
            page_size=self.cache.page_size, num_pages=self.cache.num_pages,
            prefill_buckets=self.prefill_buckets, detector=mv.detector)
        if progs.ring != self.cache.window_pages_per_slot:
            raise ValueError(
                f"cannot serve {mv.key}: its window layers ring through "
                f"{progs.ring} pages a slot, the page manager was built "
                f"for {self.cache.window_pages_per_slot} (the engine "
                "learns the ring from the model it is constructed with)")
        if progs.state != self.cache.state_slots:
            raise ValueError(
                f"cannot serve {mv.key}: it "
                f"{'keeps' if progs.state else 'keeps no'} recurrent state "
                "in state slots, the page manager was built for a net that "
                f"{'does' if self.cache.state_slots else 'does not'} (the "
                "engine learns it from the model it is constructed with)")
        if self._pools is not None:
            live = jax.tree_util.tree_map(
                lambda a: (a.shape, str(a.dtype)), self._pools)
            new = jax.tree_util.tree_map(
                lambda a: (a.shape, str(a.dtype)),
                jax.eval_shape(progs.fresh_pools))
            if live != new:
                raise ValueError(
                    f"cannot deploy {mv.key}: its paged-cache geometry "
                    "differs from the live pools (layer names / kv heads "
                    "/ head dims must match the serving architecture)")
        # the version's one cast, counted, before its programs compile
        # against the snapshot
        self._serving_params(progs, mv)
        progs.warm()
        self._programs[mv.key] = progs
        return progs

    def _serving_params(self, progs: GenerationPrograms, mv: ModelVersion):
        """``mv``'s parameters as its programs take them (the snapshot
        ``progs`` casts once and casts again when ``mv.model.params`` has
        changed); every cast lands in ``dl4j_decode_param_casts_total``."""
        casts = progs.param_casts
        params = progs.serving_params()
        if progs.param_casts != casts:
            self.metrics.param_casts.inc(model=mv.name)
        return params

    # ------------------------------------------------------------ decode loop
    def _run(self) -> None:
        while True:
            stopping = self._stop_event.is_set()
            if stopping and (not self._drain or not self._has_work()):
                break
            t_iter = time.perf_counter()
            with self.phases.phase("schedule", stage="loop"):
                self.scheduler.purge_pending()
            try:
                with self.models.lease(self.default_model) as mv:
                    progs = self._programs[mv.key]
                    if (self.prefix_cache is not None
                            and self.prefix_cache.version != mv.key):
                        # hot-swap/rollback observed: cached KV was
                        # prefilled under the displaced weights — a
                        # stale hit would be silently wrong, so the
                        # whole tree goes before any admission runs
                        n = self.prefix_cache.invalidate("swap")
                        self.prefix_cache.set_version(mv.key)
                        logger.info("prefix cache invalidated on swap "
                                    "to %s (%d nodes dropped)",
                                    mv.key, n)
                    if self._iterate(progs, mv):
                        self.busy_wall_s += time.perf_counter() - t_iter
                        continue
            except Exception as e:
                logger.exception("decode iteration failed; evicting the "
                                 "running batch and reseeding the pools")
                get_flight_recorder().record("generation_error",
                                             error=str(e)[:200])
                # what is in flight read or wrote the pools that failed
                self._in_flight, self._firsts = None, []
                self.scheduler.evict_all("error", e)
                try:
                    self._reseed(self._programs[
                        self.models.active(self.default_model).key])
                    if self.prefix_cache is not None:
                        # the reseed just zeroed every cached page
                        self.prefix_cache.invalidate("pool_reset")
                except Exception:
                    logger.exception("pool reseed failed; decode thread "
                                     "exiting")
                    return
            self.busy_wall_s += time.perf_counter() - t_iter
            if not stopping and not self._has_work():
                self._flush_moe_counts()
                # not busy time, so in the trace and not in "phases"
                with self.phases.phase("wait", stage="loop", child=True):
                    self.scheduler.wait_for_work(0.05)
        # stop(drain=False) leaves a step behind: stop() evicts its rows
        self._in_flight, self._firsts = None, []
        self._flush_moe_counts()

    def _has_work(self) -> bool:
        return self._in_flight is not None or self.scheduler.has_work

    def _reseed(self, progs: GenerationPrograms) -> None:
        self._pools, self._ids = progs.fresh_pools(), progs.fresh_ids()

    def _iterate(self, progs: GenerationPrograms, mv: ModelVersion) -> bool:
        """One turn of the loop, one step ahead of the device: dispatch
        the prefills of what can be admitted and decode step N+1, all from
        ids still on the device, and only then wait for step N's ids and
        deliver them, and after them the first tokens of the requests
        just admitted, while the device runs what was dispatched.  True
        if anything was dispatched or harvested."""
        t0 = time.perf_counter()
        self._admit(progs, mv)
        rows = self.scheduler.running_rows()
        step = self._dispatch(progs, mv, rows) if rows else None
        harvest, self._in_flight = self._in_flight, step
        if harvest is not None:
            self._harvest(harvest)
        firsts, self._firsts = self._firsts, []
        for first in firsts:
            self._harvest_first(*first)
        if step is not None and self.decode_step_floor_s > 0.0:
            # sleep (not spin) to the floor: sibling replica processes
            # share the host's cores
            remain = self.decode_step_floor_s - (time.perf_counter() - t0)
            if remain > 0:
                time.sleep(remain)
        return bool(step or harvest or firsts)

    def _admit(self, progs: GenerationPrograms, mv: ModelVersion) -> None:
        while True:
            with self.phases.phase("schedule", stage="admit"):
                req = self.scheduler.next_admittable()
            if req is None:
                return
            try:
                self._prefill(progs, mv, req)
            except Exception as e:
                # the request holds pages but no slot yet: evict_all in
                # the outer handler cannot see it, so terminate it here
                # (pages freed, waiters released, stale prefix-index
                # entries for its never-written pages removed) and let
                # the outer handler reset the pools
                self.scheduler.fail_admitted(req, e)
                raise

    def _prefill(self, progs: GenerationPrograms, mv: ModelVersion,
                 req: GenerationRequest) -> None:
        """Dispatch ``req``'s prefill and bind its slot; nothing here
        waits for the device.  The first token goes into ``self._ids`` at
        the request's lane on the device and reaches the client at this
        iteration's harvest (``_harvest_first``)."""
        phase = self.phases.phase
        # a window layer's chunk is written into its ring as a prompt
        # prefilled whole from position 0 (_apply_window_paged)
        # and a state layer's slot begins anew at position 0
        assert not ((progs.ring or progs.state) and req.shared_len), (
            "a prefix was shared under window or state layers")
        with phase("page_gather", stage="admit"):
            suffix = req.prompt[req.shared_len:]
            bucket = progs.bucket_for(len(suffix))
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :len(suffix)] = suffix
            shared_pages = req.shared_len // self.cache.page_size
            with phase("base_key", stage="admit", child=True):
                base_key = _base_key(req.seed)
            block = self.cache.block_row(req.pages)[None]
            policy = (np.asarray([req.temperature], np.float32),
                      np.asarray([req.top_k], np.int32),
                      np.asarray([req.top_p], np.float32))
        with step_guard("decode_prefill", engine=self.metrics.engine_id,
                        bucket=bucket, shared_pages=shared_pages):
            with phase("jitted_step", stage="admit"):
                self._pools, tok, self._ids = progs.prefill(
                    bucket, self._serving_params(progs, mv),
                    mv.model.net_state,
                    self._pools, block,
                    np.asarray([req.shared_len], np.int32),
                    np.int32(len(suffix) - 1), tokens, base_key[None],
                    np.zeros(1, np.int32), *policy, self._ids,
                    np.int32(req.slot))
                _copy_to_host_async(tok)
        with phase("page_gather", stage="admit"):
            self.scheduler.install(req, base_key)
        self._count_paths("prefill", progs.paths[(bucket,
                                                  req.shared_len == 0)],
                          policy)
        if progs.state:
            self.metrics.state_slot_resets.inc()
        self._firsts.append((req, tok, mv.name))

    def _harvest_first(self, req: GenerationRequest, tok,
                       model: str) -> None:
        """Wait for one prefill's sample and deliver it.  The prefill is
        ahead of the decode step just dispatched in the device's queue, so
        the device is busy with that step while the host is here."""
        phase = self.phases.phase
        with phase("sample_harvest", stage="admit"):
            tok = jax.device_get(tok)
        with phase("stream_write", stage="admit"):
            tok = self._take_moe_counts(tok, "admit")
            self.scheduler.first_token(req, int(tok[0]))
            self.metrics.ttft.observe(req.ttft_s)
            shared_pages = req.shared_len // self.cache.page_size
            self.metrics.prefix_pages.inc(shared_pages, outcome="shared")
            self.metrics.prefix_pages.inc(len(req.pages) - shared_pages,
                                          outcome="fresh")
            self.metrics.tokens.inc(model=model)
            self._refresh_gauges()

    def _dispatch(self, progs: GenerationPrograms, mv: ModelVersion,
                  rows) -> _Step:
        """Dispatch one decode step over ``rows`` from the ids on the
        device, start their copy to the host, and move the scheduler's
        mirrors to where the NEXT dispatch finds them."""
        s = self.scheduler
        with step_guard("decode_step", engine=self.metrics.engine_id,
                        active=len(rows)):
            with self.phases.phase("jitted_step", stage="decode"):
                where, policy = s.step_inputs()
                self._pools, sampled = progs.decode(
                    self._serving_params(progs, mv), mv.model.net_state,
                    self._pools, *where, self._ids, *policy)
                self._ids = sampled_ids(sampled)
                _copy_to_host_async(sampled)
                s.advance(rows)
        self.metrics.decode_dispatch.inc(
            mode="sync" if self._in_flight is None else "ahead")
        self._count_paths("decode", progs.paths[("decode", False)],
                          policy[2:])
        return _Step(sampled, rows, mv.name)

    def _count_paths(self, stage: str, paths, policy) -> None:
        """One dispatched program: each distinct (kind, path) its layers
        take (``GenerationPrograms.paths``) and its sampling epilogue's,
        from the rows' ``policy`` (kind ``head``), once."""
        sampled = ("head", SAMPLING_PATHS[sampling_path(*policy)])
        for kind, path in (*paths, sampled):
            self.metrics.layer_path_steps.inc(stage=stage, kind=kind,
                                              path=path)

    def _harvest(self, step: _Step) -> None:
        """Wait for a dispatched step's ids and deliver them; the device
        is meanwhile running the step dispatched after it."""
        s = self.scheduler
        phase = self.phases.phase
        with phase("sample_harvest", stage="decode"):
            sampled_host = jax.device_get(step.sampled)
        with phase("stream_write", stage="decode"):
            sampled_host = self._take_moe_counts(sampled_host, "decode")
            with phase("deliver", stage="decode", child=True):
                delivered = s.harvest_step(step.rows, sampled_host)
            self.steady_deliveries += delivered
            with phase("gauges", stage="decode", child=True):
                self.metrics.steps.inc()
                self.metrics.tokens.inc(delivered, model=step.model)
                self.metrics.batch_occupancy.observe(
                    len(step.rows) / s.num_slots)
                self._refresh_gauges()

    def _take_moe_counts(self, harvested, stage: str):
        """The sampled ids out of what a compute program returned.  A net
        with expert layers returns ``(ids, counts)`` (``programs.
        _with_counts``), fetched in the one ``device_get``: the counts are
        summed on the host under the child phase ``<stage>.moe_counters``
        and reach the registry every ``MOE_FLUSH_EVERY`` harvests, when
        the loop runs out of work and when it exits.  A net with
        hyper-connection blocks returns a third entry, what its step left
        ``H_res`` short of doubly stochastic: ``dl4j_mhc_row_sum_error``.
        Any other net's harvest passes through untouched."""
        if not isinstance(harvested, tuple):
            return harvested
        ids, counts, *gauge = harvested
        with self.phases.phase("moe_counters", stage=stage, child=True):
            if gauge:
                self.metrics.mhc_row_sum_error.set(float(gauge[0]))
            self._moe_pending = (counts if self._moe_pending is None
                                 else self._moe_pending + counts)
            self._moe_harvests += 1
            if self._moe_harvests % MOE_FLUSH_EVERY == 0:
                self._flush_moe_counts()
        return ids

    def _flush_moe_counts(self) -> None:
        """The pending counts into ``dl4j_moe_tokens_total`` and
        ``dl4j_moe_held_assignments_total{expert}``."""
        pending, self._moe_pending = self._moe_pending, None
        if pending is None:
            return
        self.metrics.moe_tokens.inc(int(pending[0]))
        for expert, n in enumerate(pending[1:]):
            if n:
                self.metrics.moe_held_assignments.inc(int(n),
                                                      expert=str(expert))

    def _refresh_gauges(self) -> None:
        active = len(self.scheduler.active_slots())
        self.metrics.active_slots.set(active)
        if self.cache.state_slots:
            self.metrics.state_slots_in_use.set(active)
        self.metrics.page_util.set(self.cache.utilization())
        for kind in self.cache.KINDS:
            if self.cache.pages_total(kind):
                self.metrics.set_kv_pages(kind,
                                          self.cache.pages_in_use(kind),
                                          self.cache.pages_total(kind))
        if self.prefix_cache is not None:
            # one locked snapshot — three separate reads could tear
            # across a concurrent eviction/offload (resident dropping
            # while host_bytes had not risen yet)
            st = self.prefix_cache.stats()
            self.metrics.prefix_cache_resident.set(st["resident_pages"])
            self.metrics.prefix_cache_pinned.set(st["pinned_pages"])
            self.metrics.prefix_cache_host_bytes.set(
                st["host_tier_bytes"])

    def _on_finish(self, req: GenerationRequest) -> None:
        """Terminal accounting for every request, whatever path ended it
        (completion, stop token, cancel, deadline, shutdown, error)."""
        status = req.finish_reason or "error"
        self.metrics.requests.inc(status=status)
        # SLO verdict BEFORE the waiters wake (scheduler calls on_finish
        # before releasing them), so access logs and req.as_dict() read
        # a settled slo_ok
        req.slo_ok = self.slo.observe_request(
            ttft_s=req.ttft_s, itl_s=req.itl_s,
            completed=status in _OK_REASONS)
        end_ns = time.perf_counter_ns()
        start_ns = int(req.submitted * 1e9)
        # a first token implies an admission, so queue_wait_s is set
        prefill_s = (req.ttft_s - req.queue_wait_s
                     if req.ttft_s is not None else None)
        get_tracer().record_span(
            "generation_request", start_ns, end_ns,
            trace_id=req.trace_id, tokens=len(req.tokens), status=status,
            ttft_ms=_ms(req.ttft_s), queue_wait_ms=_ms(req.queue_wait_s),
            prefill_ms=_ms(prefill_s),
            itl_p50_ms=req.itl_p50_ms(), slo_ok=req.slo_ok)

    def kv_numerics(self, allocated_only: bool = True) -> dict:
        """Per-page dynamic-range ledger over the live KV pools
        (``observability.numerics.kv_page_ledger``): the int8-KV
        quantization-readiness evidence, read from whatever pools the
        decode thread last published.  Pools are replaced (not mutated
        in place) by prefill/decode, so reading the reference from
        another thread is safe — at worst one step stale."""
        from deeplearning4j_tpu.observability import numerics
        pools = self._pools
        if pools is None:
            return {}
        allocated = None
        if allocated_only:
            # each kind of pool has page ids of its own
            allocated = {kind: self.cache.allocated_pages(kind)
                         for kind in self.cache.KINDS}
        return numerics.kv_page_ledger(pools, allocated=allocated)

    # ----------------------------------------------------------------- stats
    def stats(self) -> dict:
        return {
            "model": self.default_model,
            "models": self.models.as_dict(),
            "scheduler": self.scheduler.as_dict(),
            "prefill_buckets": list(self.prefill_buckets),
            "decode_thread_alive": (self._thread is not None
                                    and self._thread.is_alive()),
            "phases": self.phases.as_dict(),
            "busy_wall_s": round(self.busy_wall_s, 6),
            "slo": self.slo.as_dict(),
        }

    def fleet_publisher(self, worker_id: str, **kw):
        """A ``TelemetryPublisher`` pre-wired to this engine: local
        registry, SLO tracker, one-locked-snapshot prefix-cache stats,
        and the scheduler state dict.  Caller supplies the transport
        (``broker=`` or ``url=``) and calls ``start()``.  Reads only
        host-side state — publishing never touches the device."""
        from deeplearning4j_tpu.observability.fleet import (
            TelemetryPublisher,
        )
        kw.setdefault("registry", self.metrics.registry)
        kw.setdefault("slo", self.slo)
        if self.prefix_cache is not None:
            kw.setdefault("prefix_cache", self.prefix_cache)
        kw.setdefault("state_fn",
                      lambda: {"scheduler": self.scheduler.as_dict()})
        return TelemetryPublisher(worker_id, **kw)

    def cache_stats(self) -> dict:
        """The ``GET /generation/cache`` payload: allocator occupancy
        plus the persistent prefix cache's tree/host-tier stats (null
        when running the legacy free-on-release policy)."""
        return {
            "cache": self.cache.as_dict(),
            "prefix_cache": (self.prefix_cache.stats()
                             if self.prefix_cache is not None else None),
        }


def _copy_to_host_async(sampled) -> None:
    """Start the transfer of what a program sampled as soon as the program
    is dispatched, so the harvest finds it on the host."""
    for a in jax.tree_util.tree_leaves(sampled):
        a.copy_to_host_async()


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds * 1e3, 3)


def _base_key(seed: int) -> np.ndarray:
    """A request's raw uint32 base PRNG key (folded per token index on
    device — see ``utils.sampling.sample_tokens``), made on the host: what
    ``jax.random.PRNGKey(seed)`` holds for the default threefry key, with
    no program and no transfer in the way of admission.  The seed wraps
    to the integer width of the process (``jax_enable_x64``); its high
    word is 0 in 32-bit mode (tests/test_decode_loop.py proves both)."""
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)

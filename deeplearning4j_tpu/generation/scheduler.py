"""Iteration-level decode scheduling: requests join/leave a RUNNING batch.

The PR-2 batcher composes whole requests into one forward pass; that is
the wrong granularity for autoregressive decode, where a 500-token
completion would pin its batch slot for the whole tail while finished
requests' lanes idle.  ``DecodeScheduler`` schedules at ITERATION
granularity (Orca/vLLM): every decode step serves whatever requests are
active RIGHT NOW — new arrivals prefill into free slots between steps,
finished/cancelled/expired requests free their slot and pages
mid-flight, and the batch never drains to restart.

Admission is the only capacity gate: a request is admitted when a slot
is free AND its full page budget (prompt + max-new-tokens, minus any
shared prefix) fits the pool, so decode can never stall mid-flight on
pages.  The bounded pending queue sheds with the serving-stack errors
(429 ``QueueFullError`` / 503 ``ShuttingDownError`` / 504
``DeadlineExceededError``) instead of ever hanging a caller.

The engine runs its loop ONE STEP AHEAD (``generation/engine.py``): a
step is dispatched from ids still on the device while the previous one's
are on their way to the host.  So what a slot needs is split by when it
is known.  ``install`` and ``advance`` move the mirrors at DISPATCH (the
position, the token index, the generated count; a row whose last token by
``max_new_tokens`` was just dispatched leaves the next step there and
then).  ``first_token`` and ``harvest_step`` deliver at HARVEST, where
the token's value is known: a stop token, a cancel or a passed deadline
is found one step late, and the row's part of the step then in flight is
dropped when that step is harvested.

Threading: ``submit``/``cancel`` run on client threads and only touch
the pending deque + per-request flags (lock-guarded); everything else
(slots, block tables, the page allocator) is owned by the engine's
single decode thread.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import List, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.observability.tracing import new_trace_id
from deeplearning4j_tpu.serving.admission import (
    AdmissionController, DeadlineExceededError, ShuttingDownError,
)
from deeplearning4j_tpu.generation.paged_cache import (
    PagedKVCache, PageExhaustedError,
)

_DONE = object()   # stream sentinel


class GenerationRequest:
    """One generation request: client-facing handle + scheduler state.

    Clients read ``stream()`` / ``tokens()`` / ``cancel()``; everything
    else belongs to the scheduler.  Tokens are delivered per decode
    step, so ``stream()`` yields them as they are generated."""

    def __init__(self, prompt: Sequence[int], max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0,
                 deadline_s: float = 60.0, stop_token: Optional[int] = None,
                 trace_id: Optional[str] = None):
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} must be >= 1")
        self.prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not self.prompt:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k) if top_k is not None else 0
        self.top_p = float(top_p) if top_p is not None else 1.0
        if self.top_k < 0:
            raise ValueError(f"top_k={top_k} must be >= 1 (or None)")
        self.seed = int(seed)
        self.deadline = time.monotonic() + float(deadline_s)
        self.stop_token = None if stop_token is None else int(stop_token)
        self.trace_id = trace_id or new_trace_id()
        self.submitted = time.perf_counter()
        self.ttft_s: Optional[float] = None
        # submit -> picked by next_admittable; the rest of TTFT is prefill
        self.queue_wait_s: Optional[float] = None
        self.itl_s: List[float] = []    # gaps between delivered tokens
        self._last_token_t: Optional[float] = None
        self.slo_ok: Optional[bool] = None   # set by the engine's SLOTracker
        self.finish_reason: Optional[str] = None   # length|stop|cancelled…
        self.tokens: List[int] = []
        self.error: Optional[Exception] = None
        self.done = threading.Event()
        self.cancelled = False          # client flag, polled per step
        self._stream: "queue.Queue" = queue.Queue()
        # scheduler-owned (decode thread only)
        self.slot: Optional[int] = None
        self.pages: List[int] = []
        self.shared_len = 0
        self.cache_admit = None   # AdmitResult when retention is active

    # ----------------------------------------------------------- client API
    def cancel(self) -> None:
        """Ask the scheduler to drop this request at the next step
        boundary (its pages free mid-flight; already-streamed tokens
        stand)."""
        self.cancelled = True

    def stream(self, timeout: Optional[float] = None):
        """Yield token ids as they are generated; raises the request's
        terminal error (shed/deadline/model failure), if any, after the
        last delivered token."""
        while True:
            item = self._stream.get(timeout=timeout)
            if item is _DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the request finishes; returns all generated
        tokens or raises the terminal error."""
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"generation still running [trace {self.trace_id}]")
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    # -------------------------------------------------------- delivery side
    def _deliver(self, token: int) -> None:
        now = time.perf_counter()
        if self.ttft_s is None:
            self.ttft_s = now - self.submitted
        else:
            self.itl_s.append(now - self._last_token_t)
        self._last_token_t = now
        self.tokens.append(int(token))
        self._stream.put(int(token))

    def itl_p50_ms(self) -> Optional[float]:
        """Median inter-token gap in ms (None before the second token) —
        the per-request SLO evidence the access log carries."""
        if not self.itl_s:
            return None
        vs = sorted(self.itl_s)
        mid = len(vs) // 2
        p50 = vs[mid] if len(vs) % 2 else 0.5 * (vs[mid - 1] + vs[mid])
        return round(p50 * 1e3, 3)

    def _finish(self, reason: str, error: Optional[Exception] = None) -> None:
        self.finish_reason = reason
        self.error = error
        self._release_waiters()

    def _release_waiters(self) -> None:
        self._stream.put(_DONE)
        self.done.set()

    def as_dict(self) -> dict:
        return {"trace_id": self.trace_id, "prompt_tokens": len(self.prompt),
                "generated": len(self.tokens),
                "max_new_tokens": self.max_new_tokens,
                "finish_reason": self.finish_reason,
                "ttft_ms": (round(self.ttft_s * 1e3, 3)
                            if self.ttft_s is not None else None),
                "itl_p50_ms": self.itl_p50_ms(),
                "slo_ok": self.slo_ok}


class _Slot:
    """Decode-thread-side state of one running request."""

    __slots__ = ("req", "generated", "running")

    def __init__(self, req: GenerationRequest):
        self.req = req
        # tokens sampled by the programs DISPATCHED so far (the prefill
        # samples token 0); the delivered ones are req.tokens
        self.generated = 1
        # in the next decode step: False once the program that samples its
        # last token by length is dispatched (the slot is held until that
        # token is harvested and delivered)
        self.running = req.max_new_tokens > 1


class DecodeScheduler:
    """Slots + pending queue + page allocator (see module docstring)."""

    def __init__(self, cache: PagedKVCache, *, slots: int,
                 max_queue: int = 64, default_deadline_s: float = 60.0,
                 metrics=None):
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        self.cache = cache
        self.num_slots = int(slots)
        self.admission = AdmissionController(
            max_queue=max_queue, default_deadline_s=default_deadline_s,
            metrics=metrics)
        self.metrics = metrics
        # terminal hook (engine accounting): called once per request on
        # ANY terminal path, BEFORE the request's done event is set — so
        # per-request verdicts the hook computes (slo_ok) are visible the
        # moment result()/stream() return
        self.on_finish = None
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._pending: "deque[GenerationRequest]" = deque()
        self._stopping = False
        self.slots: List[Optional[_Slot]] = [None] * self.num_slots
        # the decode step's host-side mirror arrays, updated in place on
        # admit/dispatch/retire; a dispatch takes copies (step_inputs).
        # The last sampled ids have no mirror: they stay on the device
        self.block = np.zeros((self.num_slots, cache.table_width), np.int32)
        self.pos = np.zeros(self.num_slots, np.int32)
        self.keys = np.zeros((self.num_slots, 2), np.uint32)
        self.tok_idx = np.zeros(self.num_slots, np.int32)
        self.temps = np.zeros(self.num_slots, np.float32)
        self.top_ks = np.zeros(self.num_slots, np.int32)
        self.top_ps = np.ones(self.num_slots, np.float32)

    # ----------------------------------------------------------- client side
    def submit(self, req: GenerationRequest) -> GenerationRequest:
        """Admission-checked enqueue (client threads).  A request whose
        worst-case page budget can NEVER fit the pool fails immediately
        (ValueError — resubmitting cannot help); a full pending queue
        sheds 429; shutdown sheds 503."""
        worst = self.cache.pages_needed(
            len(req.prompt) + req.max_new_tokens - 1)
        if worst > self.cache.pages_per_slot:
            raise ValueError(
                f"request needs {worst} pages but a slot holds "
                f"{self.cache.pages_per_slot} "
                f"(max_context={self.cache.max_context})")
        with self._wake:
            self.admission.check_admit(len(self._pending), self._stopping,
                                       trace_id=req.trace_id)
            self._pending.append(req)
            self._wake.notify_all()
        return req

    def wait_for_work(self, timeout: float) -> None:
        """Decode-thread idle wait: returns early when a request arrives
        or stop is requested."""
        with self._wake:
            if not self._pending and not self._stopping:
                self._wake.wait(timeout)

    def reopen(self) -> None:
        """Re-arm admission after a shutdown (engine restart)."""
        with self._wake:
            self._stopping = False

    def begin_shutdown(self, drain_pending: bool) -> None:
        """Stop admitting.  Without ``drain_pending`` every queued
        request fails 503 now; active requests are the engine's to
        finish or fail."""
        with self._wake:
            self._stopping = True
            pending = list(self._pending) if not drain_pending else []
            if not drain_pending:
                self._pending.clear()
            self._wake.notify_all()
        for req in pending:
            err = self.admission.shed(ShuttingDownError,
                                      "engine is shutting down",
                                      trace_id=req.trace_id)
            self._terminate(req, "shutdown", err)

    def _terminate(self, req: GenerationRequest, reason: str,
                   error: Optional[Exception] = None) -> None:
        if req.done.is_set() or req.finish_reason is not None:
            return   # already terminal (stop() races the loop's own end)
        req.finish_reason = reason
        req.error = error
        try:
            # accounting BEFORE the waiters wake: the hook stamps the
            # request (slo_ok) and a client reading result() right after
            # done.set() must see the stamp, not race it
            if self.on_finish is not None:
                self.on_finish(req)
        finally:
            req._release_waiters()

    @property
    def queued(self) -> int:
        with self._lock:
            return len(self._pending)

    # ----------------------------------------------------- decode-thread side
    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    @property
    def has_work(self) -> bool:
        with self._lock:
            pending = bool(self._pending)
        return pending or any(s is not None for s in self.slots)

    def purge_pending(self, now: Optional[float] = None) -> List[GenerationRequest]:
        """Fail queued requests whose deadline passed without ever
        running (504, no forward pass spent) — the queue-side purge the
        PR-2 batcher does for predict."""
        now = time.monotonic() if now is None else now
        out: List[GenerationRequest] = []
        with self._lock:
            keep: "deque[GenerationRequest]" = deque()
            for req in self._pending:
                if req.cancelled or now > req.deadline:
                    out.append(req)
                else:
                    keep.append(req)
            self._pending = keep
        for req in out:
            if req.cancelled:
                self._terminate(req, "cancelled")
            else:
                err = self.admission.shed(
                    DeadlineExceededError,
                    "deadline expired while queued for a decode slot",
                    trace_id=req.trace_id)
                self._terminate(req, "deadline", err)
        return out

    def next_admittable(self) -> Optional[GenerationRequest]:
        """Pop the oldest pending request IF a slot is free and its page
        budget fits (allocates pages + a slot; the caller prefills it
        immediately).  FIFO: a head request that doesn't fit blocks
        later ones — admission order is completion-order fairness, not
        best-fit packing.

        With a retention policy installed, admission is cache-aware: the
        radix tree prices the request at ⌈suffix/page⌉ instead of
        ⌈prompt/page⌉ on a hit, refs the matched pages before anything
        can evict them, and may evict/offload cold unpinned tree nodes
        to make room — ``PageExhaustedError`` then means even eviction
        could not free enough."""
        free = next((i for i, s in enumerate(self.slots) if s is None), None)
        if free is None:
            return None
        with self._lock:
            if not self._pending:
                return None
            req = self._pending[0]
            admit_result = None
            try:
                # never-fits requests were rejected at submit(), so the
                # only failure here is transient pool pressure
                if self.cache.retention is not None:
                    admit_result = self.cache.retention.admit(
                        req.prompt, req.max_new_tokens)
                    pages = admit_result.pages
                    shared_len = admit_result.shared_len
                else:
                    pages, shared_len = self.cache.admit(req.prompt,
                                                         req.max_new_tokens)
            except PageExhaustedError:
                return None     # keep queued; pages free as slots retire
            self._pending.popleft()
        req.queue_wait_s = time.perf_counter() - req.submitted
        req.slot = free
        req.pages = pages
        req.shared_len = shared_len
        req.cache_admit = admit_result
        return req

    def fail_admitted(self, req: GenerationRequest,
                      error: Exception) -> None:
        """Terminal path for a request that was admitted (pages + slot
        reserved) but whose PREFILL failed before ``install``: free the
        pages (which also drops any prefix-index entries registered for
        its never-written pages) and release the waiters — without this
        the request is invisible to ``evict_all`` and would hang its
        clients forever while leaking its pages."""
        if req.cache_admit is not None:
            # radix nodes this admission created were never prefilled;
            # drop them (and the tree's refs) before the request's own
            # refs go, or a later match would serve unwritten pages
            self.cache.retention.forget(req.cache_admit)
            req.cache_admit = None
        self.cache.free(req.pages)
        req.pages = []
        req.slot = None
        self._terminate(req, "error", error)
        if self.metrics is not None:
            self.metrics.evictions.inc(reason="error")

    def install(self, req: GenerationRequest, base_key: np.ndarray) -> None:
        """Bind an admitted request to its slot once its prefill is
        DISPATCHED: the mirror arrays carry it from the next decode step
        on.  Its first token is still on the device (``first_token``
        delivers it); a request of one token never runs a decode step, so
        its lane stays parked."""
        i = req.slot
        slot = self.slots[i] = _Slot(req)
        if not slot.running:
            return
        self.block[i] = self.cache.block_row(req.pages)
        self.pos[i] = len(req.prompt)    # where the NEXT write lands
        self.keys[i] = base_key
        self.tok_idx[i] = 1
        self.temps[i] = req.temperature
        self.top_ks[i] = req.top_k
        self.top_ps[i] = req.top_p

    def running_rows(self) -> "List[Tuple[int, _Slot]]":
        """``(lane, slot)`` of the rows the next decode step serves."""
        return [(i, s) for i, s in enumerate(self.slots)
                if s is not None and s.running]

    def step_inputs(self) -> Tuple[np.ndarray, ...]:
        """The mirrors as one dispatch takes them, in the decode program's
        order around the ids: ``(block, pos), (keys, tok_idx, temps,
        top_ks, top_ps)``.  Copies: the mirrors move on while the step is
        in flight, and a backend may read a NumPy argument in place (the
        CPU's does, without a copy)."""
        return ((self.block.copy(), self.pos.copy()),
                (self.keys.copy(), self.tok_idx.copy(), self.temps.copy(),
                 self.top_ks.copy(), self.top_ps.copy()))

    def advance(self, rows: "List[Tuple[int, _Slot]]") -> None:
        """A decode step over ``rows`` was dispatched: move what the next
        dispatch needs and the host already knows.  A row whose last token
        by length this step samples is not in the next one."""
        for i, slot in rows:
            slot.generated += 1
            if slot.generated >= slot.req.max_new_tokens:
                slot.running = False
                self._park(i)
            else:
                self.pos[i] += 1
                self.tok_idx[i] += 1

    def first_token(self, req: GenerationRequest, tok: int) -> None:
        """Deliver the token a request's prefill sampled."""
        req._deliver(tok)
        self._finish_if_ended(req.slot, tok)

    def harvest_step(self, rows: "List[Tuple[int, _Slot]]",
                     sampled: np.ndarray) -> int:
        """Deliver one decode step's tokens to the rows it was dispatched
        with and retire what ended; returns the number delivered.  A row
        that an earlier harvest ended (stop token, cancel, deadline) while
        this step was already in flight has run one step too many: its id
        is dropped.  Its K/V row went to a page the request still held
        when the step was dispatched, or to one whose next owner's
        programs run after this step."""
        delivered = 0
        now = time.monotonic()
        for i, slot in rows:
            req = slot.req
            if self.slots[i] is not slot:
                if self.metrics is not None:
                    self.metrics.discarded_rows.inc(reason=req.finish_reason)
                continue
            tok = int(sampled[i])
            req._deliver(tok)
            if self.metrics is not None and req.itl_s:
                self.metrics.inter_token.observe(req.itl_s[-1])
            delivered += 1
            if not self._finish_if_ended(i, tok) and (
                    req.cancelled or now > req.deadline):
                self._evict(i, "cancelled" if req.cancelled else "deadline")
        return delivered

    def _finish_if_ended(self, i: int, tok: int) -> bool:
        req = self.slots[i].req
        if req.stop_token is not None and tok == req.stop_token:
            self._retire(i, "stop")
            return True
        if len(req.tokens) >= req.max_new_tokens:
            self._retire(i, "length")
            return True
        return False

    def _retire(self, i: int, reason: str) -> None:
        slot = self.slots[i]
        self._release(i)
        self._terminate(slot.req, reason)

    def _evict(self, i: int, reason: str,
               error: Optional[Exception] = None) -> None:
        """Mid-flight removal (deadline/cancel/shutdown/error): pages
        free NOW, the stream ends with the matching error (except
        cancel, which is a clean client-requested end)."""
        slot = self.slots[i]
        req = slot.req
        self._release(i)
        if reason == "deadline":
            err = self.admission.shed(
                DeadlineExceededError,
                f"deadline expired after {len(req.tokens)} tokens",
                trace_id=req.trace_id)
        elif reason == "shutdown":
            err = self.admission.shed(ShuttingDownError,
                                      "engine stopped mid-generation",
                                      trace_id=req.trace_id)
        elif reason == "error":
            err = error if error is not None else RuntimeError(
                f"decode step failed [trace {req.trace_id}]")
        else:
            err = None
        self._terminate(req, reason, err)
        if self.metrics is not None:
            self.metrics.evictions.inc(reason=reason)

    def _release(self, i: int) -> None:
        """Free the slot and its pages NOW, whatever is in flight: the
        device runs programs in the order they were dispatched, so a
        program that reuses these pages runs after the last that read or
        wrote them."""
        slot = self.slots[i]
        self.cache.free(slot.req.pages)
        self.slots[i] = None
        self._park(i)

    def _park(self, i: int) -> None:
        """Point the lane at the trash page with greedy sampling."""
        self.block[i] = self.cache.block_row([])
        self.pos[i] = 0
        self.keys[i] = 0
        self.tok_idx[i] = 0
        self.temps[i] = 0.0
        self.top_ks[i] = 0
        self.top_ps[i] = 1.0

    def evict_all(self, reason: str,
                  error: Optional[Exception] = None) -> None:
        for i in self.active_slots():
            self._evict(i, reason, error)

    def as_dict(self) -> dict:
        return {"slots": self.num_slots,
                "active": len(self.active_slots()),
                "queued": self.queued,
                "cache": self.cache.as_dict(),
                "requests": [s.req.as_dict()
                             for s in self.slots if s is not None]}

"""The decode loop one step ahead of the device (``generation/engine.py``).

The engine dispatches step N+1 from ids still on the device and only then
harvests step N; admission dispatches a prefill and does not wait for it.
What that must leave as it was:

- every request receives exactly the tokens a step-by-step loop gives it
  (each program awaited, ids through the host), greedy or drawn, never more
  than ``max_new_tokens``, never one past its ``stop_token``, with requests
  joining and leaving mid-run;
- a row that ends by stop token, cancel or deadline runs at most one step
  more, whose id is dropped (``dl4j_decode_discarded_rows_total``); its
  pages are free at once and the next request in its slot and pages is
  served right;
- an exception at a harvest drops the step in flight, evicts, reseeds, and
  the next request is served; ``deploy`` and both kinds of ``stop`` with a
  step in flight;
- the host-made base key is ``jax.random.PRNGKey(seed)``;
- nothing compiles after ``start()`` with the ids a device array.
"""

import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.generation import GenerationEngine
from deeplearning4j_tpu.generation.engine import _base_key
from deeplearning4j_tpu.generation.paged_cache import TRASH_PAGE
from deeplearning4j_tpu.generation.programs import GenerationPrograms
from deeplearning4j_tpu.models.zoo import transformer_char_lm
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.serving.admission import (
    DeadlineExceededError, ShuttingDownError,
)

pytestmark = pytest.mark.generation

VOCAB = 29
SLOTS, PAGE, CONTEXT = 4, 4, 32
BUCKETS = (8, 16, 32)
POLICIES = {
    "greedy": {},
    "temperature": {"temperature": 0.9},
    "top_k": {"temperature": 1.1, "top_k": 5},
    "top_p": {"temperature": 0.8, "top_k": 9, "top_p": 0.85},
}


def small_lm(seed=12345):
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=seed)


@pytest.fixture(scope="module")
def lm():
    return small_lm()


def new_engine(net, slots=SLOTS, **kw):
    kw.setdefault("deadline_s", 60.0)
    return GenerationEngine(net, slots=slots, page_size=PAGE,
                            max_context=CONTEXT, prefill_buckets=BUCKETS,
                            max_queue=64, registry=MetricsRegistry(), **kw)


@pytest.fixture(scope="module")
def engine(lm):
    eng = new_engine(lm).start()
    yield eng
    eng.stop()


class StepByStep:
    """The loop as it was before it ran ahead, by hand: one request alone in
    lane 0 of the same programs, every program awaited and its ids taken
    through the host before the next is dispatched."""

    def __init__(self, net):
        self.pages = CONTEXT // PAGE
        self.progs = GenerationPrograms(
            net, slots=SLOTS, pages_per_slot=self.pages, page_size=PAGE,
            num_pages=SLOTS * self.pages + 1, prefill_buckets=BUCKETS)

    def __call__(self, prompt, n, temperature=0.0, top_k=None, top_p=None,
                 seed=0, stop_token=None):
        p = self.progs
        params, state, pools = (p.serving_params(), p.net.net_state,
                                p.fresh_pools())
        key = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        need = -(-(len(prompt) + n - 1) // PAGE)
        row = np.full(self.pages, TRASH_PAGE, np.int32)
        row[:need] = np.arange(1, need + 1)
        policy = (np.float32(temperature), np.int32(top_k or 0),
                  np.float32(1.0 if top_p is None else top_p))
        bucket = p.bucket_for(len(prompt))
        chunk = np.zeros((1, bucket), np.int32)
        chunk[0, :len(prompt)] = prompt
        pools, tok, _ = p._prefill[bucket](
            params, state, pools, row[None], np.zeros(1, np.int32),
            np.int32(len(prompt) - 1), chunk, key[None],
            np.zeros(1, np.int32), *(a[None] for a in policy),
            p.fresh_ids(), np.int32(0))
        out = [int(np.asarray(tok)[0])]
        block = np.full((SLOTS, self.pages), TRASH_PAGE, np.int32)
        block[0] = row
        keys = np.zeros((SLOTS, 2), np.uint32)
        keys[0] = key
        lanes = [np.zeros(SLOTS, a.dtype) for a in policy]
        lanes[2][:] = 1.0
        for lane, a in zip(lanes, policy):
            lane[0] = a
        while len(out) < n and out[-1] != stop_token:
            ids = np.zeros(SLOTS, np.int32)
            ids[0] = out[-1]
            pos = np.zeros(SLOTS, np.int32)
            pos[0] = len(prompt) + len(out) - 1
            idx = np.zeros(SLOTS, np.int32)
            idx[0] = len(out)
            pools, sampled = p._decode(params, state, pools, block, pos,
                                       ids, keys, idx, *lanes)
            out.append(int(np.asarray(sampled)[0]))
        return out


@pytest.fixture(scope="module")
def reference(lm):
    return StepByStep(lm)


def value(eng, name, **labels):
    return eng.metrics.registry.get_value(name, **labels) or 0.0


def wait_until(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.002)
    assert cond()


def settled(eng):
    """Nothing queued, running or in flight, every page back."""
    wait_until(lambda: not eng._has_work()
               and eng.cache.used_pages == 0)
    return True


# ------------------------------------------------------------- same tokens
@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_served_tokens_equal_the_step_by_step_loop(engine, reference, rng,
                                                   policy):
    """Requests of every length from 1 join a running batch at different
    steps and leave it at different steps; each gets the tokens the
    step-by-step loop gives it alone."""
    prompts = [rng.randint(0, VOCAB, rng.randint(1, 12)).tolist()
               for _ in range(10)]
    lens = [1, 2] + [int(rng.randint(3, 14)) for _ in prompts[2:]]
    kws = [dict(POLICIES[policy], seed=100 + i) for i in range(len(prompts))]
    handles = []
    for i, (p, n, kw) in enumerate(zip(prompts, lens, kws)):
        handles.append(engine.submit(p, n, **kw))
        if i % 3 == 0:
            time.sleep(0.003)
    for h, p, n, kw in zip(handles, prompts, lens, kws):
        assert h.result(timeout=60) == reference(p, n, **kw)
        assert h.finish_reason == "length" and len(h.tokens) == n


# ------------------------------------------------------------- stop token
@pytest.mark.parametrize("at", ("first", "third"))
def test_stop_token_row_ends_there_and_its_slot_and_pages_serve_the_next(
        lm, reference, rng, at):
    """One slot: the row ends exactly at its stop token (the prefill's own
    sample, or a decode step's), delivers nothing after it though one more
    step was in flight, and the request that takes its slot and pages right
    away is served as if alone."""
    eng = new_engine(lm, slots=1).start()
    try:
        prompt = rng.randint(0, VOCAB, 6).tolist()
        free = reference(prompt, 12)
        stop = free[0] if at == "first" else free[2]
        cut = free.index(stop) + 1
        h = eng.submit(prompt, 12, stop_token=stop)
        nxt_prompt = rng.randint(0, VOCAB, 9).tolist()
        nxt = eng.submit(nxt_prompt, 10, temperature=0.7, seed=5)
        assert h.result(timeout=60) == free[:cut]
        assert h.finish_reason == "stop"
        assert h.tokens == reference(prompt, 12, stop_token=stop)
        assert nxt.result(timeout=60) == reference(
            nxt_prompt, 10, temperature=0.7, seed=5)
        assert settled(eng)
        # the step after the stop token was dispatched before it was seen
        assert value(eng, "dl4j_decode_discarded_rows_total",
                     reason="stop") == 1
        assert list(h.stream(timeout=1)) == free[:cut]   # and nothing more
    finally:
        eng.stop()


# ------------------------------------------------------ cancel and deadline
@pytest.mark.parametrize("how", ("cancelled", "deadline"))
def test_cancel_and_deadline_with_a_step_in_flight(lm, reference, rng, how):
    eng = new_engine(lm, slots=2, decode_step_floor_s=0.04).start()
    try:
        prompt = rng.randint(0, VOCAB, 5).tolist()
        other_prompt = rng.randint(0, VOCAB, 7).tolist()
        other = eng.submit(other_prompt, 20)
        h = eng.submit(prompt, 26,       # 26 steps of 0.04 s outlast 0.6 s
                       deadline_s=0.6 if how == "deadline" else None)
        stream = h.stream(timeout=30)
        got = [next(stream)]
        if how == "cancelled":
            h.cancel()
            got += list(stream)
        else:
            with pytest.raises(DeadlineExceededError):
                for tok in stream:
                    got.append(tok)
        assert h.finish_reason == how
        full = reference(prompt, 26)
        assert 0 < len(got) < 26 and got == full[:len(got)]
        # the neighbour never noticed
        assert other.result(timeout=60) == reference(other_prompt, 20)
        assert settled(eng)
        # the row was in the step dispatched before its end was seen
        assert value(eng, "dl4j_decode_discarded_rows_total",
                     reason=how) == 1
        assert value(eng, "dl4j_decode_evicted_total", reason=how) == 1
    finally:
        eng.stop()


# ----------------------------------------------------------------- errors
def test_exception_at_a_harvest_drops_the_step_in_flight_and_reseeds(
        lm, reference, rng):
    eng = new_engine(lm, slots=2).start()
    try:
        seen = {"calls": 0, "in_flight": None}
        real = eng._harvest

        def exploding(step):
            seen["calls"] += 1
            if seen["calls"] == 3:
                seen["in_flight"] = eng._in_flight
                raise RuntimeError("injected harvest failure")
            return real(step)

        eng._harvest = exploding
        doomed = [eng.submit(rng.randint(0, VOCAB, 5).tolist(), 20)
                  for _ in range(2)]
        for h in doomed:
            with pytest.raises(RuntimeError, match="injected"):
                h.result(timeout=30)
            assert h.finish_reason == "error"
        # a step was in flight behind the one that failed, and was dropped
        assert seen["in_flight"] is not None
        assert settled(eng) and eng._in_flight is None
        prompt = rng.randint(0, VOCAB, 8).tolist()
        assert eng.generate(prompt, 9).tolist() == reference(prompt, 9)
        # the step that follows an error finds nothing in flight
        assert value(eng, "dl4j_decode_dispatch_total", mode="sync") >= 2
    finally:
        eng.stop()


def test_prefill_that_fails_on_dispatch_goes_through_fail_admitted(
        lm, reference, rng):
    """With a batch running and a step in flight, a prefill that raises
    fails its own request through ``fail_admitted`` (pages back, waiter
    released), takes the batch with it as it always has, and the engine
    serves on."""
    eng = new_engine(lm, slots=2).start()
    try:
        running = eng.submit(rng.randint(0, VOCAB, 5).tolist(), 24)
        next(iter(running.stream(timeout=30)))
        progs = eng._programs[eng.models.active("default").key]
        real = progs.prefill
        progs.prefill = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("injected prefill failure"))
        doomed = eng.submit([1, 2, 3], 4)
        with pytest.raises(RuntimeError, match="injected"):
            doomed.result(timeout=30)
        progs.prefill = real
        with pytest.raises(RuntimeError, match="injected"):
            running.result(timeout=30)
        assert settled(eng)
        prompt = rng.randint(0, VOCAB, 4).tolist()
        assert eng.generate(prompt, 6).tolist() == reference(prompt, 6)
    finally:
        eng.stop()


# ------------------------------------------------------------------- swap
def test_swap_with_a_step_in_flight_keeps_streams_and_pages(
        lm, reference, rng):
    """``deploy`` warms the incoming version first, so the swap that can
    be timed to land between two dispatches of running streams is the
    ``rollback`` that follows it (its programs are warm).  The streams keep
    their slots and pages and come out whole, what they got before the
    swap is the displaced weights', and what is admitted after it is
    served by the restored ones."""
    eng = new_engine(lm, slots=2, decode_step_floor_s=0.04).start()
    try:
        newer = small_lm(seed=777)
        eng.deploy("default", newer, retain_old=True)
        prompts = [rng.randint(0, VOCAB, 6).tolist() for _ in range(2)]
        handles = [eng.submit(p, 26) for p in prompts]
        streams = [h.stream(timeout=60) for h in handles]
        before = [[next(s) for _ in range(3)] for s in streams]
        pages = [list(h.pages) for h in handles]
        assert eng._in_flight is not None
        eng.rollback()
        assert not any(h.done.is_set() for h in handles)   # mid-stream
        assert [list(h.pages) for h in handles] == pages
        displaced = StepByStep(newer)
        for h, s, b, p in zip(handles, streams, before, prompts):
            assert len(b + list(s)) == 26 and h.finish_reason == "length"
            assert b == displaced(p, 26)[:3]
        assert value(eng, "dl4j_decode_dispatch_total", mode="ahead") >= 24
        prompt = rng.randint(0, VOCAB, 7).tolist()
        assert eng.generate(prompt, 8).tolist() == reference(prompt, 8)
        assert reference(prompt, 8) != displaced(prompt, 8)
    finally:
        eng.stop()


# ------------------------------------------------------------------- stop
@pytest.mark.parametrize("drain", (True, False))
def test_stop_with_a_step_in_flight(lm, reference, rng, drain):
    eng = new_engine(lm, slots=2, decode_step_floor_s=0.05).start()
    prompts = [rng.randint(0, VOCAB, 5).tolist() for _ in range(3)]
    handles = [eng.submit(p, 18) for p in prompts]     # two run, one waits
    next(iter(handles[0].stream(timeout=30)))
    wait_until(lambda: eng._in_flight is not None)
    eng.stop(drain=drain, timeout=30.0)
    assert eng._in_flight is None and not eng._firsts
    assert eng.cache.used_pages == 0
    if drain:       # what was in flight was harvested, the queue served
        for h, p in zip(handles, prompts):
            assert h.result(timeout=5) == reference(p, 18)
    else:           # nobody hangs; what was delivered is a true prefix
        for h, p in zip(handles, prompts):
            with pytest.raises(ShuttingDownError):
                h.result(timeout=5)
            assert h.tokens == reference(p, 18)[:len(h.tokens)]


# --------------------------------------------------------------- base key
@pytest.mark.parametrize("seed", (0, 1, 2 ** 31, 2 ** 32 - 1, 2 ** 32 + 5,
                                  -1))
def test_host_made_base_key_is_prngkey(seed):
    """No program and no transfer, the same two words: in the integer
    width the tests run in (x64) and in the 32-bit mode a server does."""
    for x64 in (True, False):
        with jax.enable_x64(x64):
            want = np.asarray(jax.random.PRNGKey(seed))
            got = _base_key(seed)
        assert got.dtype == np.uint32 and got.shape == (2,)
        np.testing.assert_array_equal(got, want)
    with jax.enable_x64(False):
        assert _base_key(seed).tolist() == [0, seed & 0xFFFFFFFF]


# ------------------------------------------------------------ no compiles
class Compiles:
    """XLA compilations by JAX's own monitoring events, as the benchmark's
    ``window_compiles.serve`` counts them."""

    def __init__(self):
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **_):
        if self.on and name.endswith("backend_compile_duration"):
            self.count += 1


def test_nothing_compiles_after_start_with_the_ids_on_the_device(lm, rng):
    compiles = Compiles()
    eng = new_engine(lm).start()
    try:
        mv = eng.models.active("default")
        warm = mv.detector.compile_count
        assert isinstance(eng._ids, jax.Array)
        compiles.on = True
        handles = [eng.submit(rng.randint(0, VOCAB, rng.randint(1, 20)),
                              int(rng.randint(1, 9)),
                              temperature=float(rng.rand() * 1.3),
                              top_k=int(rng.randint(0, 6)) or None,
                              stop_token=int(rng.randint(0, VOCAB)), seed=i)
                   for i in range(20)]
        for h in handles:
            h.result(timeout=60)
        compiles.on = False
        assert compiles.count == 0
        assert mv.detector.compile_count == warm
        assert mv.detector.recompile_count == 0
        progs = eng._programs[mv.key]
        sizes = [f._cache_size() for f in progs._prefill.values()]
        sizes.append(progs._decode._cache_size())
        assert sizes == [1] * len(sizes)
        assert isinstance(eng._ids, jax.Array)     # never came to the host
    finally:
        compiles.on = False
        eng.stop()


# --------------------------------------------------------------- counters
def test_ahead_dominates_under_a_standing_batch(lm, rng):
    """Four clients keep four slots full: the only steps dispatched with
    nothing in flight are the ones that follow an idle loop."""
    eng = new_engine(lm).start()
    try:
        stop = threading.Event()

        def client(cid):
            r = np.random.RandomState(cid)
            while not stop.is_set():
                eng.generate(r.randint(0, VOCAB, 6).tolist(),
                             int(r.randint(8, 20)))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(SLOTS)]
        [t.start() for t in threads]
        wait_until(lambda: value(eng, "dl4j_decode_steps_total") > 150, 60)
        stop.set()
        [t.join(30) for t in threads]
        ahead = value(eng, "dl4j_decode_dispatch_total", mode="ahead")
        sync = value(eng, "dl4j_decode_dispatch_total", mode="sync")
        assert settled(eng)
        assert ahead + sync == value(eng, "dl4j_decode_steps_total")
        assert ahead / (ahead + sync) > 0.95
        # no stop token, cancel or deadline: no row ran a step for nothing
        assert not any(value(eng, "dl4j_decode_discarded_rows_total",
                             reason=r)
                       for r in ("stop", "cancelled", "deadline"))
    finally:
        eng.stop()

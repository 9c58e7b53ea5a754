"""Persistent radix-tree prefix cache (`generation/prefix_cache.py`).

Acceptance oracles from the PR issue:

- a cached-hit decode is BIT-IDENTICAL to a cold prefill of the same
  prompt (and to the legacy free-on-release engine — the oracle path);
- a host-tier offload -> restore round-trip is bit-identical, at the
  numpy-transport unit level and through the live engine;
- refcount/pin/evict invariants hold under churn: no page freed while
  referenced, pinned nodes never evicted, double-unpin raises;
- hot-swap invalidation: no hit ever serves KV prefilled under
  displaced weights (and a forced stale match raises);
- page exhaustion still sheds 429 — admission never evicts a pinned or
  in-flight node to make room;
- a seeded randomized fuzzer drives admit/release/pin/unpin/offload/
  evict sequences against a model-checker dict.
"""

import json
import threading
import time

import http.client

import numpy as np
import pytest

from deeplearning4j_tpu.generation import (
    GenerationEngine, PagedKVCache, PageExhaustedError, PrefixCache,
    PrefixCacheConfig, StalePrefixError,
)
from deeplearning4j_tpu.models.zoo import transformer_char_lm
from deeplearning4j_tpu.serving.admission import QueueFullError

pytestmark = pytest.mark.prefix_cache

VOCAB = 29


@pytest.fixture(scope="module")
def lm():
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=12345)


@pytest.fixture(scope="module")
def lm2():
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=777)


@pytest.fixture(scope="module")
def engine(lm):
    eng = GenerationEngine(lm, slots=4, page_size=4, max_context=32,
                           max_queue=64, deadline_s=30.0,
                           prefix_cache=True)
    eng.start()
    yield eng
    eng.stop()


# ------------------------------------------------------- numpy-level plumbing
class NumpyTransport:
    """Unit-test pool transport: fake numpy pools, byte-exact slices."""

    def __init__(self, num_pages, page_size, feat=4):
        self.pools = {"att": {
            "pk": np.zeros((num_pages, 1, page_size, feat), np.float32),
            "pv": np.zeros((num_pages, 1, page_size, feat), np.float32)}}

    def page_bytes(self):
        c = self.pools["att"]
        return (c["pk"].nbytes + c["pv"].nbytes) // c["pk"].shape[0]

    def cache_read_page(self, page):
        c = self.pools["att"]
        return {"att": {"pk": c["pk"][page].copy(),
                        "pv": c["pv"][page].copy()}}

    def cache_write_page(self, page, payload):
        self.pools["att"]["pk"][page] = payload["att"]["pk"]
        self.pools["att"]["pv"][page] = payload["att"]["pv"]

    def stamp(self, page, value):
        self.pools["att"]["pk"][page] = value
        self.pools["att"]["pv"][page] = -value

    def read_stamp(self, page):
        return float(self.pools["att"]["pk"][page].flat[0])


def _mk(num_pages=17, page_size=4, pages_per_slot=8, budget=1 << 20):
    cache = PagedKVCache(num_pages, page_size, pages_per_slot)
    tp = NumpyTransport(num_pages, page_size)
    pc = PrefixCache(cache, host_budget_bytes=budget, transport=tp,
                     page_bytes=tp.page_bytes())
    pc.set_version("v1")
    cache.retention = pc
    return cache, pc, tp


def _stamp_fresh(pc, tp, prompt, res):
    """What the engine's prefill does to full prompt pages: write
    content that is a function of the WHOLE chain up to each page."""
    ps = pc.page_size
    for i in range(len(prompt) // ps):
        page = res.pages[i]
        if i >= res.shared_len // ps:
            tp.stamp(page, _chain_stamp(prompt, i))


def _chain_stamp(prompt, i):
    return float(hash(tuple(prompt[:(i + 1) * 4])) % 100003) + 1.0


# ------------------------------------------------------------ unit: admission
def test_admission_pricing_hit_cheaper_than_miss():
    """A hit is priced at ⌈suffix/page⌉: a pool too small for a cold
    admission still admits the same prompt when its prefix is cached."""
    cache, pc, tp = _mk(num_pages=9, page_size=4, pages_per_slot=8)
    prompt = list(range(12))
    res = pc.admit(prompt, 5)          # 12+5-1=16 -> 4 pages, 3 cached
    _stamp_fresh(pc, tp, prompt, res)
    assert res.shared_len == 0 and len(res.pages) == 4
    cache.free(res.pages)              # request leaves; tree keeps 3
    assert pc.resident_pages() == 3
    pin = pc.pin(prompt)               # cached prefix is un-evictable
    # an in-flight blocker takes 2 more: 3 of 8 pages left free
    blocker = pc.admit([100, 101, 102, 103, 104], 4)   # 2 pages
    _stamp_fresh(pc, tp, [100, 101, 102, 103, 104], blocker)
    assert cache.free_pages == 3
    # a cold 4-page admission finds no victim (blocker in flight,
    # prompt pinned, blocker's own node shares the in-flight page)
    with pytest.raises(PageExhaustedError):
        pc.admit([200 + i for i in range(12)], 5)
    # but the CACHED prompt matches 2 pages (the match cap leaves >= 1
    # prompt token to prefill) and only needs 2 fresh -> admits
    res2 = pc.admit(prompt, 5)
    assert res2.shared_len == 8 and len(res2.pages) == 4
    assert res2.pages[:2] == res.pages[:2]
    cache.free(res2.pages)
    cache.free(blocker.pages)
    pc.unpin(pin)


def test_mid_admission_hit_refs_before_eviction():
    """The matched nodes are ref'd before room-making runs, so the
    eviction pass can never free the very pages the hit points at —
    even when they are the coldest in the tree."""
    cache, pc, tp = _mk(num_pages=7, page_size=4, pages_per_slot=8,
                        budget=0)      # no host tier: evictions drop
    old = list(range(9))
    res = pc.admit(old, 8)             # 9+8-1=16 -> 4 pages, 2 cached
    _stamp_fresh(pc, tp, old, res)
    cache.free(res.pages)
    assert pc.resident_pages() == 2 and cache.free_pages == 4
    # a second prompt leaves `old`'s nodes the COLDEST in the tree
    filler = [50 + i for i in range(9)]
    res_f = pc.admit(filler, 8)
    _stamp_fresh(pc, tp, filler, res_f)
    cache.free(res_f.pages)
    assert cache.free_pages == 2 and pc.resident_pages() == 4
    # hit on `old` needing 4 fresh (2 free): matched pages are ref'd
    # FIRST, so room-making must victimize the WARMER filler nodes —
    # plain LRU without the ref step would evict the hit's own pages
    res2 = pc.admit(old, 16)           # 9+16-1=24 -> 6 pages
    assert res2.shared_len == 8
    for i in range(2):
        assert tp.read_stamp(res2.pages[i]) == _chain_stamp(old, i)
    assert pc.evictions.get("capacity", 0) == 2   # both filler nodes
    assert pc.resident_pages() == 2               # only old's remain
    cache.free(res2.pages)


def test_stale_version_match_raises():
    cache, pc, tp = _mk()
    prompt = list(range(8))
    res = pc.admit(prompt, 4)
    _stamp_fresh(pc, tp, prompt, res)
    cache.free(res.pages)
    pc.set_version("v2")   # swap WITHOUT the engine's invalidate
    with pytest.raises(StalePrefixError):
        pc.admit(prompt, 4)


def test_failed_prefill_forgets_created_nodes():
    """fail_admitted unwinds nodes the admission created — a later
    identical prompt must MISS (the pages were never prefilled)."""
    cache, pc, tp = _mk()
    prompt = list(range(8))
    res = pc.admit(prompt, 4)
    # prefill "fails": scheduler calls forget() then frees the pages
    pc.forget(res)
    cache.free(res.pages)
    assert pc.resident_pages() == 0
    assert cache.free_pages == cache.num_pages - 1
    res2 = pc.admit(prompt, 4)
    assert res2.shared_len == 0
    cache.free(res2.pages)


# -------------------------------------------------- unit: host tier + pinning
def test_offload_restore_round_trip_unit():
    """Evicted-to-host pages restore bit-identically, via the same
    chain-stamp content the model checker uses."""
    cache, pc, tp = _mk(num_pages=6, page_size=4, pages_per_slot=8)
    a = list(range(9))
    res = pc.admit(a, 8)               # 4 pages (2 become tree nodes)
    _stamp_fresh(pc, tp, a, res)
    cache.free(res.pages)
    assert cache.free_pages == 3
    # a second prompt needs 4: one of a's cold pages spills to host
    b = [20 + i for i in range(9)]
    res_b = pc.admit(b, 8)
    _stamp_fresh(pc, tp, b, res_b)
    cache.free(res_b.pages)
    assert pc.offload_total > 0 and pc.host_pages() > 0
    assert pc.host_bytes == pc.host_pages() * tp.page_bytes()
    # hitting `a` again restores from host — bit-identical stamps
    res_a = pc.admit(a, 8)
    assert res_a.shared_len == 8 and res_a.restored_pages > 0
    for i in range(2):
        assert tp.read_stamp(res_a.pages[i]) == _chain_stamp(a, i)
    assert pc.restore_total > 0
    cache.free(res_a.pages)


def test_matched_host_node_protected_during_room_making():
    """A host-tier hit has no device page to ref when admission starts,
    so the admission pins must keep room-making off it: without them
    `_host_has_room`'s drop pass picks the very node the restore loop
    is about to write back (it is childless, unpinned, and the coldest
    host leaf), detaching it from the tree mid-admission and nulling
    its payload."""
    # host budget of exactly ONE page: any further offload must first
    # drop a host leaf — and the only host leaf is the matched node
    cache, pc, tp = _mk(num_pages=6, page_size=4, pages_per_slot=8,
                        budget=128)
    assert tp.page_bytes() == 128
    a = list(range(9))
    res = pc.admit(a, 8)               # 4 pages, 2 become tree nodes
    _stamp_fresh(pc, tp, a, res)
    cache.free(res.pages)
    # b's admission victimizes a's coldest node -> offloaded to host
    b = [20 + i for i in range(9)]
    res_b = pc.admit(b, 8)
    _stamp_fresh(pc, tp, b, res_b)
    cache.free(res_b.pages)
    assert pc.host_pages() == 1 and res_b.offloaded_pages == 1
    # hitting `a` matches one resident + one HOST node and still needs
    # room; the full host tier must find its victims elsewhere
    res_a = pc.admit(a, 8)
    assert res_a.shared_len == 8 and res_a.restored_pages == 1
    for i in range(2):
        assert tp.read_stamp(res_a.pages[i]) == _chain_stamp(a, i)
    # the matched host node was never dropped — a cold resident b-node
    # was dropped outright instead (host tier full, budget 1 page)
    assert pc.evictions.get("host_capacity", 0) == 0
    assert pc.evictions.get("capacity", 0) == 1
    # restore emptied the tier; a mid-admission drop of the matched
    # node would have decremented host_bytes twice (negative bytes)
    assert pc.host_pages() == 0 and pc.host_bytes == 0
    # the admission pins were temporary: nothing stays pinned
    assert pc.pinned_pages() == 0
    cache.free(res_a.pages)


def test_host_budget_bounds_tier_then_drops():
    """Past the host budget the coldest host leaf is dropped for room;
    with budget 0 the tier never holds anything."""
    cache, pc, tp = _mk(num_pages=7, page_size=4, pages_per_slot=8,
                        budget=0)
    for base in (0, 20, 40):
        p = [base + i for i in range(8)]
        r = pc.admit(p, 9)
        _stamp_fresh(pc, tp, p, r)
        cache.free(r.pages)
    assert pc.host_pages() == 0 and pc.offload_total == 0
    assert pc.evictions.get("capacity", 0) > 0


def test_pinned_nodes_survive_pressure_and_unpin_releases():
    cache, pc, tp = _mk(num_pages=6, page_size=4, pages_per_slot=8,
                        budget=0)
    a = list(range(9))
    res = pc.admit(a, 8)
    _stamp_fresh(pc, tp, a, res)
    cache.free(res.pages)
    pin = pc.pin(a)
    assert pc.pinned_pages() == 2 and cache.free_pages == 3
    # pressure: another request would need a's pages evicted — pinned,
    # so admission fails instead of evicting them
    b = [20 + i for i in range(12)]
    with pytest.raises(PageExhaustedError):
        pc.admit(b, 5)                 # 4 pages, only 3 free
    assert pc.resident_pages() == 2    # a's nodes untouched
    res_a = pc.admit(a, 8)             # pinned prefix still hits
    assert res_a.shared_len == 8
    cache.free(res_a.pages)
    pc.unpin(pin)
    res_b = pc.admit(b, 5)             # now a's cold nodes may go
    cache.free(res_b.pages)
    with pytest.raises(KeyError):
        pc.unpin(pin)                  # double unpin raises


def test_double_unpin_raises_after_invalidate():
    """Invalidation empties pins' node lists but keeps the ids: the one
    legal unpin works, the second still raises."""
    cache, pc, tp = _mk()
    a = list(range(8))
    r = pc.admit(a, 4)
    _stamp_fresh(pc, tp, a, r)
    cache.free(r.pages)
    pin = pc.pin(a)
    pc.invalidate("swap")
    pc.unpin(pin)                      # legal (no-op on nodes)
    with pytest.raises(KeyError):
        pc.unpin(pin)


# ------------------------------------------------------------- seeded fuzzer
def test_stats_consistent_under_concurrent_eviction():
    """``GET /generation/cache`` and the fleet snapshot read
    prefix-cache stats through ``stats()``, which owns the tree lock —
    a stats walk racing admit/offload/invalidate churn must never
    report torn numbers (e.g. a node's host slice set but
    ``host_tier_bytes`` not yet bumped)."""
    rng = np.random.RandomState(20260807)
    cache, pc, tp = _mk(num_pages=13, page_size=4, pages_per_slot=8,
                        budget=3 * 512)   # tiny host tier: evicts + drops
    families = [list(rng.randint(0, 50, 16)) for _ in range(4)]
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            st = pc.stats()
            # every pair below is updated together under the lock, so
            # any mismatch inside ONE returned dict is a torn read
            if st["host_tier_bytes"] != st["host_pages"] * tp.page_bytes():
                torn.append(("host_tier", st))
            if (st["resident_pages"] > st["nodes"]
                    or st["pinned_pages"] > st["nodes"]):
                torn.append(("pages_vs_nodes", st))
            total = st["hits"] + st["misses"]
            expect = round(st["hits"] / total, 4) if total else 0.0
            if st["hit_rate"] != expect:
                torn.append(("hit_rate", st))

    readers = [threading.Thread(target=reader, daemon=True)
               for _ in range(3)]
    for t in readers:
        t.start()
    inflight = []
    try:
        for step in range(300):
            op = rng.randint(0, 8)
            if op <= 4:
                fam = families[rng.randint(len(families))]
                prompt = fam[:int(rng.randint(5, len(fam) + 1))]
                try:
                    res = pc.admit(prompt, int(rng.randint(1, 6)))
                except PageExhaustedError:
                    continue
                _stamp_fresh(pc, tp, prompt, res)
                inflight.append(res)
            elif op <= 6 and inflight:
                cache.free(inflight.pop(
                    rng.randint(len(inflight))).pages)
            elif rng.random_sample() < 0.2:
                pc.invalidate("pool_reset")
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=10.0)
    assert torn == [], f"torn stats snapshots: {torn[:3]}"
    # the churn must actually have exercised eviction/offload paths
    assert pc.offload_total > 0 or pc.evictions


def test_cache_invariant_fuzz():
    """Randomized admit/release/pin/unpin/invalidate churn, checked
    step-by-step against a model-checker dict: chain-stamped content on
    every hit, allocator/free-list consistency, pinned nodes never
    evicted, no page freed while referenced."""
    rng = np.random.RandomState(20260806)
    cache, pc, tp = _mk(num_pages=13, page_size=4, pages_per_slot=8,
                        budget=3 * 512)   # tiny tier: exercises drops
    inflight = []          # (prompt, AdmitResult)
    pins = {}              # pin_id -> prompt
    # prompts drawn from few families => real shared-prefix structure
    families = [list(rng.randint(0, 50, 16)) for _ in range(4)]

    def check_invariants():
        free = set(cache._free)
        assert len(free) == len(cache._free), "free list duplicates"
        for p in free:
            assert cache.refcount(p) == 0, f"page {p} free but ref'd"
        for p in range(1, cache.num_pages):
            assert cache.refcount(p) >= 0
            if cache.refcount(p) == 0:
                assert p in free, f"page {p} leaked (ref 0, not free)"
        resident = [n.page for n in pc._all if n.page is not None]
        assert len(resident) == len(set(resident)), "node page dup"
        for pg in resident:
            assert cache.refcount(pg) >= 1 and pg not in free
        assert pc.host_bytes == pc.host_pages() * tp.page_bytes()
        for pid, nodes in pc._pins.items():
            for n in nodes:
                assert n.pins >= 1

    for step in range(400):
        op = rng.randint(0, 10)
        if op <= 3:          # admit
            fam = families[rng.randint(len(families))]
            cut = int(rng.randint(5, len(fam) + 1))
            prompt = fam[:cut]
            try:
                res = pc.admit(prompt, int(rng.randint(1, 6)))
            except PageExhaustedError:
                pass
            else:
                # model check: every matched page's content must be the
                # chain stamp its prefix dictates
                for i in range(res.shared_len // 4):
                    got = tp.read_stamp(res.pages[i])
                    assert got == _chain_stamp(prompt, i), (
                        f"step {step}: hit page {res.pages[i]} holds "
                        f"{got}, expected chain stamp of "
                        f"{tuple(prompt[:(i + 1) * 4])}")
                _stamp_fresh(pc, tp, prompt, res)
                inflight.append((prompt, res))
        elif op <= 5 and inflight:   # release a random request
            _, res = inflight.pop(rng.randint(len(inflight)))
            cache.free(res.pages)
        elif op == 6:        # pin a family prefix
            fam = families[rng.randint(len(families))]
            pins[pc.pin(fam[:int(rng.randint(4, 13))])] = True
        elif op == 7 and pins:       # unpin
            pid = list(pins)[rng.randint(len(pins))]
            del pins[pid]
            pc.unpin(pid)
        elif op == 8 and rng.random_sample() < 0.1:
            pc.invalidate("pool_reset")
            tp.pools["att"]["pk"][:] = 0
            tp.pools["att"]["pv"][:] = 0
        check_invariants()
    for _, res in inflight:
        cache.free(res.pages)
    check_invariants()
    # the run must actually have exercised the interesting paths
    assert pc.hits > 0 and pc.misses > 0
    assert pc.offload_total > 0 or pc.evictions


# ----------------------------------------------------------- engine: parity
def test_persistent_hits_bit_identical_and_legacy_oracle(engine, lm, rng):
    """Cold pass == warm (cached-hit) pass == legacy free-on-release
    engine, token for token; warm passes must actually hit."""
    legacy = GenerationEngine(lm, slots=4, page_size=4, max_context=32)
    legacy.start()
    prompts = [rng.randint(0, VOCAB, 9).tolist() for _ in range(4)]
    ref = [legacy.generate(p, 8).tolist() for p in prompts]
    legacy.stop()

    h0 = engine.prefix_cache.hits
    cold = [engine.generate(p, 8).tolist() for p in prompts]
    assert cold == ref
    warm = [engine.generate(p, 8).tolist() for p in prompts]
    assert warm == ref
    assert engine.prefix_cache.hits >= h0 + len(prompts)
    # sampled decoding hits the cache identically
    kw = dict(temperature=0.9, top_k=7, seed=42)
    s1 = engine.generate(prompts[0], 8, **kw).tolist()
    s2 = engine.generate(prompts[0], 8, **kw).tolist()
    assert s1 == s2


def test_engine_offload_restore_round_trip(lm, rng):
    """Tight pool: cold pages spill to host mid-run and restore on
    revisit; every completion stays bit-identical to the legacy
    engine."""
    eng = GenerationEngine(lm, slots=2, page_size=4, max_context=32,
                           num_pages=13, prefix_cache=True)
    eng.start()
    prompts = [rng.randint(0, VOCAB, 9).tolist() for _ in range(6)]
    ref = [eng.generate(p, 8).tolist() for p in prompts]
    st = eng.prefix_cache.stats()
    assert st["offload_total"] > 0, st
    again = [eng.generate(p, 8).tolist() for p in prompts]
    assert again == ref
    st = eng.prefix_cache.stats()
    assert st["restore_total"] > 0 and st["hits"] >= len(prompts), st
    eng.stop()

    legacy = GenerationEngine(lm, slots=2, page_size=4, max_context=32,
                              num_pages=13)
    legacy.start()
    assert [legacy.generate(p, 8).tolist() for p in prompts] == ref
    legacy.stop()


def test_chat_session_pinning(engine, rng):
    """Multi-turn conversation: pin the history after each turn; later
    turns only prefill the new tokens (shared_len grows monotonically)
    and the transcript matches an unpinned cold engine."""
    history = rng.randint(0, VOCAB, 6).tolist()
    pin = None
    shared_seen = []
    for turn in range(3):
        req = engine.submit(history, 4)
        toks = req.result(timeout=60)
        shared_seen.append(req.shared_len)
        history = history + toks + rng.randint(0, VOCAB, 2).tolist()
        if pin is not None:
            engine.unpin_prefix(pin)
        pin = engine.pin_prefix(history)
    engine.unpin_prefix(pin)
    assert shared_seen[1] > 0 and shared_seen[2] > shared_seen[1]
    with pytest.raises(KeyError):
        engine.unpin_prefix(pin)


# ---------------------------------------------- engine: invalidation + 429s
def test_hot_swap_invalidation_drill(lm, lm2, rng):
    """After a deploy, the very next identical prompt must NOT hit the
    old tree (stale weights) — its tokens must equal a fresh engine
    running the new weights; rollback invalidates again."""
    eng = GenerationEngine(lm, slots=2, page_size=4, max_context=32,
                           prefix_cache=True)
    eng.start()
    prompt = rng.randint(0, VOCAB, 9).tolist()
    eng.generate(prompt, 8)
    assert eng.generate(prompt, 8) is not None
    assert eng.prefix_cache.hits >= 1

    eng.deploy("default", lm2, retain_old=True)
    got = eng.generate(prompt, 8).tolist()
    st = eng.prefix_cache.stats()
    assert st["evictions_total"].get("swap", 0) > 0, st
    fresh = GenerationEngine(lm2, slots=2, page_size=4, max_context=32)
    fresh.start()
    assert got == fresh.generate(prompt, 8).tolist()
    fresh.stop()

    eng.rollback()
    back = eng.generate(prompt, 8).tolist()
    fresh_old = GenerationEngine(lm, slots=2, page_size=4,
                                 max_context=32)
    fresh_old.start()
    assert back == fresh_old.generate(prompt, 8).tolist()
    fresh_old.stop()
    eng.stop()


def test_restart_invalidates_pool_reset(lm, rng):
    """stop() + start() reseeds the pools; the tree must not survive
    into the new pools (their pages hold zeros, not the cached KV)."""
    eng = GenerationEngine(lm, slots=2, page_size=4, max_context=32,
                           prefix_cache=True)
    eng.start()
    prompt = rng.randint(0, VOCAB, 9).tolist()
    ref = eng.generate(prompt, 8).tolist()
    assert eng.prefix_cache.resident_pages() > 0
    eng.stop()
    eng.start()
    assert eng.prefix_cache.resident_pages() == 0
    assert eng.prefix_cache.stats()["evictions_total"].get(
        "pool_reset", 0) > 0
    assert eng.generate(prompt, 8).tolist() == ref
    eng.stop()


def test_page_exhaustion_sheds_never_evicts_pinned(lm, rng):
    """Every page pinned or in flight: admission must shed (429 once
    the queue fills) rather than evict a pinned/in-use node; unpinning
    unblocks the queued request."""
    # pool of 8 usable pages: one 16-occupancy request takes 4
    eng = GenerationEngine(lm, slots=2, page_size=4, max_context=32,
                           num_pages=9, max_queue=2, deadline_s=30.0,
                           prefix_cache=True)
    eng.start()
    a = rng.randint(0, VOCAB, 9).tolist()
    b = rng.randint(0, VOCAB, 9).tolist()
    for p in (a, b):
        eng.generate(p, 8)
    pin_a, pin_b = eng.pin_prefix(a), eng.pin_prefix(b)
    assert eng.prefix_cache.pinned_pages() == 4
    # a long-running request occupies the remaining 4 pages
    blocker = eng.submit(rng.randint(0, VOCAB, 9).tolist(), 8,
                         temperature=0.5, seed=3)
    blocker.result(timeout=60)
    # now every allocatable page is pinned tree state; new cold
    # requests queue (cannot admit), then overflow sheds 429
    q1 = eng.submit(rng.randint(0, VOCAB, 12).tolist(), 8)
    q2 = eng.submit(rng.randint(0, VOCAB, 12).tolist(), 8)
    time.sleep(0.3)
    assert not q1.done.is_set() and not q2.done.is_set()
    assert eng.prefix_cache.pinned_pages() == 4   # nothing evicted
    with pytest.raises(QueueFullError):
        eng.submit(rng.randint(0, VOCAB, 12).tolist(), 8)
    # release the pins: the queued requests admit and complete
    eng.unpin_prefix(pin_a)
    eng.unpin_prefix(pin_b)
    assert len(q1.result(timeout=60)) == 8
    assert len(q2.result(timeout=60)) == 8
    eng.stop()


# ------------------------------------------------------------ engine: churn
def test_concurrent_join_leave_pin_churn(engine, rng):
    """Client threads submitting/pinning/unpinning concurrently while
    the decode loop evicts and restores: every request completes with
    deterministic greedy tokens; allocator invariants hold after."""
    prompts = [rng.randint(0, VOCAB, 9).tolist() for _ in range(6)]
    ref = {i: engine.generate(p, 6).tolist()
           for i, p in enumerate(prompts)}
    pinned_before = engine.prefix_cache.pinned_pages()
    errors = []

    def worker(wid):
        try:
            r = np.random.RandomState(wid)
            for _ in range(5):
                i = int(r.randint(len(prompts)))
                pin = engine.pin_prefix(prompts[i])
                got = engine.generate(prompts[i], 6).tolist()
                assert got == ref[i], (i, got, ref[i])
                engine.unpin_prefix(pin)
        except Exception as e:      # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    # steady state: everything in flight drained, refcounts consistent
    time.sleep(0.2)
    cache = engine.cache
    for p in range(1, cache.num_pages):
        assert cache.refcount(p) >= 0
    free = set(cache._free)
    for n in engine.prefix_cache._all:
        if n.page is not None:
            assert n.page not in free
    assert engine.prefix_cache.pinned_pages() == pinned_before


# ------------------------------------------------------------- HTTP surface
def test_generation_cache_endpoint(engine, rng):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.streaming.serving import InferenceServer

    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater("sgd", learning_rate=0.1).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=2, activation="softmax",
                               loss="mcxent"))
            .build())
    pred = MultiLayerNetwork(conf).init()
    srv = InferenceServer(pred, generation=engine)
    port = srv.start()
    try:
        engine.generate(rng.randint(0, VOCAB, 9).tolist(), 4)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/generation/cache")
        resp = conn.getresponse()
        assert resp.status == 200
        body = json.loads(resp.read())
        assert body["cache"]["num_pages"] == engine.cache.num_pages
        pc = body["prefix_cache"]
        assert pc is not None and pc["nodes"] >= 1
        assert set(pc) >= {"hits", "misses", "resident_pages",
                           "host_tier_bytes", "pinned_pages",
                           "offload_total", "restore_total",
                           "evictions_total"}
        conn.close()
    finally:
        srv.stop()


def test_ui_generation_cache_route(engine):
    from deeplearning4j_tpu.ui.server import UIServer

    ui = UIServer()
    port = ui.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/generation/cache")
        assert conn.getresponse().status == 404   # nothing attached
        ui.attach_generation(engine)
        conn.request("GET", "/generation/cache")
        resp = conn.getresponse()
        assert resp.status == 200
        assert json.loads(resp.read())["prefix_cache"] is not None
        conn.close()
    finally:
        ui.stop()

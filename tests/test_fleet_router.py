"""Serving-fleet control plane: placement, membership, failover, rollout.

Covers the PR-20 contracts end to end:
- placement policy (pure simulation: CI gate 6's selftest + seeded-tie
  determinism),
- in-process fleet: prefix-affinity stickiness, session pin + re-pin on
  a survivor, queued-request failover with zero client-visible errors,
  fleet_route spans, router metrics,
- fleet-wide rollout: canary → wave → commit, and forced watch
  regression → every replica rolled back,
- subprocess fleet (supervisor-spawned replicas): SIGKILL mid-flight →
  queued requests retried on survivors, in-stream kill → clean terminal
  SSE error event at the frontend, crash → restart → rejoin with a
  fresh publisher epoch,
- TelemetryPublisher publish-loop retry hygiene (PR-5 RetryPolicy).
"""

import json
import logging
import signal
import threading
import time
import urllib.request

import pytest

from deeplearning4j_tpu.fleet.placement import (
    AFFINITY, CANARY, LEAST_LOADED, PINNED, ReplicaView, ShadowIndex,
    choose, placement_selftest)
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.models.zoo import transformer_char_lm
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability.tracing import get_tracer

pytestmark = pytest.mark.fleet_router

VOCAB = 40
PROMPT = list(range(8))


def small_lm(seed=12345):
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=2,
                               layers=1, max_cache=32, seed=seed)


def make_engine(seed=12345, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("max_queue", 16)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("prefix_cache", True)
    return GenerationEngine(small_lm(seed), **kw).start()


def make_router(**kw):
    from deeplearning4j_tpu.fleet import FleetRouter

    kw.setdefault("page_size", 4)
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("refresh_interval_s", 0.0)
    return FleetRouter(**kw)


# ---------------------------------------------------------------- placement
def test_placement_selftest_passes():
    # the same simulation CI gate 6 runs (determinism, affinity vs
    # random, version-tag invalidation, drain, canary split, pins)
    assert placement_selftest() == 0


def test_placement_deterministic_under_seeded_ties():
    def fresh_views():
        out = []
        for i in range(4):
            v = ReplicaView(f"r{i}", page_size=4, slots=4)
            v.healthy, v.free_pages = True, 64
            out.append(v)
        return out

    seq_a = [choose(fresh_views(), PROMPT, seed=11, n=n)[0]
             for n in range(32)]
    seq_b = [choose(fresh_views(), PROMPT, seed=11, n=n)[0]
             for n in range(32)]
    assert seq_a == seq_b           # same seed → identical tie-breaks
    seq_c = [choose(fresh_views(), PROMPT, seed=12, n=n)[0]
             for n in range(32)]
    assert seq_a != seq_c           # the seed is load-bearing


def test_shadow_index_pricing_matches_admission():
    # matched pages = whole page_size-token chunks, the PR-17 pricing
    sh = ShadowIndex(page_size=4)
    sh.insert(list(range(10)))      # 2 whole pages recorded (10 // 4)
    assert sh.matched_pages(list(range(10))) == 2
    assert sh.matched_pages(list(range(4))) == 1
    assert sh.matched_pages([9, 9, 9, 9]) == 0
    assert sh.observe_version("v2") is True     # version move resets
    assert sh.matched_pages(list(range(8))) == 0


# ----------------------------------------------------------- in-process fleet
@pytest.fixture(scope="module")
def duo():
    """Two live in-process replicas behind one router."""
    from deeplearning4j_tpu.fleet import FleetRouter, InProcessReplica

    e0, e1 = make_engine(), make_engine()
    router = make_router(seed=3)
    router.attach(InProcessReplica("r0", e0))
    router.attach(InProcessReplica("r1", e1))
    yield router, {"r0": e0, "r1": e1}
    for e in (e0, e1):
        e.stop(drain=False)


def test_affinity_keeps_session_on_one_replica(duo):
    router, _engines = duo
    prompt = [3] * 8
    first = router.submit(prompt, 3)
    first.result(timeout=30)
    assert first.finish_reason in ("length", "stop")
    again = router.submit(prompt, 3)
    again.result(timeout=30)
    assert again.replica_id == first.replica_id
    assert again.placements[0].reason == AFFINITY


def test_fleet_route_span_records_placement(duo):
    router, _engines = duo
    req = router.submit([5] * 8, 2)
    req.result(timeout=30)
    spans = [s for s in get_tracer().spans_for_trace(req.trace_id)
             if s.name == "fleet_route"]
    assert spans, "placement must record a fleet_route span"
    attrs = spans[-1].attrs
    assert attrs["replica"] == req.replica_id
    assert attrs["reason"] in (AFFINITY, LEAST_LOADED, PINNED, CANARY,
                               "repin", "random")
    assert set(attrs["candidates"]) == {"r0", "r1"}
    for s in attrs["candidates"].values():
        assert {"affinity_pages", "load", "free_pages"} <= set(s)


def test_router_metrics_and_replica_table(duo):
    router, _engines = duo
    router.submit([7] * 8, 2).result(timeout=30)
    rows = {r["replica"]: r for r in router.replicas()}
    assert set(rows) == {"r0", "r1"}
    assert all(r["live"] for r in rows.values())
    placed = sum(c.value for _l, c in router._m_requests.samples())
    assert placed >= 1


def test_admin_drain_excludes_replica(duo):
    router, _engines = duo
    router.drain("r0")
    try:
        for _ in range(4):
            req = router.submit([11] * 8, 2)
            req.result(timeout=30)
            assert req.replica_id == "r1"
    finally:
        router.drain("r0", False)


def test_queued_failover_zero_errors_and_session_repin():
    # a dead replica's queued (not-yet-streamed) requests land on the
    # survivor with no client-visible error, and the pinned session
    # re-pins there — the in-process version of the SIGKILL drill
    from deeplearning4j_tpu.fleet import FleetRouter, InProcessReplica

    e0, e1 = make_engine(), make_engine()
    # long refresh interval: the router must still BELIEVE the victim is
    # live when it submits, so the failure happens at the replica and
    # the failover path (not just placement avoidance) is exercised
    router = make_router(seed=5, refresh_interval_s=30.0)
    router.attach(InProcessReplica("a", e0))
    router.attach(InProcessReplica("b", e1))
    try:
        prompt = [2] * 8
        pinned_on = router.pin_session("conv", prompt)
        victim = {"a": e0, "b": e1}[pinned_on]
        survivor_id = "b" if pinned_on == "a" else "a"
        victim.stop(drain=False)    # in-queue requests die ShuttingDown

        req = router.submit(prompt, 3, session_id="conv")
        toks = req.result(timeout=30)       # zero client-visible errors
        assert len(toks) == 3
        assert req.replica_id == survivor_id
        assert req.failovers >= 1
        assert router.session_replica("conv") == survivor_id
        fo = sum(c.value for _l, c in router._m_failovers.samples())
        assert fo >= 1
        # dead replica is drained from subsequent placements entirely
        again = router.submit(prompt, 2, session_id="conv")
        again.result(timeout=30)
        assert again.replica_id == survivor_id and again.failovers == 0
    finally:
        e0.stop(drain=False) if e1 is victim else e1.stop(drain=False)


def test_no_live_replica_is_terminal():
    from deeplearning4j_tpu.fleet import (
        FleetRouter, InProcessReplica, NoLiveReplicaError)

    e = make_engine()
    router = make_router()
    router.attach(InProcessReplica("only", e))
    e.stop(drain=False)
    with pytest.raises(NoLiveReplicaError):
        router.submit(PROMPT, 2)


# ------------------------------------------------------------- fleet rollout
def test_fleet_rollout_promotes_and_forced_regression_rolls_back_all():
    from deeplearning4j_tpu.fleet import (
        FleetRollout, FleetRouter, InProcessReplica)

    engines = {f"r{i}": make_engine() for i in range(3)}
    router = make_router(seed=9)
    handles = {rid: InProcessReplica(rid, e) for rid, e in engines.items()}
    for h in handles.values():
        router.attach(h)
    stop_load = threading.Event()

    def load():
        while not stop_load.is_set():
            try:
                router.submit([1] * 8, 2).result(timeout=30)
            except Exception:
                time.sleep(0.05)

    t = threading.Thread(target=load, daemon=True)
    t.start()
    try:
        before = {rid: e.models.active("default").version
                  for rid, e in engines.items()}
        good = transformer_char_lm(vocab_size=VOCAB, d_model=32,
                                   n_heads=2, layers=1, max_cache=32,
                                   seed=777)
        ro = FleetRollout(router, handles, canary_fraction=0.5,
                          canary_min_requests=2, canary_timeout_s=60,
                          watch_window_s=0.3, watch_poll_s=0.05,
                          registry=router.registry)
        res = ro.consider(good, "good")
        assert res.outcome == "promoted"
        assert sorted(res.committed) == sorted(engines)
        after = {rid: e.models.active("default").version
                 for rid, e in engines.items()}
        assert all(after[r] > before[r] for r in engines)

        # forced regression mid-wave: EVERY deployed replica (canary
        # included) must return to the promoted version
        bad = transformer_char_lm(vocab_size=VOCAB, d_model=32,
                                  n_heads=2, layers=1, max_cache=32,
                                  seed=778)
        ro2 = FleetRollout(router, handles, canary_fraction=0.5,
                           canary_min_requests=2, canary_timeout_s=60,
                           watch_window_s=0.3, watch_poll_s=0.05,
                           registry=router.registry,
                           watch_extra_fn=lambda rid: {
                               "probe_ok": False,
                               "probe_detail": "forced regression"})
        res2 = ro2.consider(bad, "bad")
        assert res2.outcome == "rolled_back"
        restored = {rid: e.models.active("default").version
                    for rid, e in engines.items()}
        assert restored == after
        outcomes = {l[0][1]: c.value
                    for l, c in ro2._m_outcomes.samples()}
        assert outcomes.get("rolled_back", 0) >= 1
    finally:
        stop_load.set()
        t.join(timeout=5)
        for e in engines.values():
            e.stop(drain=False)


def test_fleet_rollout_rejects_http_replicas():
    from deeplearning4j_tpu.fleet import FleetRollout, HTTPReplica

    with pytest.raises(ValueError):
        FleetRollout(object(), {"w": HTTPReplica("w", "http://x")})


# ------------------------------------------------------- publisher retry loop
class _FlakyBroker:
    def __init__(self, fail_times, exc=ConnectionError("broker down")):
        self.fail_times = fail_times
        self.exc = exc
        self.calls = 0
        self.delivered = []

    def publish(self, topic, payload):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise self.exc
        self.delivered.append(topic)
        return 1


def test_publisher_transient_outage_backs_off_and_resumes():
    from deeplearning4j_tpu.observability.fleet import TelemetryPublisher

    broker = _FlakyBroker(fail_times=2)
    pub = TelemetryPublisher("w", broker=broker, interval_s=0.05,
                             registry=MetricsRegistry())
    pub.retry_policy.base_delay_s = 0.01
    pub.start()
    try:
        deadline = time.monotonic() + 10
        while not broker.delivered and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        pub.stop()
    assert broker.delivered, "publish must resume after transient outage"
    assert broker.calls >= 3                      # 2 failures + success
    assert pub.retry_policy.retries >= 2          # rode the RetryPolicy


def test_publisher_fatal_error_surfaces(caplog):
    from deeplearning4j_tpu.observability.fleet import TelemetryPublisher

    broker = _FlakyBroker(fail_times=10**9, exc=ValueError("bad payload"))
    pub = TelemetryPublisher("w", broker=broker, interval_s=0.05,
                             registry=MetricsRegistry())
    with caplog.at_level(logging.WARNING,
                         logger="deeplearning4j_tpu.observability"):
        pub.start()
        deadline = time.monotonic() + 10
        while not any("telemetry publish failed after retries" in r.message
                      for r in caplog.records) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        pub.stop()
    assert any("telemetry publish failed after retries" in r.message
               for r in caplog.records)
    assert broker.calls >= 1
    assert pub.retry_policy.retries == 0          # fatal: no backoff loop


def test_publisher_publish_once_keeps_swallow_semantics():
    from deeplearning4j_tpu.observability.fleet import TelemetryPublisher

    broker = _FlakyBroker(fail_times=10**9)
    pub = TelemetryPublisher("w", broker=broker,
                             registry=MetricsRegistry())
    assert pub.publish_once() == -1               # no raise, old contract


# ----------------------------------------------------------- subprocess fleet
@pytest.fixture(scope="module")
def subprocess_fleet():
    """Two supervisor-spawned replicas + broker + aggregator + router.

    Spawn cost ~10s for the module; every test leaves BOTH replicas
    serving (the SIGKILL drill restores the fleet via supervisor
    restart before yielding back).
    """
    from deeplearning4j_tpu.fleet import FleetRouter, ReplicaSupervisor
    from deeplearning4j_tpu.observability.fleet import FleetAggregator
    from deeplearning4j_tpu.streaming.pubsub import MessageBroker

    # replicas are processes of their own and take their platform from the
    # environment the supervisor passes through: pin them to the CPU like
    # the rest of this tier (restarts during the drills included)
    env = pytest.MonkeyPatch()
    env.setenv("JAX_PLATFORMS", "cpu")
    broker = MessageBroker()
    burl = f"http://127.0.0.1:{broker.serve(port=0)}"
    agg = FleetAggregator(url=burl, expire_after_s=3.0,
                          registry=MetricsRegistry()).start()
    sup = ReplicaSupervisor(
        broker_url=burl, warmup_timeout_s=180,
        registry=MetricsRegistry(),
        replica_args={"slots": 4, "page_size": 4, "max_context": 32,
                      "prefill_buckets": "8", "d_model": 32,
                      "n_heads": 2, "layers": 1, "vocab": VOCAB,
                      "interval_s": 0.25,
                      # paced decode: wide enough per-token window for
                      # the mid-stream kill drill to land mid-stream
                      "step_floor_ms": 25}).start()
    sup.start_replica("w0")
    sup.start_replica("w1")
    router = make_router(aggregator=agg, seed=7, refresh_interval_s=0.1)
    for h in sup.handles(timeout=60).values():
        router.attach(h)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if sum(r["live"] for r in router.replicas()) == 2:
            break
        time.sleep(0.1)
    assert sum(r["live"] for r in router.replicas()) == 2
    yield router, sup, agg
    sup.stop_all()
    agg.stop()
    broker.stop()
    env.undo()


def _wait_live(router, wid, timeout=90):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rows = {r["replica"]: r for r in router.replicas()}
        if rows.get(wid, {}).get("live"):
            return True
        time.sleep(0.2)
    return False


def test_http_replica_envelope_echoes_replica_id(subprocess_fleet):
    router, sup, _agg = subprocess_fleet
    rp = sup.processes()["w0"]
    body = json.dumps({"prompt": PROMPT, "max_tokens": 2}).encode()
    req = urllib.request.Request(
        f"{rp.url}/generate", data=body,
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "cafe0123deadbeef"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        env = json.loads(resp.read().decode())
    assert env["replica"] == "w0"
    assert env["trace_id"] == "cafe0123deadbeef"   # propagated, not minted


def test_sigkill_failover_and_restart_rejoin(subprocess_fleet):
    """The headline drill: SIGKILL one replica mid-flight → queued
    requests retried on the survivor with zero client-visible errors,
    the pinned session re-pins there, and the supervisor's restart
    rejoins the routing table under a fresh publisher epoch."""
    router, sup, _agg = subprocess_fleet
    prompt = [9] * 8
    pinned_on = router.pin_session("talk", prompt)
    survivor = "w1" if pinned_on == "w0" else "w0"

    sup.kill(pinned_on, sig=signal.SIGKILL, restart=True)
    ok, errors = 0, []
    for _ in range(6):
        try:
            r = router.submit(prompt, 2, session_id="talk")
            r.result(timeout=60)
            ok += 1
        except Exception as e:      # noqa: BLE001 - recording, not hiding
            errors.append(e)
    assert not errors, f"queued requests must not error: {errors!r}"
    assert ok == 6
    assert router.session_replica("talk") == survivor
    fo = sum(c.value for _l, c in router._m_failovers.samples())
    assert fo >= 1

    # crash → restart → rejoin: fresh epoch clears the death mark
    assert _wait_live(router, pinned_on), "restarted replica must rejoin"
    assert sup.processes()[pinned_on].restarts >= 1
    restarts = sum(c.value for _l, c in sup._m_restarts.samples())
    assert restarts >= 1


def test_mid_stream_kill_clean_terminal_sse_event(subprocess_fleet):
    """A replica killed MID-STREAM cannot be failed over (tokens were
    already delivered): the frontend must end the stream with a clean
    terminal SSE error event, never a silent EOF."""
    from deeplearning4j_tpu.fleet import FleetFrontend

    router, sup, _agg = subprocess_fleet
    front = FleetFrontend(router, access_log=True)
    fport = front.start()
    try:
        # 20 paced tokens (25 ms step floor) = a ~500 ms stream: plenty
        # of window to kill after the first event
        body = json.dumps({"prompt": [4] * 8, "max_tokens": 20,
                           "stream": True}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{fport}/generate", data=body,
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=60)
        events, killed = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[len(b"data: "):].decode())
            events.append(ev)
            if killed is None and "token" in ev:
                # first token seen: find the serving replica (the
                # router-local inflight count is current, unlike the
                # snapshot-lagged active/queued) and kill it
                killed = next(
                    r["replica"] for r in router.replicas()
                    if r["inflight"] > 0)
                sup.kill(killed, sig=signal.SIGKILL, restart=True)
            if ev.get("done"):
                break
        assert killed is not None
        terminal = events[-1]
        assert terminal.get("done") is True
        assert "error" in terminal, f"want terminal error event: {terminal}"
        assert any("token" in e for e in events)   # stream really started
        assert _wait_live(router, killed)          # fleet heals for peers
    finally:
        front.stop()


def test_frontend_mints_and_propagates_request_id(subprocess_fleet):
    from deeplearning4j_tpu.fleet import FleetFrontend

    router, _sup, _agg = subprocess_fleet
    front = FleetFrontend(router)
    fport = front.start()
    try:
        body = json.dumps({"prompt": [6] * 8, "max_tokens": 2}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{fport}/generate", data=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "feedface00000001"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            env = json.loads(resp.read().decode())
        assert env["trace_id"] == "feedface00000001"
        assert env["replica"] in ("w0", "w1")
        assert env["placement_reason"] in (AFFINITY, LEAST_LOADED,
                                           PINNED, "repin")
        # the SAME id names the router's placement span
        spans = [s for s in get_tracer().spans_for_trace(
            "feedface00000001") if s.name == "fleet_route"]
        assert spans and spans[-1].attrs["replica"] == env["replica"]
        # minted when absent
        req2 = urllib.request.Request(
            f"http://127.0.0.1:{fport}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req2, timeout=60) as resp:
            env2 = json.loads(resp.read().decode())
        assert len(env2["trace_id"]) == 16
    finally:
        front.stop()

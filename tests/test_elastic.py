"""Elastic data parallelism: degraded-mode eviction/re-admission and the
renormalized average (docs/resilience.md "Elasticity").

Correctness oracles follow the repo's equivalence discipline
(TestCompareParameterAveragingSparkVsSingleMachine): a degraded collective
must equal the EXPLICIT math over the healthy set — manual replica
averaging for ParallelWrapper, single-device training on the healthy rows
for SyncTrainingMaster.  Every fault is driven deterministically by the
PR-5 FaultInjector (delay/hang/kill + until_step clearing)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.backend import device as backend
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.observability import (
    HealthEvaluator, HealthRule, get_flight_recorder, get_registry,
)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel import (
    DistributedNetwork, ElasticConfig, ElasticController,
    ParallelWrapper, ParameterAveragingTrainingMaster, SyncTrainingMaster,
)
from deeplearning4j_tpu.resilience import FaultInjector, inject_faults

pytestmark = pytest.mark.elastic


def make_net(seed=12345, updater="sgd", lr=0.1):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
        .list()
        .layer(DenseLayer(n_in=6, n_out=10, activation="tanh"))
        .layer(OutputLayer(n_in=10, n_out=3, loss="mcxent",
                           activation="softmax"))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def make_batches(n_batches, batch_size, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n_batches):
        x = rs.randn(batch_size, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, batch_size)]
        out.append(DataSet(x, y))
    return out


def counter_value(name, **labels):
    fam = get_registry().get(name)
    if fam is None:
        return 0.0
    total = 0.0
    for label_pairs, child in fam.samples():
        d = dict(label_pairs)
        if all(d.get(k) == v for k, v in labels.items()):
            total += child.value
    return total


def flight_events(kind, **attrs):
    out = []
    for ev in get_flight_recorder().events():
        if ev.kind != kind:
            continue
        if all(ev.attrs.get(k) == v for k, v in attrs.items()):
            out.append(ev)
    return out


# ------------------------------------------------------- injector chaos modes
@pytest.mark.faults
def test_fault_injector_worker_states():
    inj = FaultInjector(seed=0)
    inj.hang_worker("1", at_step=3, until_step=6)
    inj.kill_worker("2", at_step=5)
    assert inj.worker_state("1", 2) == "ok"
    assert inj.worker_state("1", 3) == "hung"
    assert inj.worker_state("1", 5) == "hung"
    assert inj.worker_state("1", 6) == "ok"       # until_step clears it
    assert inj.worker_state("2", 4) == "ok"
    assert inj.worker_state("2", 5) == "dead"
    assert inj.worker_state("2", 999) == "dead"   # no until: dead forever
    inj.clear_worker("2")
    assert inj.worker_state("2", 999) == "ok"
    # dead wins over hung when both are armed
    inj.hang_worker("3", at_step=0)
    inj.kill_worker("3", at_step=0)
    assert inj.worker_state("3", 1) == "dead"
    kinds = [e["kind"] for e in inj.injected]
    assert "worker_hung" in kinds and "worker_dead" in kinds
    inj.reset()
    assert inj.worker_state("1", 4) == "ok"


# -------------------------------------------------------- controller invariants
def test_controller_min_healthy_and_max_evicted():
    reg = MetricsRegistry()
    ctl = ElasticController(
        "t", ["0", "1", "2"],
        config=ElasticConfig(min_healthy=2), registry=reg)
    assert ctl.evict("1", "manual", step=0) is True
    assert ctl.active_workers == ["0", "2"]
    # a second eviction would drop below min_healthy=2: refused
    assert ctl.evict("2", "manual", step=1) is False
    assert ctl.active_workers == ["0", "2"]
    ctl.readmit("1", step=2)
    assert ctl.active_workers == ["0", "1", "2"]
    # max_evicted caps simultaneous evictions even when min_healthy allows
    ctl2 = ElasticController(
        "t2", ["0", "1", "2", "3"],
        config=ElasticConfig(min_healthy=1, max_evicted=1), registry=reg)
    assert ctl2.evict("0", "manual", step=0) is True
    assert ctl2.evict("1", "manual", step=0) is False


def test_health_rule_max_evicted_replicas():
    reg = MetricsRegistry()
    ctl = ElasticController("hr", ["0", "1", "2", "3"],
                            config=ElasticConfig(), registry=reg)
    rule = HealthRule("evicted_budget", "max_evicted_replicas", 1)
    ev = HealthEvaluator([rule], component="hr_test", registry=reg)
    assert ev.evaluate().healthy
    ctl.evict("1", "manual", step=0)
    assert ev.evaluate().healthy           # 1 evicted <= budget 1
    ctl.evict("2", "manual", step=1)
    verdict = ev.evaluate()
    assert not verdict.healthy
    assert verdict.failing[0]["observed"] == 2.0


# ------------------------------------------------------------ tail-window bias
def test_tail_window_padding_not_double_counted():
    """3 minibatches over K=2: the tail window pads replica 1 with a
    duplicate of b2.  The pad-filled replica must be weighted out, so the
    result equals the EXPLICIT math: average after (b0, b1), then train
    replica 0 alone on b2."""
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    batches = make_batches(3, 4, seed=3)

    net = make_net(updater="sgd", lr=0.2)
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh)
    pw.fit(iter(batches))

    r0, r1 = make_net(updater="sgd", lr=0.2), make_net(updater="sgd", lr=0.2)
    r0.fit(batches[0].features, batches[0].labels)
    r1.fit(batches[1].features, batches[1].labels)
    avg = jax.tree_util.tree_map(lambda a, b: (a + b) / 2.0,
                                 r0.params, r1.params)
    ref = make_net(updater="sgd", lr=0.2)
    ref.params = avg
    ref.fit(batches[2].features, batches[2].labels)

    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), rtol=2e-5, atol=1e-6)


def test_tail_split_keeps_real_minibatches_with_avg_freq():
    """avg_freq=2, K=2, 7 batches: the tail (b4, b5, b6) must emit its
    full frame (b4, b5) as a real averaging window and only mask the
    padded slot of the final partial frame — weighting the whole tail
    per-replica would silently drop b5 (a REAL minibatch) from the
    average."""
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    batches = make_batches(7, 4, seed=31)
    net = make_net(updater="sgd", lr=0.2)
    ParallelWrapper(net, workers=K, averaging_frequency=2,
                    mesh=mesh).fit(iter(batches))

    def avg(trees):
        return jax.tree_util.tree_map(
            lambda *xs: sum(xs) / len(xs), *trees)

    # window 1 (full, F=2): r0 <- b0,b2; r1 <- b1,b3; average
    r0, r1 = make_net(updater="sgd", lr=0.2), make_net(updater="sgd", lr=0.2)
    for b in (batches[0], batches[2]):
        r0.fit(b.features, b.labels)
    for b in (batches[1], batches[3]):
        r1.fit(b.features, b.labels)
    avg1 = avg([r0.params, r1.params])
    # window 2 (tail full frame, F=1): r0 <- b4; r1 <- b5; average
    # (independent copies: the jitted facade step donates its buffers)
    copy = lambda t: jax.tree_util.tree_map(jnp.array, t)  # noqa: E731
    r0.params, r1.params = copy(avg1), copy(avg1)
    r0.fit(batches[4].features, batches[4].labels)
    r1.fit(batches[5].features, batches[5].labels)
    avg2 = avg([r0.params, r1.params])
    # window 3 (partial frame): r0 <- b6; r1 is pad-filled -> masked out
    ref = make_net(updater="sgd", lr=0.2)
    ref.params = avg2
    ref.fit(batches[6].features, batches[6].labels)

    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), rtol=2e-5, atol=1e-6)


def test_native_and_generic_tail_paths_agree():
    """The native C++ slab path and the generic window assembler must
    produce identical params on a ragged tail (7 batches over K=2, F=2) —
    zero-fill + mask + weight-out vs duplicate-fill + weight-out."""
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    batches = make_batches(7, 8, seed=37)
    merged = DataSet.merge(batches)

    generic = make_net(updater="adam", lr=0.05)
    ParallelWrapper(generic, workers=K, averaging_frequency=2,
                    mesh=mesh).fit(iter(batches))
    native = make_net(updater="adam", lr=0.05)
    ParallelWrapper(native, workers=K, averaging_frequency=2,
                    mesh=mesh).fit(ListDataSetIterator(merged, 8))

    assert native.iteration == generic.iteration
    np.testing.assert_allclose(native.params_to_vector(),
                               generic.params_to_vector(),
                               rtol=2e-5, atol=1e-6)


def test_all_ones_weights_reproduce_plain_mean():
    """With no faults and no padding, the weighted average must reproduce
    the legacy unweighted path (the healthy hot path is unchanged)."""
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    batches = make_batches(4, 4, seed=5)
    plain = make_net()
    ParallelWrapper(plain, workers=K, averaging_frequency=2,
                    mesh=mesh).fit(iter(batches))
    elastic = make_net()
    ParallelWrapper(elastic, workers=K, averaging_frequency=2, mesh=mesh,
                    elastic=ElasticConfig()).fit(iter(batches))
    np.testing.assert_allclose(plain.params_to_vector(),
                               elastic.params_to_vector(),
                               rtol=1e-6, atol=1e-7)


# ------------------------------------------------------------ manual eviction
def test_manual_eviction_renormalizes_average():
    """With replica 1 evicted for the whole run (K=2), every window's
    average is replica 0's params alone — the run must equal sequential
    training on replica 0's batch share (b0 then b2)."""
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    batches = make_batches(4, 4, seed=7)
    net = make_net(updater="sgd", lr=0.2)
    pw = ParallelWrapper(
        net, workers=K, averaging_frequency=1, mesh=mesh,
        elastic=ElasticConfig(readmit_after_windows=10 ** 9))
    pw.elastic.evict("1", "manual", step=0)
    pw.fit(iter(batches))

    ref = make_net(updater="sgd", lr=0.2)
    ref.fit(batches[0].features, batches[0].labels)
    ref.fit(batches[2].features, batches[2].labels)
    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), rtol=2e-5, atol=1e-6)


@pytest.mark.faults
def test_refused_eviction_of_dead_worker_is_visible():
    """When min_healthy blocks evicting a dead worker, the refusal must
    be loud — metric + flight event, once per episode — because the dead
    replica keeps weight 1 while the evicted-replicas gauge reads within
    budget."""
    reg = MetricsRegistry()
    ctl = ElasticController(
        "ref", ["0", "1", "2"],
        config=ElasticConfig(min_healthy=2), registry=reg)
    inj = FaultInjector(seed=0)
    inj.kill_worker("0", at_step=0)
    inj.kill_worker("1", at_step=0)
    with inject_faults(inj):
        for step in range(3):
            ctl.begin_window(step)
    # one eviction landed, the second was refused by min_healthy=2
    assert len(ctl.evicted_workers) == 1
    refused = [w for w in ("0", "1") if w not in ctl.evicted_workers]
    fam = reg.get("dl4j_elastic_eviction_refusals_total")
    counts = {dict(lp)["worker"]: c.value for lp, c in fam.samples()}
    assert counts == {refused[0]: 1.0}      # once per episode, not per window
    evs = flight_events("elastic_eviction_refused", component="ref")
    assert evs and evs[-1].attrs["worker"] == refused[0]
    assert evs[-1].attrs["reason"] == "dead"
    # fault clears -> refused worker is fine, episode re-arms; a new death
    # (now evictable: the other dead slot was readmitted) evicts cleanly
    inj.clear_worker(refused[0])
    ctl.begin_window(3)
    assert ctl._state[refused[0]]["refused"] is None


def test_manual_eviction_is_not_auto_readmitted():
    """Only straggler evictions take the readmit_after_windows probation
    path; a manual eviction stays in force until an explicit readmit()."""
    reg = MetricsRegistry()
    ctl = ElasticController(
        "man", ["0", "1"],
        config=ElasticConfig(readmit_after_windows=2), registry=reg)
    assert ctl.evict("1", "manual", step=0) is True
    for step in range(6):
        ctl.begin_window(step)
    assert ctl.evicted_workers == ["1"]
    ctl.readmit("1", step=6)
    assert ctl.evicted_workers == []


def test_lockstep_config_admits_no_evictions():
    """degraded_mode=False is the lockstep baseline arm: evict() is
    refused even when called manually, so nothing is ever weighted out
    of the average and the degraded-windows counter stays flat."""
    reg = MetricsRegistry()
    ctl = ElasticController(
        "lockstep", ["0", "1"],
        config=ElasticConfig(degraded_mode=False), registry=reg)
    assert ctl.evict("1", "manual", step=0) is False
    assert ctl.active_workers == ["0", "1"]
    assert (ctl.begin_window(0) == 1.0).all()


def test_param_averaging_master_elastic_state_survives_epochs():
    """ParameterAveragingTrainingMaster builds a fresh ParallelWrapper
    per epoch; its ElasticController must be persistent so an eviction
    in epoch 1 is still in force in epoch 2 and visible afterwards via
    master.elastic / training_stats()."""
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    master = ParameterAveragingTrainingMaster(
        workers=K, averaging_frequency=1, mesh=mesh,
        elastic=ElasticConfig(readmit_after_windows=10 ** 9))
    assert isinstance(master.elastic, ElasticController)
    master.elastic.evict("1", "manual", step=0)
    net = make_net(updater="sgd", lr=0.2)
    batches = make_batches(4, 4, seed=11)
    DistributedNetwork(net, master).fit(
        ListDataSetIterator(DataSet.merge(batches), 4), epochs=2)
    assert master.elastic.evicted_workers == ["1"]
    assert master.training_stats()["elastic"]["evicted"]["1"][
        "reason"] == "manual"
    # two epochs over replica 0's batch share: b0, b2, then b0, b2 again
    ref = make_net(updater="sgd", lr=0.2)
    for b in (batches[0], batches[2], batches[0], batches[2]):
        ref.fit(b.features, b.labels)
    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), rtol=2e-5, atol=1e-6)


# --------------------------------------------------- straggler-driven eviction
@pytest.mark.faults
def test_straggler_eviction_named_in_metrics_and_flight_events():
    K = 8
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    base_evictions = counter_value("dl4j_elastic_evictions_total",
                                   component="parallel_wrapper", worker="3")
    net = make_net()
    # straggler_window=8 ages the compile-inflated first windows out of
    # the rolling medians quickly; 16 windows leaves ample room for the
    # min_steps warm-up + 2 flags before the run ends
    pw = ParallelWrapper(
        net, workers=K, averaging_frequency=1, mesh=mesh,
        elastic=ElasticConfig(evict_after_flags=2, straggler_min_steps=2,
                              straggler_window=8,
                              readmit_after_windows=10 ** 9))
    inj = FaultInjector(seed=1).delay_worker("3", 0.1)
    with inject_faults(inj):
        pw.fit(iter(make_batches(K * 16, 4, seed=9)))
    assert "3" in pw.elastic.evicted_workers
    assert pw.elastic.summary()["evicted"]["3"]["reason"] == "straggler"
    assert counter_value("dl4j_elastic_evictions_total",
                         component="parallel_wrapper",
                         worker="3") > base_evictions
    evs = flight_events("elastic_eviction", component="parallel_wrapper",
                        worker="3")
    assert evs and evs[-1].attrs["reason"] == "straggler"
    # training continued on the healthy set
    assert np.isfinite(net.score_value)
    assert np.isfinite(net.params_to_vector()).all()


@pytest.mark.faults
def test_kill_worker_eviction_then_readmission_converges():
    """Worker 2 dies at step 2 and comes back at step 6: the run must
    evict it (reason dead), re-admit it when the fault clears, and land
    within tolerance of the uninterrupted elastic run (the degraded
    windows lose worker 2's minibatches from the average — DeepSpark
    relaxed synchrony, not bit-parity)."""
    K = 8
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    batches = make_batches(K * 12, 4, seed=11)

    ref = make_net(updater="sgd", lr=0.05)
    ParallelWrapper(ref, workers=K, averaging_frequency=1, mesh=mesh,
                    elastic=ElasticConfig()).fit(iter(batches))

    net = make_net(updater="sgd", lr=0.05)
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh,
                         elastic=ElasticConfig(evict_after_flags=None))
    inj = FaultInjector(seed=2).kill_worker("2", at_step=2, until_step=6)
    with inject_faults(inj):
        pw.fit(iter(batches))

    assert pw.elastic.evicted_workers == []    # re-admitted
    evs = flight_events("elastic_eviction", component="parallel_wrapper",
                        worker="2")
    assert evs and evs[-1].attrs["reason"] == "dead"
    assert flight_events("elastic_readmission",
                         component="parallel_wrapper", worker="2")
    assert inj.injected and inj.injected[0]["kind"] == "worker_dead"
    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), atol=0.05)
    assert abs(float(net.score_value) - float(ref.score_value)) < 0.05


@pytest.mark.faults
def test_hang_worker_evicts_and_clear_readmits():
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    net = make_net()
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh,
                         elastic=ElasticConfig(evict_after_flags=None,
                                               hang_stall_s=0.0))
    inj = FaultInjector(seed=3).hang_worker("1", at_step=1, until_step=4)
    with inject_faults(inj):
        pw.fit(iter(make_batches(K * 8, 4, seed=13)))
    evs = flight_events("elastic_eviction", component="parallel_wrapper",
                        worker="1")
    assert evs and evs[-1].attrs["reason"] == "hang"
    assert "1" in pw.elastic.active_workers    # hang cleared -> re-admitted


# ------------------------------------------------------------- sync master
def test_sync_master_eviction_equals_healthy_rows_math():
    """Sync DP with a dead data slot == single-device training on the
    batch WITHOUT that slot's rows: the masked loss renormalizes the
    gradient mean over the healthy rows (exact, not approximate)."""
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    rs = np.random.RandomState(17)
    x = rs.randn(32, 6).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 32)]

    net = make_net()
    master = SyncTrainingMaster(mesh=mesh, elastic=ElasticConfig())
    victim = master.elastic.workers[2]         # data slot 2, rows 4:6 of 8
    inj = FaultInjector(seed=4).kill_worker(victim, at_step=0)
    with inject_faults(inj):
        DistributedNetwork(net, master).fit(
            ListDataSetIterator(DataSet(x, y), 8))
    assert master.elastic.summary()["evicted"][victim]["reason"] == "dead"
    assert master.training_stats()["elastic"]["active"] == K - 1

    ref = make_net()
    keep = np.r_[0:4, 6:8]
    for i in range(4):
        bx = x[i * 8:(i + 1) * 8][keep]
        by = y[i * 8:(i + 1) * 8][keep]
        ref.fit(bx, by)
    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), rtol=2e-5, atol=1e-6)


def test_sync_master_readmission_after_fault_clears():
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    rs = np.random.RandomState(19)
    x = rs.randn(64, 6).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]
    net = make_net()
    master = SyncTrainingMaster(mesh=mesh, elastic=ElasticConfig())
    victim = master.elastic.workers[1]
    inj = FaultInjector(seed=5).kill_worker(victim, at_step=1, until_step=4)
    recompiles0 = counter_value("dl4j_recompiles_total")
    with inject_faults(inj):
        DistributedNetwork(net, master).fit(
            ListDataSetIterator(DataSet(x, y), 8))
    assert master.elastic.evicted_workers == []
    assert flight_events("elastic_readmission", component="sync_master",
                         worker=victim)
    assert np.isfinite(net.score_value)
    # eviction/re-admission flip mask VALUES, not the pytree: the elastic
    # sync master always feeds a labels mask, so degrading the mesh never
    # triggers an XLA recompile
    assert counter_value("dl4j_recompiles_total") == recompiles0


# ----------------------------------------------------------- barrier semantics
@pytest.mark.faults
def test_degraded_mode_stops_paying_the_straggler_stall():
    """The synchrony-barrier simulation: lockstep (degraded off) pays the
    slow worker's injected delay every window; degraded mode stops paying
    the moment the worker is evicted.  Eviction is driven by a
    deterministic kill at step 2 (not detector timing), so the two arms
    differ by exactly (n_win - 2) barrier stalls."""
    import time as _time

    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    delay = 0.1
    n_win = 8

    def run(cfg):
        net = make_net()
        pw = ParallelWrapper(net, workers=K, averaging_frequency=1,
                             mesh=mesh, elastic=cfg)
        inj = (FaultInjector(seed=6).delay_worker("1", delay)
               .kill_worker("1", at_step=2))
        t0 = _time.perf_counter()
        with inject_faults(inj):
            pw.fit(iter(make_batches(K * n_win, 4, seed=23)))
        return _time.perf_counter() - t0

    lock_s = run(ElasticConfig(degraded_mode=False, hang_stall_s=0.0))
    deg_s = run(ElasticConfig(evict_after_flags=None, hang_stall_s=0.0))
    # lockstep pays ~n_win * delay; degraded pays only the 2 pre-kill
    # windows — assert a wide margin so compile jitter can't flip it
    assert lock_s >= n_win * delay
    assert deg_s < lock_s - 3 * delay


def test_degraded_windows_counter_increments():
    reg_before = counter_value("dl4j_elastic_degraded_windows_total",
                               component="parallel_wrapper")
    K = 2
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    net = make_net()
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh,
                         elastic=ElasticConfig(readmit_after_windows=10 ** 9))
    pw.elastic.evict("1", "manual", step=0)
    pw.fit(iter(make_batches(4, 4, seed=29)))
    assert counter_value("dl4j_elastic_degraded_windows_total",
                         component="parallel_wrapper") >= reg_before + 2

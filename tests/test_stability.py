"""Training stability engine (docs/resilience.md "Stability"): device-side
non-finite step guard, dynamic loss scaling, divergence sentinel with
auto-rewind, and per-replica poison masking in the data-parallel masters.

Correctness oracles follow the repo's equivalence discipline: the guarded
healthy path must agree with the unguarded one to a few ulps (two
compiled programs: see ``assert_params_within_ulps``), a guarded
poisoned step must be a bit-exact no-op, the wrapper's poison masking
must equal an explicit manual eviction of the same replica, and the sync
master's row masking must equal single-device training on the healthy
rows.  Every fault is driven deterministically by
``FaultInjector.poison_gradients`` (nan | inf | spike, at/until_step).
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.backend import device as backend
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import (
    NeuralNetConfiguration, TrainingStability,
)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.observability import (
    HealthEvaluator, HealthRule, get_flight_recorder, get_registry,
)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel import (
    DistributedNetwork, ElasticConfig, ElasticController,
    ParallelWrapper, SyncTrainingMaster,
)
from deeplearning4j_tpu.resilience import (
    CheckpointManager, FaultInjector, inject_faults, stability,
)

pytestmark = pytest.mark.stability


def make_net(seed=12345, updater="adam", lr=0.01, stab=None):
    b = (NeuralNetConfiguration.builder()
         .seed(seed)
         .updater(updater, learning_rate=lr))
    if stab is not None:
        b.training_stability(stab)
    conf = (b.list()
            .layer(DenseLayer(n_in=6, n_out=10, activation="tanh"))
            .layer(OutputLayer(n_in=10, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def make_batches(n_batches, batch_size, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n_batches):
        x = rs.randn(batch_size, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, batch_size)]
        out.append((x, y))
    return out


def params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# Two DIFFERENT compiled programs are compared here (with and without the
# guard; with and without a loss scale).  Every operation the guard adds is
# exact on healthy data — a multiply by 1.0, by 2^k and by 2^-k, a select
# on a true flag — but the compiler fuses the two programs differently:
# in the compiled text the guarded step recomputes Adam's moments inside
# the fusion that applies the update, the plain step reads them back from
# a fusion of their own, and multiply-adds contract as each fusion allows.
# After the second step (the first with moments that are not zero) the
# moments are still equal bit for bit and the params differ in the last
# place: a rounding a step, which ``np.array_equal`` cannot hold and no
# edit of the guard removes.  The bound is in ulps of a leaf's largest
# entry: an entry that cancelled towards zero carries the absolute error
# of the updates it summed.
ULPS = 8


def assert_params_within_ulps(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        gap = np.abs(x - y).max() / np.spacing(np.abs(x).max())
        assert gap <= ULPS, f"{gap} ulps of the leaf's largest entry"


def all_finite_tree(tree):
    return all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree_util.tree_leaves(tree))


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


# ------------------------------------------------------------ the step guard
def test_guarded_healthy_run_matches_unguarded():
    """The guard changes nothing when nothing is poisoned: the skip mask
    multiplies updates by 1.0 and the loss scale is 1, both exact, so the
    params after identical batches agree to the rounding two compiled
    programs are free to differ by."""
    batches = make_batches(8, 6, seed=1)
    plain = make_net().fit(batches)
    guarded = make_net(stab=TrainingStability(check_every=100)).fit(batches)
    assert_params_within_ulps(plain.params, guarded.params)


def test_poisoned_step_is_bitexact_noop():
    """One poisoned step: params, updater moments, and net state keep
    their exact pre-step values; the device counter records the skip; the
    unguarded contrast run is NaN from the same poison."""
    batches = make_batches(6, 6, seed=2)
    net = make_net(stab=TrainingStability(check_every=100))
    net.fit(batches[:3])
    before_p = jax.tree_util.tree_map(np.asarray, net.params)
    before_u = jax.tree_util.tree_map(
        np.asarray, {k: v for k, v in net.updater_state.items()
                     if k != stability.STATE_KEY})
    inj = FaultInjector(seed=3).poison_gradients("0", at_step=3,
                                                 until_step=4)
    with inject_faults(inj):
        net.fit([batches[3]])
    assert inj.injected[0]["kind"] == "worker_poisoned"
    assert params_equal(before_p, net.params)
    assert params_equal(before_u,
                        {k: v for k, v in net.updater_state.items()
                         if k != stability.STATE_KEY})
    stab = net.updater_state[stability.STATE_KEY]
    assert float(np.asarray(stab["nonfinite_total"])) == 1.0

    unguarded = make_net()
    inj2 = FaultInjector(seed=3).poison_gradients("0", at_step=3,
                                                  until_step=4)
    with inject_faults(inj2):
        unguarded.fit(batches[:4])
    assert not all_finite_tree(unguarded.params)


@pytest.mark.parametrize("mode", ["nan", "inf", "spike"])
def test_poison_modes(mode):
    """nan/inf poison non-finite steps (skipped); spike stays finite (the
    sentinel's domain) but every mode leaves guarded params finite."""
    net = make_net(stab=TrainingStability(check_every=100))
    inj = FaultInjector().poison_gradients("0", at_step=1, until_step=2,
                                           mode=mode)
    with inject_faults(inj):
        net.fit(make_batches(4, 6, seed=3))
    assert all_finite_tree(net.params)
    nf = float(np.asarray(
        net.updater_state[stability.STATE_KEY]["nonfinite_total"]))
    assert nf == (0.0 if mode == "spike" else 1.0)


def test_guarded_run_converges_to_no_fault_trajectory():
    """Acceptance (a): a guarded single-device run with a poisoned step
    skips it and converges back to the no-fault trajectory — and the skip
    flips VALUES, not the trace (zero recompiles at the poison step).
    Both runs train the same small problem to (near) convergence; one
    skipped update early on must wash out."""
    batches = make_batches(10, 8, seed=4) * 15       # 150 steps
    clean = make_net(stab=TrainingStability(check_every=100)).fit(batches)
    poisoned = make_net(stab=TrainingStability(check_every=100))
    poisoned.fit(batches[:3])
    compiles0 = counter_value("dl4j_compiles_total")
    recompiles0 = counter_value("dl4j_recompiles_total")
    inj = FaultInjector().poison_gradients("0", at_step=3, until_step=4)
    with inject_faults(inj):
        poisoned.fit(batches[3:])
    assert counter_value("dl4j_compiles_total") == compiles0
    assert counter_value("dl4j_recompiles_total") == recompiles0
    # same minimum: compare the trained functions on held-out data and
    # the converged parameter vectors
    probe = make_batches(1, 16, seed=99)[0][0]
    np.testing.assert_allclose(np.asarray(poisoned.output(probe)),
                               np.asarray(clean.output(probe)), atol=0.02)
    np.testing.assert_allclose(poisoned.params_to_vector(),
                               clean.params_to_vector(), atol=0.05)


# ------------------------------------------------------------- loss scaling
def test_static_loss_scaling_matches_unscaled():
    """Power-of-two scales multiply/divide exactly: a statically scaled
    run agrees with the unscaled one on healthy data to the rounding two
    compiled programs are free to differ by."""
    batches = make_batches(6, 6, seed=5)
    plain = make_net().fit(batches)
    scaled = make_net(stab=TrainingStability(
        loss_scaling="static", loss_scale=2.0 ** 10,
        check_every=100)).fit(batches)
    assert_params_within_ulps(plain.params, scaled.params)
    st = scaled.updater_state[stability.STATE_KEY]
    assert float(np.asarray(st["loss_scale"])) == 2.0 ** 10


def test_dynamic_loss_scale_grows_and_halves():
    stab = TrainingStability(loss_scaling="dynamic", loss_scale=2.0 ** 8,
                             loss_scale_growth_interval=3, check_every=100)
    net = make_net(stab=stab)
    net.fit(make_batches(7, 6, seed=6))      # 7 finite steps: 2 growths
    scale = float(np.asarray(
        net.updater_state[stability.STATE_KEY]["loss_scale"]))
    assert scale == 2.0 ** 10
    inj = FaultInjector().poison_gradients("0", at_step=7, until_step=8,
                                           mode="inf")
    with inject_faults(inj):
        net.fit(make_batches(1, 6, seed=7))
    scale = float(np.asarray(
        net.updater_state[stability.STATE_KEY]["loss_scale"]))
    assert scale == 2.0 ** 9                 # halved on overflow


def test_scale_state_checkpoints_and_resumes():
    """The scale state rides in the updater-state pytree, so a resumed
    run continues with the exact scale it crashed with."""
    stab = TrainingStability(loss_scaling="dynamic", loss_scale=2.0 ** 8,
                             loss_scale_growth_interval=2, check_every=100)
    with tempfile.TemporaryDirectory() as tmp:
        cm = CheckpointManager(tmp, save_every_steps=2, async_save=False)
        net = make_net(stab=stab)
        net.fit(make_batches(6, 6, seed=8), checkpoint_manager=cm)
        want = jax.tree_util.tree_map(
            np.asarray, net.updater_state[stability.STATE_KEY])
        # the save landed at step 6 (boundary save); restore into a fresh
        # net and compare the whole stability subtree
        fresh = make_net(stab=stab)
        cm2 = CheckpointManager(tmp, async_save=False)
        cm2.restore(fresh)
        got = jax.tree_util.tree_map(
            np.asarray, fresh.updater_state[stability.STATE_KEY])
        assert params_equal(want, got)
        assert fresh.iteration == net.iteration
        cm.close()
        cm2.close()


# ------------------------------------------------------- divergence sentinel
def test_sentinel_escalates_backoff_then_rewind_and_resumes_past_failure():
    """Acceptance (c): sustained poison drives skip -> LR backoff ->
    auto-rewind to the last good checkpoint; once the poison clears the
    run resumes and trains PAST the original failure step with finite
    params — with zero recompiles throughout."""
    # poison spans iterations 8..19: long enough that the escalation
    # ladder (backoff at the 1st hot check, rewind at the next
    # non-cooldown check) fires while the fault is live; the post-rewind
    # cooldown (6 checks = 12 steps) lets the rewound run march through
    # the poisoned region on guard-skips alone and come out healthy
    stab = TrainingStability(check_every=2, nonfinite_streak=2,
                             rewind_cooldown_checks=6)
    batches = make_batches(40, 8, seed=9)
    net = make_net(stab=stab)
    with tempfile.TemporaryDirectory() as tmp:
        cm = CheckpointManager(tmp, keep=4, save_every_steps=4,
                               async_save=False)
        net.fit(batches[:8], checkpoint_manager=cm)     # healthy prefix
        compiles0 = counter_value("dl4j_compiles_total")
        inj = FaultInjector().poison_gradients("0", at_step=8,
                                               until_step=20)
        with inject_faults(inj):
            net.fit(batches[8:], checkpoint_manager=cm)
        cm.close()
    assert counter_value("dl4j_compiles_total") == compiles0
    assert flight_events("divergence_backoff", component="MultiLayerNetwork")
    rewinds = flight_events("divergence_rewind",
                            component="MultiLayerNetwork")
    assert rewinds
    assert rewinds[0].attrs["to_step"] <= 8
    assert net.iteration > 20          # resumed past the failure region
    assert all_finite_tree(net.params)
    assert all_finite_tree(
        {k: v for k, v in net.updater_state.items()
         if k != stability.STATE_KEY})


def test_rewind_without_checkpoint_manager_downgrades_to_backoff():
    stab = TrainingStability(check_every=1, nonfinite_streak=1,
                             rewind_cooldown_checks=1, lr_backoff=0.5)
    net = make_net(stab=stab)
    inj = FaultInjector().poison_gradients("0", at_step=0)
    with inject_faults(inj):
        net.fit(make_batches(6, 6, seed=10))
    lr_scale = float(np.asarray(
        net.updater_state[stability.STATE_KEY]["lr_scale"]))
    assert lr_scale < 1.0              # backoffs landed in the state
    assert all_finite_tree(net.params)


def test_resumed_run_does_not_recount_checkpointed_nonfinite():
    """A checkpointed nonfinite_total restored by auto-resume is history:
    the fresh runtime must baseline on it, not re-publish it as a new
    delta (which would double-count the metric and could trip a spurious
    backoff on a healthy resumed run)."""
    stab = TrainingStability(check_every=1, nonfinite_streak=1)
    with tempfile.TemporaryDirectory() as tmp:
        cm = CheckpointManager(tmp, save_every_steps=2, async_save=False)
        net = make_net(stab=stab)
        inj = FaultInjector().poison_gradients("0", at_step=1, until_step=2)
        with inject_faults(inj):
            net.fit(make_batches(4, 6, seed=20), checkpoint_manager=cm)
        total0 = counter_value("dl4j_nonfinite_steps_total",
                               component="MultiLayerNetwork")
        backoffs0 = counter_value("dl4j_divergence_backoffs_total",
                                  component="MultiLayerNetwork")
        # "new process": fresh facade + fresh runtime, same checkpoint
        # dir, same stream — resume skips the consumed prefix
        net2 = make_net(stab=stab)
        net2.fit(make_batches(8, 6, seed=21),
                 checkpoint_manager=CheckpointManager(tmp, async_save=False))
        assert net2.iteration > 4          # resumed ahead, trained on
        assert counter_value("dl4j_nonfinite_steps_total",
                             component="MultiLayerNetwork") == total0
        assert counter_value("dl4j_divergence_backoffs_total",
                             component="MultiLayerNetwork") == backoffs0
        cm.close()


def test_wrapper_without_cm_keeps_backing_off_instead_of_stalling():
    """A master with no CheckpointManager downgrades every rewind verdict
    to a further LR backoff (mirrors poll_net) — sustained divergence
    must keep being mitigated, not silently dropped after level 1."""
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    stab = TrainingStability(check_every=1, nonfinite_streak=1,
                             rewind_cooldown_checks=1)
    net = make_net(stab=stab)
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh)
    backoffs0 = counter_value("dl4j_divergence_backoffs_total",
                              component="parallel_wrapper")
    inj = FaultInjector()
    for k in range(K):
        inj.poison_gradients(str(k), at_step=0)   # every replica: nf loss
    with inject_faults(inj):
        pw.fit(iter(DataSet(x, y) for x, y in make_batches(K * 8, 4,
                                                           seed=22)))
    assert counter_value("dl4j_divergence_backoffs_total",
                         component="parallel_wrapper") >= backoffs0 + 2
    assert all_finite_tree(net.params)


def test_spike_mode_trips_the_sentinel():
    """A finite loss spike (poison mode 'spike') must escalate through
    the spike-strike path, not the non-finite path."""
    stab = TrainingStability(check_every=1, spike_factor=5.0,
                             spike_patience=2)
    net = make_net(stab=stab)
    net.fit(make_batches(6, 6, seed=11))   # establish the loss baseline
    inj = FaultInjector().poison_gradients("0", at_step=6, mode="spike")
    with inject_faults(inj):
        net.fit(make_batches(6, 6, seed=12))
    assert flight_events("divergence_backoff",
                         component="MultiLayerNetwork")


# ------------------------------------------- per-replica poisoning (wrapper)
def test_wrapper_poison_masking_equals_manual_eviction():
    """Acceptance (b, wrapper): the healthy replicas' window average with
    replica 1 poisoned is bit-exact the average with replica 1 manually
    evicted — the poison mask IS the elastic [K] weight mask."""
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    stab = TrainingStability(check_every=100)
    data = make_batches(K * 6, 4, seed=13)
    ds = [DataSet(x, y) for x, y in data]

    evicted = make_net(stab=stab)
    ctrl = ElasticController("parallel_wrapper", [str(k) for k in range(K)],
                             config=ElasticConfig())
    assert ctrl.evict("1", "manual", step=0)
    ParallelWrapper(evicted, workers=K, averaging_frequency=1,
                    mesh=mesh, elastic=ctrl).fit(iter(ds))

    poisoned = make_net(stab=stab)
    inj = FaultInjector().poison_gradients("1", at_step=0)
    with inject_faults(inj):
        ParallelWrapper(poisoned, workers=K, averaging_frequency=1,
                        mesh=mesh).fit(iter(ds))
    assert params_equal(evicted.params, poisoned.params)
    assert all_finite_tree(poisoned.params)


def test_wrapper_repeat_offender_evicted_as_poisoned():
    """Acceptance (b): a repeat offender is handed to the elastic layer
    as eviction reason "poisoned", named in metrics + flight events."""
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    stab = TrainingStability(check_every=1, poison_evict_after=2)
    net = make_net(stab=stab)
    pw = ParallelWrapper(net, workers=K, averaging_frequency=1, mesh=mesh,
                         elastic=ElasticConfig())
    ev0 = counter_value("dl4j_elastic_evictions_total",
                        component="parallel_wrapper", worker="1",
                        reason="poisoned")
    recompiles0 = counter_value("dl4j_recompiles_total")
    inj = FaultInjector().poison_gradients("1", at_step=0)
    with inject_faults(inj):
        pw.fit(iter(DataSet(x, y) for x, y in make_batches(K * 8, 4,
                                                           seed=14)))
    # poison masking + eviction flip VALUES, not the pytree: zero
    # steady-state recompiles while the mesh degrades
    assert counter_value("dl4j_recompiles_total") == recompiles0
    assert "1" in pw.elastic.evicted_workers
    assert pw.elastic.summary()["evicted"]["1"]["reason"] == "poisoned"
    assert counter_value("dl4j_elastic_evictions_total",
                         component="parallel_wrapper", worker="1",
                         reason="poisoned") == ev0 + 1
    assert flight_events("elastic_eviction", component="parallel_wrapper",
                         worker="1", reason="poisoned")
    assert flight_events("replica_poisoned", component="parallel_wrapper",
                         worker="1")
    assert counter_value("dl4j_poisoned_replica_windows_total",
                         component="parallel_wrapper", worker="1") > 0


def test_wrapper_poison_clears_and_readmits():
    """Poison with until_step: the replica is evicted while poisoned and
    probationally re-admitted once the injector state clears."""
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    stab = TrainingStability(check_every=1, poison_evict_after=1)
    net = make_net(stab=stab)
    pw = ParallelWrapper(
        net, workers=K, averaging_frequency=1, mesh=mesh,
        elastic=ElasticConfig(readmit_after_windows=2))
    inj = FaultInjector().poison_gradients("1", at_step=0, until_step=3)
    with inject_faults(inj):
        pw.fit(iter(DataSet(x, y) for x, y in make_batches(K * 10, 4,
                                                           seed=15)))
    assert pw.elastic.evicted_workers == []
    assert flight_events("elastic_readmission",
                         component="parallel_wrapper", worker="1")
    assert all_finite_tree(net.params)


# --------------------------------------------- per-replica poisoning (sync)
def test_sync_master_poison_equals_healthy_rows_math():
    """Acceptance (b, sync master): with one data slot poisoned, the
    global gradient equals single-device training on the healthy rows
    (the poisoned rows are zeroed pre-forward and renormalized out of the
    masked loss mean)."""
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    stab = TrainingStability(check_every=100)
    rs = np.random.RandomState(17)
    x = rs.randn(32, 6).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 32)]

    net = make_net(stab=stab)
    master = SyncTrainingMaster(mesh=mesh)
    inj = FaultInjector(seed=4).poison_gradients("d2", at_step=0)
    with inject_faults(inj):
        DistributedNetwork(net, master).fit(
            ListDataSetIterator(DataSet(x, y), 8))
    assert all_finite_tree(net.params)

    ref = make_net(stab=stab)
    keep = np.r_[0:4, 6:8]                  # slot 2 owns rows 4:6 of 8
    for i in range(4):
        ref.fit(x[i * 8:(i + 1) * 8][keep], y[i * 8:(i + 1) * 8][keep])
    np.testing.assert_allclose(net.params_to_vector(),
                               ref.params_to_vector(), rtol=2e-5,
                               atol=1e-6)


def test_sync_master_repeat_offender_evicted_as_poisoned():
    K = 4
    mesh = backend.default_mesh(data=K, devices=jax.devices()[:K])
    stab = TrainingStability(check_every=1, poison_evict_after=2)
    net = make_net(stab=stab)
    master = SyncTrainingMaster(mesh=mesh, elastic=ElasticConfig())
    victim = master.elastic.workers[1]
    recompiles0 = counter_value("dl4j_recompiles_total")
    inj = FaultInjector().poison_gradients(victim, at_step=0)
    with inject_faults(inj):
        DistributedNetwork(net, master).fit(
            ListDataSetIterator(
                DataSet(*map(np.concatenate,
                             zip(*[(x, y) for x, y in
                                   make_batches(10, 8, seed=18)]))), 8))
    assert master.elastic.summary()["evicted"][victim]["reason"] == \
        "poisoned"
    assert counter_value("dl4j_recompiles_total") == recompiles0
    assert all_finite_tree(net.params)


# ------------------------------------------------------- health + earlystop
def test_stability_health_rules():
    reg = MetricsRegistry()
    rt = stability.StabilityRuntime(
        "hr", TrainingStability(check_every=1), registry=reg)
    rules = [HealthRule("nf_budget", "max_nonfinite_steps", 2),
             HealthRule("rw_budget", "max_divergence_rewinds", 0)]
    ev = HealthEvaluator(rules, component="hr_test", registry=reg)
    assert ev.evaluate().healthy
    rt._publish(3.0, 1.0)                  # 3 non-finite steps harvested
    verdict = ev.evaluate()
    assert not verdict.healthy
    assert verdict.failing[0]["observed"] == 3.0


def test_invalid_score_condition_watches_nonfinite_counter():
    """Satellite: early stopping catches NaN through the device-side
    counter even though the guard keeps the score finite."""
    from deeplearning4j_tpu.earlystopping import (
        InvalidScoreIterationTerminationCondition,
    )

    cond = InvalidScoreIterationTerminationCondition()
    cond.initialize()
    assert not cond.terminate(0.5)
    net = make_net(stab=TrainingStability(check_every=1))
    inj = FaultInjector().poison_gradients("0", at_step=1, until_step=2)
    with inject_faults(inj):
        net.fit(make_batches(3, 6, seed=19))
    # the guarded score is finite, but the counter advanced
    assert np.isfinite(net.score_value)
    assert cond.terminate(net.score_value)
    # classic path still works
    cond2 = InvalidScoreIterationTerminationCondition()
    cond2.initialize()
    assert cond2.terminate(float("nan"))
    # component filter: another component's skipped step must not
    # terminate a run watching only its own counter children
    cond3 = InvalidScoreIterationTerminationCondition(
        component="ComputationGraph")
    cond3.initialize()
    net2 = make_net(stab=TrainingStability(check_every=1))
    inj2 = FaultInjector().poison_gradients("0", at_step=1, until_step=2)
    with inject_faults(inj2):
        net2.fit(make_batches(3, 6, seed=23))   # MultiLayerNetwork bump
    assert not cond3.terminate(0.5)


# ----------------------------------------------------------- pipeline + conf
def test_pipeline_gradient_normalization_downgrade_is_loud():
    """Satellite: the sharded-fast-path downgrade emits a one-shot
    RuntimeWarning + a flight event naming gradient_normalization."""
    from deeplearning4j_tpu.parallel.pipeline import (
        PipelineParallelTrainingMaster,
    )

    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater("sgd", learning_rate=0.1)
            .gradient_normalization("clip_l2_per_layer", 1.0)
            .list()
            .layer(DenseLayer(n_in=6, n_out=8, activation="relu"))
            .layer(DenseLayer(n_in=8, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    master = PipelineParallelTrainingMaster(
        n_stages=2, n_microbatches=2, mode="compiled",
        devices=jax.devices()[:2])
    with pytest.warns(RuntimeWarning, match="fast path DISABLED"):
        master._build(net)
    evs = flight_events("pipeline_fast_path_downgrade",
                        component="pipeline_master")
    assert evs and "gradient_normalization='clip_l2_per_layer'" in \
        evs[-1].attrs["reasons"][0]


def test_training_stability_conf_validation_and_serde():
    with pytest.raises(ValueError, match="loss_scaling"):
        TrainingStability(loss_scaling="bogus")
    with pytest.raises(ValueError, match="lr_backoff"):
        TrainingStability(lr_backoff=1.5)
    with pytest.raises(ValueError, match="takes no kwargs"):
        NeuralNetConfiguration.builder().training_stability(
            False, check_every=3)
    stab = TrainingStability(loss_scaling="dynamic", check_every=7)
    conf = (NeuralNetConfiguration.builder().training_stability(stab)
            .list()
            .layer(DenseLayer(n_in=4, n_out=4, activation="relu"))
            .layer(OutputLayer(n_in=4, n_out=2, loss="mcxent",
                               activation="softmax"))
            .build())
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.stability == stab

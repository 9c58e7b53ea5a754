"""Compiled pipeline schedule: one XLA program (shard_map + scan + ppermute)
for homogeneous-block nets; equivalence to serial training is the oracle
(the reference's distributed-vs-local pattern, SURVEY.md §4).
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (
    DistributedNetwork, PipelineParallelTrainingMaster,
)
from deeplearning4j_tpu.parallel.pipeline import find_periodic_run, _layer_sig


def block_mlp(n_blocks=4, width=16, seed=7, updater="sgd", lr=0.2, l2=0.0):
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(updater, learning_rate=lr).list()
         .layer(DenseLayer(n_in=8, n_out=width, activation="tanh", l2=l2)))
    for _ in range(n_blocks):
        b.layer(DenseLayer(n_in=width, n_out=width, activation="tanh", l2=l2))
    b.layer(OutputLayer(n_in=width, n_out=4, l2=l2))
    return MultiLayerNetwork(b.build()).init()


def data(n=32, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, n)]
    return x, y


def test_find_periodic_run():
    net = block_mlp(n_blocks=4)
    sigs = [_layer_sig(l) for l in net.layers]
    run = find_periodic_run(sigs, 4)
    assert run == (1, 1, 4)
    # with 2 stages, the 4-block run still qualifies
    assert find_periodic_run(sigs, 2) == (1, 1, 4)
    # no run long enough for 8 stages
    assert find_periodic_run(sigs, 8) is None


def _fit_pp(net, x, y, n_stages, n_micro, epochs=2):
    master = PipelineParallelTrainingMaster(
        n_stages=n_stages, n_microbatches=n_micro,
        devices=jax.devices()[:n_stages])
    DistributedNetwork(net, master).fit(
        ListDataSetIterator(DataSet(x, y), len(x)), epochs=epochs)
    return master


@pytest.mark.parametrize("n_stages,n_micro", [(2, 4), (4, 2), (4, 4)])
def test_compiled_pipeline_matches_serial(n_stages, n_micro):
    x, y = data(32)
    serial = block_mlp()
    serial.fit(x, y)
    serial.fit(x, y)

    pp_net = block_mlp()
    master = _fit_pp(pp_net, x, y, n_stages, n_micro)
    assert master._mode == "compiled"
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(pp_net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")
    assert abs(serial.score_value - pp_net.score_value) < 1e-4


def test_compiled_pipeline_momentum_state_roundtrips():
    x, y = data(32)
    serial = block_mlp(updater="nesterovs", lr=0.1)
    serial.fit(x, y)
    serial.fit(x, y)
    pp_net = block_mlp(updater="nesterovs", lr=0.1)
    master = _fit_pp(pp_net, x, y, 4, 2)
    assert master._mode == "compiled"
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(pp_net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")
    # updater momentum state mirrored back per layer
    assert set(serial.updater_state["v"]) == set(pp_net.updater_state["v"])


def test_compiled_pipeline_regularization():
    x, y = data(16)
    serial = block_mlp(l2=0.01, seed=9)
    serial.fit(x, y)
    pp_net = block_mlp(l2=0.01, seed=9)
    master = _fit_pp(pp_net, x, y, 2, 2, epochs=1)
    assert master._mode == "compiled"
    assert abs(serial.score_value - pp_net.score_value) < 1e-5


def test_compiled_pipeline_single_compile():
    x, y = data(32)
    pp_net = block_mlp(seed=11)
    master = PipelineParallelTrainingMaster(
        n_stages=4, n_microbatches=4, devices=jax.devices()[:4])
    dn = DistributedNetwork(pp_net, master)
    dn.fit(ListDataSetIterator(DataSet(x, y), len(x)), epochs=3)
    assert master._mode == "compiled"
    # one program for the whole config: 3 epochs reuse one compiled step
    assert len(master._compiled_steps) == 1
    assert next(iter(master._compiled_steps.values()))._cache_size() == 1


def test_compiled_pipeline_handles_batch_size_change():
    # regression: second fit with a different batch size must rebuild the
    # schedule for the new microbatch shape, not crash on the stale probe
    x, y = data(32)
    pp_net = block_mlp(seed=13)
    master = PipelineParallelTrainingMaster(
        n_stages=2, n_microbatches=2, devices=jax.devices()[:2])
    dn = DistributedNetwork(pp_net, master)
    dn.fit(ListDataSetIterator(DataSet(x, y), 32))
    dn.fit(ListDataSetIterator(DataSet(x[:16], y[:16]), 16))
    assert master._mode == "compiled"
    assert len(master._compiled_steps) == 2
    assert np.isfinite(pp_net.score_value)


def hetero_mlp(seed=3, lr=0.1):
    """Non-periodic stack: every boundary has a different width, so no
    periodic run exists — exercises the switch-based compiled path."""
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater("sgd", learning_rate=lr).list()
         .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
         .layer(DenseLayer(n_in=16, n_out=12, activation="relu"))
         .layer(DenseLayer(n_in=12, n_out=8, activation="tanh"))
         .layer(OutputLayer(n_in=8, n_out=4)))
    return MultiLayerNetwork(b.build()).init()


@pytest.mark.parametrize("n_stages,n_micro", [(2, 4), (4, 2)])
def test_heterogeneous_compiles_and_matches_serial(n_stages, n_micro):
    """Round 4: non-periodic stacks COMPILE (lax.switch stages, padded
    activation buffer) — serial equivalence is the oracle."""
    x, y = data(32)
    serial = hetero_mlp()
    serial.fit(x, y)
    serial.fit(x, y)
    net = hetero_mlp()
    master = _fit_pp(net, x, y, n_stages, n_micro)
    assert master._mode == "compiled"
    assert master._compiled_kind == "hetero"
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")
    assert abs(serial.score_value - net.score_value) < 1e-4


def conv_then_dense(seed=5, lr=0.05):
    """The conv-then-dense shape the compiled-heterogeneity work targets:
    CNN input, conv + pooling stages, preprocessor-flattened dense head."""
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (
        ConvolutionLayer, SubsamplingLayer,
    )

    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater("sgd", learning_rate=lr).list()
         .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                 activation="relu"))
         .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
         .layer(DenseLayer(n_out=16, activation="tanh"))
         .layer(OutputLayer(n_out=4)))
    b.set_input_type(InputType.convolutional(8, 8, 1))
    return MultiLayerNetwork(b.build()).init()


def test_conv_then_dense_pipeline_compiles():
    rs = np.random.RandomState(0)
    x = rs.rand(16, 8, 8, 1).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 16)]
    serial = conv_then_dense()
    serial.fit(x, y)
    net = conv_then_dense()
    master = _fit_pp(net, x, y, 2, 2, epochs=1)
    assert master._mode == "compiled"
    assert master._compiled_kind == "hetero"
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")


def test_orchestrated_opt_in_and_1f1b_schedules_match_serial():
    """mode='orchestrated' still exists (real per-device param placement),
    and both schedules produce serial-identical math — 1F1B only reorders
    the same vjp calls (memory, not numerics)."""
    x, y = data(16)
    for schedule in ("gpipe", "1f1b"):
        serial = hetero_mlp(seed=21)
        serial.fit(x, y)
        net = hetero_mlp(seed=21)
        master = PipelineParallelTrainingMaster(
            n_stages=2, n_microbatches=4, devices=jax.devices()[:2],
            mode="orchestrated", schedule=schedule)
        DistributedNetwork(net, master).fit(
            ListDataSetIterator(DataSet(x, y), 16))
        assert master._mode == "orchestrated"
        for ln in serial.params:
            for pn in serial.params[ln]:
                np.testing.assert_allclose(
                    np.asarray(serial.params[ln][pn]),
                    np.asarray(net.params[ln][pn]), atol=2e-5,
                    err_msg=f"{schedule}: {ln}/{pn}")


def test_bubble_fraction_analytic_and_measured():
    from deeplearning4j_tpu.parallel.pipeline import measure_bubble_fraction

    m = PipelineParallelTrainingMaster(n_stages=4, n_microbatches=4,
                                       devices=jax.devices()[:4])
    assert abs(m.bubble_fraction() - 3 / 7) < 1e-9

    def make_batch(n):
        x, y = data(n)
        return DataSet(x, y)

    stats = measure_bubble_fraction(
        lambda: block_mlp(n_blocks=4, seed=17), make_batch,
        n_stages=2, mb_size=8, m_small=2, m_large=4, iters=2,
        devices=jax.devices()[:2])
    assert stats["mode"] == "compiled"
    assert 0.0 <= stats["bubble_analytic"] < 1.0
    assert np.isfinite(stats["bubble_measured"])


def test_hetero_sharded_params_with_adam_matches_serial():
    """Round 5: the flat-row SHARDED param layout must be exact through a
    STATEFUL elementwise updater (adam m/v ride the same flat rows)."""
    x, y = data(32)

    def make():
        b = (NeuralNetConfiguration.builder().seed(17)
             .updater("adam", learning_rate=0.01).list()
             .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
             .layer(DenseLayer(n_in=16, n_out=12, activation="relu"))
             .layer(DenseLayer(n_in=12, n_out=8, activation="tanh"))
             .layer(OutputLayer(n_in=8, n_out=4)))
        return MultiLayerNetwork(b.build()).init()

    serial = make()
    serial.fit(x, y)
    serial.fit(x, y)
    net = make()
    master = _fit_pp(net, x, y, 2, 4)
    assert master._compiled_kind == "hetero"
    assert master._hetero_sharded
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")
    # adam state rode the flat rows and unflattened back per layer
    for slot in ("m", "v"):
        for ln in serial.updater_state[slot]:
            for pn in serial.updater_state[slot][ln]:
                np.testing.assert_allclose(
                    np.asarray(serial.updater_state[slot][ln][pn]),
                    np.asarray(net.updater_state[slot][ln][pn]), atol=2e-5,
                    err_msg=f"{slot}/{ln}/{pn}")


def test_hetero_params_actually_partitioned_per_device():
    """The memory point of pipeline parallelism: with
    the sharded layout, each device holds ~1/S of the param bytes, not a
    full replica."""
    x, y = data(32)
    net = hetero_mlp()
    total = sum(int(np.prod(p.shape)) * 4
                for lp in net.params.values() for p in lp.values())
    master = PipelineParallelTrainingMaster(
        n_stages=2, n_microbatches=4, devices=jax.devices()[:2])
    master._build(net)
    assert master._hetero_sharded
    rows = jax.device_put(master._hetero_flatten(net.params),
                          master._row_sharding)
    shard_bytes = {s.device: s.data.nbytes for s in rows.addressable_shards}
    assert len(shard_bytes) == 2
    for dev, nb in shard_bytes.items():
        # Pmax row per device: strictly less than the whole model, and no
        # more than the padded largest stage
        assert nb < total, f"{dev} holds a full replica ({nb} >= {total})"
        assert nb == master._flat_pmax * 4


def test_hetero_falls_back_to_replicated_with_lr_overrides(capsys):
    """Per-layer lr overrides break the one-pseudo-layer updater trick; the
    build must keep params replicated (with a note) and stay serially
    exact."""
    x, y = data(16)

    def make():
        b = (NeuralNetConfiguration.builder().seed(19)
             .updater("sgd", learning_rate=0.1).list()
             .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
             .layer(DenseLayer(n_in=16, n_out=12, activation="relu",
                               learning_rate=0.05))
             .layer(OutputLayer(n_in=12, n_out=4)))
        return MultiLayerNetwork(b.build()).init()

    serial = make()
    serial.fit(x, y)
    net = make()
    master = _fit_pp(net, x, y, 2, 2, epochs=1)
    assert master._compiled_kind == "hetero"
    assert not master._hetero_sharded
    assert "REPLICATED" in capsys.readouterr().err  # the one-time note fired
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")


def test_pipeline_rejects_net_without_output_tail_early():
    b = (NeuralNetConfiguration.builder().seed(23)
         .updater("sgd", learning_rate=0.1).list()
         .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
         .layer(DenseLayer(n_in=16, n_out=4, activation="identity")))
    net = MultiLayerNetwork(b.build()).init()
    master = PipelineParallelTrainingMaster(
        n_stages=2, n_microbatches=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="score"):
        master._build(net)


@pytest.mark.parametrize("maker", ["periodic", "hetero"])
def test_remat_pipeline_matches_serial(maker):
    """remat=True (jax.checkpoint per schedule tick — the compiled-path
    counterpart of 1F1B's activation-memory win) must not change numerics."""
    x, y = data(32)
    make = (lambda: block_mlp(seed=31)) if maker == "periodic" \
        else (lambda: hetero_mlp(seed=31))
    serial = make()
    serial.fit(x, y)
    net = make()
    master = PipelineParallelTrainingMaster(
        n_stages=2, n_microbatches=4, devices=jax.devices()[:2], remat=True)
    DistributedNetwork(net, master).fit(
        ListDataSetIterator(DataSet(x, y), 32))
    assert master._mode == "compiled"
    assert master._compiled_kind == ("periodic" if maker == "periodic"
                                     else "hetero")
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(net.params[ln][pn]), atol=2e-5,
                err_msg=f"{maker}: {ln}/{pn}")


def test_remat_rejected_on_orchestrated_mode():
    with pytest.raises(ValueError, match="remat"):
        PipelineParallelTrainingMaster(n_stages=2, mode="orchestrated",
                                       remat=True,
                                       devices=jax.devices()[:2])


def test_hetero_sharded_randomized_config_sweep():
    """Seeded property sweep over the sharded-hetero config space: random
    widths/depths/updaters/stage counts must all match serial training
    (the flat-row layout has per-config offsets — exercise many)."""
    rs = np.random.RandomState(77)
    for trial in range(4):
        depth = int(rs.randint(3, 7))
        widths = [int(rs.choice([6, 10, 14, 18, 22])) for _ in range(depth)]
        updater = ["sgd", "nesterovs", "adam", "rmsprop"][trial % 4]
        n_stages = int(rs.choice([2, 3, 4]))
        n_micro = int(rs.choice([2, 4]))
        acts = ["tanh", "relu", "sigmoid"]

        def make():
            b = (NeuralNetConfiguration.builder().seed(100 + trial)
                 .updater(updater, learning_rate=0.05).list())
            prev = 8
            for i, w in enumerate(widths):
                b.layer(DenseLayer(n_in=prev, n_out=w,
                                   activation=acts[i % 3]))
                prev = w
            b.layer(OutputLayer(n_in=prev, n_out=4))
            return MultiLayerNetwork(b.build()).init()

        x, y = data(n_micro * 8, seed=trial)
        serial = make()
        serial.fit(x, y)
        net = make()
        master = _fit_pp(net, x, y, n_stages, n_micro, epochs=1)
        cfg = (f"trial {trial}: widths={widths} updater={updater} "
               f"S={n_stages} M={n_micro}")
        assert master._compiled_kind == "hetero", cfg
        assert master._hetero_sharded, cfg
        for ln in serial.params:
            for pn in serial.params[ln]:
                np.testing.assert_allclose(
                    np.asarray(serial.params[ln][pn]),
                    np.asarray(net.params[ln][pn]), atol=3e-5,
                    err_msg=f"{cfg}: {ln}/{pn}")

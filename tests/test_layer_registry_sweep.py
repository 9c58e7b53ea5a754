"""Registry-wide invariants: EVERY registered layer type round-trips its
config through the subtype registry (the Jackson @JsonSubTypes contract,
reference custom-layer tests ``nn/layers/custom/``) and, when parameterised,
initialises + applies with matching shapes.

A sweep rather than per-layer tests: a newly registered layer gets this
coverage automatically or fails loudly here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeplearning4j_tpu.nn.layers  # noqa: F401 — populate the registry
from deeplearning4j_tpu.nn.layers import base

# minimal constructor kwargs per type (sizes chosen tiny); None = defaults ok
_KWARGS = {
    "ActivationLayer": dict(activation="relu"),
    "AutoEncoder": dict(n_in=6, n_out=4),
    "BatchNormalization": dict(n_out=5),
    "ConvolutionLayer": dict(n_in=2, n_out=3, kernel_size=(3, 3)),
    "DenseLayer": dict(n_in=4, n_out=3),
    "DropoutLayer": dict(dropout=0.5),
    "EmbeddingLayer": dict(n_in=7, n_out=4),
    "GatedDeltaNetLayer": dict(n_in=4, n_out=4, n_heads=2, d_k=3, d_v=2),
    "GatedMLP": dict(n_in=4, n_out=4, hidden=6),
    "GlobalPoolingLayer": dict(),
    "GravesBidirectionalLSTM": dict(n_in=3, n_out=4),
    "GravesLSTM": dict(n_in=3, n_out=4),
    "HyperConnectionBlock": dict(
        n_in=8, streams=2,
        layers=(base._LAYER_REGISTRY["RMSNorm"](n_in=4),
                base._LAYER_REGISTRY["GatedMLP"](n_in=4, n_out=4, hidden=6))),
    "HyperStreamExpand": dict(n_in=4, streams=2),
    "HyperStreamReduce": dict(n_in=8, streams=2),
    "LSTM": dict(n_in=3, n_out=4),
    "KimiDeltaAttentionLayer": dict(n_in=4, n_out=4, n_heads=2, d_k=3, d_v=2),
    "LatentAttentionLayer": dict(n_in=8, n_out=8, n_heads=2, q_rank=6,
                                 kv_rank=4, nope_dim=4, rope_dim=2, v_dim=4),
    "LayerNorm": dict(n_in=5),
    "LocalResponseNormalization": dict(),
    "MambaLayer": dict(n_in=4, n_out=4, d_state=3, dt_rank=2),
    "MoELayer": dict(n_in=4, n_out=4, num_experts=2),
    "OutputLayer": dict(n_in=4, n_out=3),
    "RBM": dict(n_in=6, n_out=4),
    "RMSNorm": dict(n_in=5),
    "ResidualBlock": None,  # composite: exercised in test_mixed/test_graph
    "RnnOutputLayer": dict(n_in=4, n_out=3),
    "RoutedMoELayer": dict(n_in=4, n_out=4, n_experts=4, top_k=2, hidden=6,
                           shared=6, experts_held=(1, 2)),
    "SelfAttentionLayer": dict(n_in=4, n_out=4, n_heads=2),
    "SubsamplingLayer": dict(kernel_size=(2, 2), stride=(2, 2)),
}

# input shape per type for the apply smoke (batch of 2)
_INPUTS = {
    "ActivationLayer": (2, 5),
    "AutoEncoder": (2, 6),
    "BatchNormalization": (2, 5),
    "ConvolutionLayer": (2, 6, 6, 2),
    "DenseLayer": (2, 4),
    "DropoutLayer": (2, 5),
    "EmbeddingLayer": (2, 3),          # integer ids
    "GatedDeltaNetLayer": (2, 5, 4),
    "GatedMLP": (2, 4),
    "GlobalPoolingLayer": (2, 4, 4, 3),
    "GravesBidirectionalLSTM": (2, 5, 3),
    "GravesLSTM": (2, 5, 3),
    "HyperConnectionBlock": (2, 5, 8),
    "HyperStreamExpand": (2, 5, 4),
    "HyperStreamReduce": (2, 5, 8),
    "LSTM": (2, 5, 3),
    "KimiDeltaAttentionLayer": (2, 5, 4),
    "LatentAttentionLayer": (2, 5, 8),
    "LayerNorm": (2, 5),
    "LocalResponseNormalization": (2, 4, 4, 3),
    "MambaLayer": (2, 5, 4),
    "MoELayer": (2, 4),
    "OutputLayer": (2, 4),
    "RBM": (2, 6),
    "RMSNorm": (2, 5),
    "RnnOutputLayer": (2, 5, 4),
    "RoutedMoELayer": (2, 4),
    "SelfAttentionLayer": (2, 5, 4),
    "SubsamplingLayer": (2, 6, 6, 2),
}


def _make(name):
    kwargs = _KWARGS[name]
    if kwargs is None:
        pytest.skip("composite covered elsewhere")
    return base._LAYER_REGISTRY[name](name=f"t_{name}", **kwargs)


def test_registry_covers_sweep():
    """The sweep tables must track the registry exactly — a new layer type
    has to add itself here (and thereby gain the invariants below)."""
    assert set(_KWARGS) == set(base._LAYER_REGISTRY), (
        set(_KWARGS) ^ set(base._LAYER_REGISTRY))


@pytest.mark.parametrize("name", sorted(_KWARGS))
def test_config_round_trips(name):
    layer = _make(name)
    d = layer.to_dict()
    assert d["type"] == name
    back = base.layer_from_dict(d)
    assert back.to_dict() == d


@pytest.mark.parametrize("name", sorted(_KWARGS))
def test_init_and_apply_smoke(name):
    layer = _make(name)
    layer.validate()
    key = jax.random.key(0)
    params = layer.init(key) if layer.has_params() else {}
    state = layer.init_state() or {}
    shape = _INPUTS[name]
    rs = np.random.RandomState(0)
    if name == "EmbeddingLayer":
        x = jnp.asarray(rs.randint(0, 7, shape).astype(np.float32))
    else:
        x = jnp.asarray(rs.rand(*shape).astype(np.float32))
    if hasattr(layer, "apply_with_carry"):
        y, _, carry = layer.apply_with_carry(params, state, x, None,
                                             train=False, rng=None)
    else:
        y, _ = layer.apply(params, state, x, train=False, rng=None)
    y = np.asarray(y)
    assert np.isfinite(y).all(), name
    assert y.shape[0] == shape[0], name


@pytest.mark.parametrize("updater", ["sgd", "nesterovs", "adagrad",
                                     "rmsprop", "adadelta", "adam"])
def test_every_updater_trains_finite(updater):
    """Updater sweep: each rule initialises state, applies one step, and
    moves params without NaN (reference UpdaterCreator zoo)."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    net = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(1)
         .updater(updater, learning_rate=0.05).list()
         .layer(DenseLayer(n_in=4, n_out=8))
         .layer(OutputLayer(n_in=8, n_out=2)).build())).init()
    rs = np.random.RandomState(0)
    x = rs.rand(8, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 8)]
    before = net.params_to_vector()
    net.fit(x, y)
    net.fit(x, y)
    after = net.params_to_vector()
    assert np.isfinite(after).all(), updater
    assert not np.allclose(before, after), updater
    assert np.isfinite(net.score_value), updater

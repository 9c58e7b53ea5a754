"""Early stopping + full-batch solver tests.

Reference: deeplearning4j-core ``earlystopping`` test suites (e.g.
TestEarlyStopping.java patterns: max-epochs termination, score improvement
patience, invalid-score guard, best-model tracking) and the solver dispatch
(``Solver.java``, ``BackTrackLineSearch.java``, ``LBFGS.java``).
"""

import math

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.earlystopping import (
    BestScoreEpochTerminationCondition,
    DataSetLossCalculator,
    EarlyStoppingConfiguration,
    EarlyStoppingTrainer,
    InMemoryModelSaver,
    InvalidScoreIterationTerminationCondition,
    LocalFileModelSaver,
    MaxEpochsTerminationCondition,
    MaxScoreIterationTerminationCondition,
    MaxTimeIterationTerminationCondition,
    ScoreImprovementEpochTerminationCondition,
    TerminationReason,
)
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize import solvers


def make_net(lr=0.5, algo="stochastic_gradient_descent", iters=1):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(7)
        .updater("sgd", learning_rate=lr)
        .optimization_algo(algo)
        .iterations(iters)
        .list()
        .layer(DenseLayer(n_in=2, n_out=8, activation="tanh", weight_init="xavier"))
        .layer(OutputLayer(n_in=8, n_out=2, loss="mcxent", activation="softmax"))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def xor_iter(batch=4):
    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
    y = np.array([[1, 0], [0, 1], [0, 1], [1, 0]], np.float32)
    return ListDataSetIterator(DataSet(x, y), batch)


def test_max_epochs_termination():
    net = make_net()
    cfg = (EarlyStoppingConfiguration.Builder()
           .epoch_termination_conditions(MaxEpochsTerminationCondition(5))
           .score_calculator(DataSetLossCalculator(xor_iter()))
           .model_saver(InMemoryModelSaver())
           .build())
    result = EarlyStoppingTrainer(cfg, net, xor_iter()).fit()
    assert result.termination_reason == TerminationReason.EPOCH_TERMINATION_CONDITION
    assert result.total_epochs == 5
    assert result.best_model is not None
    assert len(result.score_vs_epoch) == 5
    # best model score must equal the recorded minimum
    assert math.isclose(result.best_model_score,
                        min(result.score_vs_epoch.values()), rel_tol=1e-9)


def test_score_improvement_patience_stops_on_plateau():
    net = make_net(lr=0.0)  # lr 0 -> score never improves
    cfg = (EarlyStoppingConfiguration.Builder()
           .epoch_termination_conditions(
               ScoreImprovementEpochTerminationCondition(3),
               MaxEpochsTerminationCondition(50))
           .score_calculator(DataSetLossCalculator(xor_iter()))
           .build())
    result = EarlyStoppingTrainer(cfg, net, xor_iter()).fit()
    assert result.termination_reason == TerminationReason.EPOCH_TERMINATION_CONDITION
    assert result.total_epochs <= 6  # plateau detected quickly


def test_max_score_iteration_termination():
    net = make_net()
    cfg = (EarlyStoppingConfiguration.Builder()
           .iteration_termination_conditions(MaxScoreIterationTerminationCondition(1e-9))
           .epoch_termination_conditions(MaxEpochsTerminationCondition(50))
           .score_calculator(DataSetLossCalculator(xor_iter()))
           .build())
    result = EarlyStoppingTrainer(cfg, net, xor_iter()).fit()
    assert result.termination_reason == TerminationReason.ITERATION_TERMINATION_CONDITION


def test_invalid_score_guard():
    c = InvalidScoreIterationTerminationCondition()
    # the baseline of the process-wide non-finite counter: another test
    # file run earlier by the same worker may have counted skipped steps
    c.initialize()
    assert c.terminate(float("nan"))
    assert c.terminate(float("inf"))
    assert not c.terminate(1.0)


def test_max_time_condition():
    c = MaxTimeIterationTerminationCondition(0.0)
    c.initialize()
    assert c.terminate(1.0)


def test_best_score_condition_and_local_saver(tmp_path):
    net = make_net(lr=1.0)
    saver = LocalFileModelSaver(str(tmp_path), MultiLayerNetwork)
    cfg = (EarlyStoppingConfiguration.Builder()
           .epoch_termination_conditions(
               BestScoreEpochTerminationCondition(0.3),
               MaxEpochsTerminationCondition(400))
           .score_calculator(DataSetLossCalculator(xor_iter()))
           .model_saver(saver)
           .save_last_model()
           .build())
    result = EarlyStoppingTrainer(cfg, net, xor_iter()).fit()
    assert result.best_model_score < 0.31
    best = saver.get_best_model()
    latest = saver.get_latest_model()
    assert best is not None and latest is not None
    # restored best model reproduces the recorded score
    sc = DataSetLossCalculator(xor_iter()).calculate_score(best)
    assert math.isclose(sc, result.best_model_score, rel_tol=1e-5)


# ---------------------------------------------------------------- solvers

def quadratic(center):
    center = np.asarray(center, np.float64)

    def vg(x):
        d = x - center
        return float(np.dot(d, d)), 2.0 * d

    return vg


def test_lbfgs_minimizes_quadratic():
    x, fx = solvers.lbfgs(quadratic([1.0, -2.0, 3.0]), np.zeros(3), 50)
    assert fx < 1e-8
    np.testing.assert_allclose(x, [1.0, -2.0, 3.0], atol=1e-4)


def test_cg_minimizes_quadratic():
    x, fx = solvers.conjugate_gradient(quadratic([0.5, 0.5]), np.zeros(2), 50)
    assert fx < 1e-8


def test_line_gd_minimizes_quadratic():
    x, fx = solvers.line_gradient_descent(quadratic([2.0]), np.zeros(1), 100)
    assert fx < 1e-6


def test_rosenbrock_lbfgs():
    def vg(x):
        a, b = 1.0, 100.0
        f = (a - x[0]) ** 2 + b * (x[1] - x[0] ** 2) ** 2
        g = np.array([
            -2 * (a - x[0]) - 4 * b * x[0] * (x[1] - x[0] ** 2),
            2 * b * (x[1] - x[0] ** 2),
        ])
        return float(f), g

    x, fx = solvers.lbfgs(vg, np.array([-1.2, 1.0]), 200)
    assert fx < 1e-6


@pytest.mark.parametrize("algo", ["lbfgs", "conjugate_gradient", "line_gradient_descent"])
def test_network_trains_with_solver(algo):
    net = make_net(algo=algo, iters=30)
    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
    y = np.array([[1, 0], [0, 1], [0, 1], [1, 0]], np.float32)
    s0 = net.score(x, y)
    net.fit(x, y)  # one call = `iters` solver iterations on the full batch
    net.fit(x, y)
    assert net.score(x, y) < s0


def test_lbfgs_solves_xor_fully():
    net = make_net(algo="lbfgs", iters=100)
    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
    y = np.array([[1, 0], [0, 1], [0, 1], [1, 0]], np.float32)
    for _ in range(3):
        net.fit(x, y)
    preds = np.asarray(net.output(x))
    assert (preds.argmax(-1) == y.argmax(-1)).all()


def test_early_stopping_with_computation_graph():
    """The trainer is facade-generic: a ComputationGraph trains, saves, and
    restores through the same early-stopping loop (reference
    EarlyStoppingGraphTrainer)."""
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    b = (NeuralNetConfiguration.builder().seed(3)
         .updater("adam", learning_rate=0.1).graph()
         .add_inputs("in")
         .add_layer("h", DenseLayer(n_in=2, n_out=8, activation="tanh"), "in")
         .add_layer("out", OutputLayer(n_in=8, n_out=2), "h")
         .set_outputs("out"))
    net = ComputationGraph(b.build()).init()
    cfg = (EarlyStoppingConfiguration.Builder()
           .epoch_termination_conditions(MaxEpochsTerminationCondition(8))
           .score_calculator(DataSetLossCalculator(xor_iter()))
           .model_saver(InMemoryModelSaver())
           .build())
    result = EarlyStoppingTrainer(cfg, net, xor_iter()).fit()
    assert result.total_epochs == 8
    assert result.best_model is not None
    assert np.isfinite(result.best_model_score)
    scores = list(result.score_vs_epoch.values())
    assert scores[-1] < scores[0]  # xor is learnable by epoch 8


def test_computation_graph_trains_with_lbfgs():
    """CG's solver path (Solver.java dispatch on a DAG facade)."""
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    b = (NeuralNetConfiguration.builder().seed(5)
         .optimization_algo("lbfgs").iterations(50).graph()
         .add_inputs("in")
         .add_layer("h", DenseLayer(n_in=2, n_out=8, activation="tanh"), "in")
         .add_layer("out", OutputLayer(n_in=8, n_out=2), "h")
         .set_outputs("out"))
    net = ComputationGraph(b.build()).init()
    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
    y = np.array([[1, 0], [0, 1], [0, 1], [1, 0]], np.float32)
    s0 = net.score(x, y)
    net.fit(x, y)
    net.fit(x, y)
    assert net.score(x, y) < s0

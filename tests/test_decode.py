"""On-device generation (models.decode): parity with the host-side sampling
loop and cache-mode coverage (GQA, rolling window).

The contract: ``generate`` is ``utils.sampling.sample_sequence`` compiled
into one XLA program — greedy decoding must produce IDENTICAL token ids
through both paths (same forward math through the same KV caches / LSTM
carries), for both input encodings (embedding-ids transformers, one-hot
LSTMs).
"""

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.decode import generate
from deeplearning4j_tpu.utils.sampling import sample_sequence


def _greedy_both(net, prompt, steps, **kw):
    ref = sample_sequence(net, prompt, steps, temperature=0.0, **kw)
    got = generate(net, prompt, steps, temperature=0.0, **kw)
    return ref, got


def test_transformer_greedy_matches_host_loop():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=17, d_model=16, n_heads=2, layers=2,
                              max_cache=64)
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 17, (3, 5))
    ref, got = _greedy_both(net, prompt, 12)
    np.testing.assert_array_equal(got, ref)


def test_transformer_gqa_rolling_greedy_matches_host_loop():
    """The decode-bandwidth features (GQA cache, rolling window cache) run
    through the same scanned program and still match the host loop."""
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=13, d_model=16, n_heads=4, layers=2,
                              n_kv_heads=2, window=8)
    rs = np.random.RandomState(1)
    prompt = rs.randint(0, 13, (2, 6))
    # decode well past the window: the rolling cache wraps several times
    ref, got = _greedy_both(net, prompt, 20)
    np.testing.assert_array_equal(got, ref)


def test_lstm_one_hot_greedy_matches_host_loop():
    from deeplearning4j_tpu.models.zoo import graves_lstm_char_lm

    net = graves_lstm_char_lm(vocab_size=11, hidden=12, tbptt=8)
    rs = np.random.RandomState(2)
    prompt = rs.randint(0, 11, (2, 4))
    ref, got = _greedy_both(net, prompt, 10)
    np.testing.assert_array_equal(got, ref)


def test_sampled_decode_shape_determinism_and_filtering():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=17, d_model=16, n_heads=2, layers=1,
                              max_cache=64)
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, 17, (4, 3))
    key = jax.random.PRNGKey(7)
    a = generate(net, prompt, 9, temperature=0.8, top_k=5, rng=key)
    b = generate(net, prompt, 9, temperature=0.8, top_k=5, rng=key)
    assert a.shape == (4, 9)
    np.testing.assert_array_equal(a, b)      # same key -> same draw
    c = generate(net, prompt, 9, temperature=0.8, top_k=5,
                 rng=jax.random.PRNGKey(8))
    assert not np.array_equal(a, c)          # different key -> different draw


def test_generate_overflow_checked_upfront():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=8, d_model=8, n_heads=2, layers=1,
                              max_cache=6)
    prompt = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="max_cache"):
        generate(net, prompt, 5)             # 4 + 5 > 6
    assert generate(net, prompt, 2).shape == (1, 2)


def _cg_lstm_char_lm(vocab=11, hidden=12):
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer

    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater("sgd", learning_rate=0.1).graph()
            .add_inputs("in")
            .add_layer("lstm", GravesLSTM(n_in=vocab, n_out=hidden), "in")
            .add_layer("out", RnnOutputLayer(n_in=hidden, n_out=vocab,
                                             loss="mcxent",
                                             activation="softmax"), "lstm")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _cg_attention_char_lm(vocab=13, d=16, heads=2, cache=64):
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, LayerNorm, RnnOutputLayer, SelfAttentionLayer,
    )

    conf = (NeuralNetConfiguration.builder().seed(6)
            .updater("sgd", learning_rate=0.1).graph()
            .add_inputs("ids")
            .add_layer("emb", EmbeddingLayer(n_in=vocab, n_out=d,
                                             collapse_column=False), "ids")
            .add_layer("attn", SelfAttentionLayer(n_in=d, n_out=d,
                                                  n_heads=heads, causal=True,
                                                  max_cache=cache), "emb")
            .add_layer("ln", LayerNorm(n_in=d), "attn")
            .add_layer("out", RnnOutputLayer(n_in=d, n_out=vocab,
                                             loss="mcxent",
                                             activation="softmax"), "ln")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def test_cg_lstm_greedy_matches_host_loop():
    """The compiled decode scan covers
    ComputationGraph (reference ComputationGraph.rnnTimeStep:1674)."""
    net = _cg_lstm_char_lm()
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, 11, (2, 4))
    ref = sample_sequence(net, prompt, 10, temperature=0.0, one_hot=True,
                          vocab_size=11)
    net.rnn_clear_previous_state()
    got = generate(net, prompt, 10, temperature=0.0)  # encoding auto-detected
    np.testing.assert_array_equal(got, ref)


def test_cg_attention_greedy_matches_host_loop():
    net = _cg_attention_char_lm()
    rs = np.random.RandomState(4)
    prompt = rs.randint(0, 13, (3, 5))
    ref = sample_sequence(net, prompt, 12, temperature=0.0, one_hot=False)
    net.rnn_clear_previous_state()
    got = generate(net, prompt, 12, temperature=0.0)
    np.testing.assert_array_equal(got, ref)


def test_cg_multi_input_graph_rejected_with_guidance():
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.models.vertices import MergeVertex

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater("sgd", learning_rate=0.1).graph()
            .add_inputs("a", "b")
            .add_layer("da", DenseLayer(n_in=4, n_out=4), "a")
            .add_layer("db", DenseLayer(n_in=4, n_out=4), "b")
            .add_vertex("m", MergeVertex(), "da", "db")
            .add_layer("out", OutputLayer(n_in=8, n_out=2, loss="mcxent",
                                          activation="softmax"), "m")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    with pytest.raises(ValueError, match="single-input"):
        generate(net, np.zeros((1, 3), np.int64), 2)


def test_cg_collapse_column_embedding_greedy_matches_host_loop():
    """Regression: a default (collapse_column=True) EmbeddingLayer feeds
    per-token [B,1] ids that would collapse away the time axis; decode
    must expand them like rnn_time_step does."""
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GravesLSTM, RnnOutputLayer,
    )

    conf = (NeuralNetConfiguration.builder().seed(8)
            .updater("sgd", learning_rate=0.1).graph()
            .add_inputs("ids")
            .add_layer("emb", EmbeddingLayer(n_in=11, n_out=8), "ids")
            .add_layer("lstm", GravesLSTM(n_in=8, n_out=10), "emb")
            .add_layer("out", RnnOutputLayer(n_in=10, n_out=11,
                                             loss="mcxent",
                                             activation="softmax"), "lstm")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    rs = np.random.RandomState(9)
    prompt = rs.randint(0, 11, (2, 4))
    ref = sample_sequence(net, prompt, 6, temperature=0.0)
    net.rnn_clear_previous_state()
    got = generate(net, prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(got, ref)


def test_cg_one_hot_vocab_inferred_from_input_consumer():
    """Asymmetric vocab: one-hot width must come from the INPUT consumer's
    n_in (30), not the output head's n_out (11)."""
    from deeplearning4j_tpu.models.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer

    conf = (NeuralNetConfiguration.builder().seed(10)
            .updater("sgd", learning_rate=0.1).graph()
            .add_inputs("in")
            .add_layer("lstm", GravesLSTM(n_in=30, n_out=10), "in")
            .add_layer("out", RnnOutputLayer(n_in=10, n_out=11,
                                             loss="mcxent",
                                             activation="softmax"), "lstm")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    rs = np.random.RandomState(10)
    prompt = rs.randint(0, 30, (2, 3))
    out = generate(net, prompt, 4, temperature=0.0)  # would crash at 11
    assert out.shape == (2, 4) and out.max() < 11


def test_mln_one_hot_vocab_inferred_from_first_layer():
    """Asymmetric vocab, sequential net: one-hot width = first layer's
    n_in (30), not the head's n_out (11) — same input-side rule as CG."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer

    b = (NeuralNetConfiguration.builder().seed(12)
         .updater("sgd", learning_rate=0.1).list()
         .layer(GravesLSTM(n_in=30, n_out=10))
         .layer(RnnOutputLayer(n_in=10, n_out=11, loss="mcxent",
                               activation="softmax")))
    net = MultiLayerNetwork(b.build()).init()
    rs = np.random.RandomState(12)
    prompt = rs.randint(0, 30, (2, 3))
    out = generate(net, prompt, 4, temperature=0.0)
    assert out.shape == (2, 4) and out.max() < 11


def test_generate_identical_after_zip_round_trip(tmp_path):
    """Serialization composes with the compiled decode: save -> load ->
    generate must reproduce the original tokens exactly (config carries
    GQA/window/max_cache; params + updater state ride the zip)."""
    from deeplearning4j_tpu.models.serialization import load_model
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=19, d_model=16, n_heads=4,
                              layers=2, n_kv_heads=2, window=16,
                              max_cache=32)
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, 19, (2, 4))
    before = generate(net, prompt, 10, temperature=0.0)
    path = tmp_path / "lm.zip"
    net.save(str(path))
    loaded = load_model(str(path))
    after = generate(loaded, prompt, 10, temperature=0.0)
    np.testing.assert_array_equal(before, after)

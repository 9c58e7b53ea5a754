"""State slots, the generation engine's third kind of pool: one row of
recurrent state a slot, beside the pages.  A slot's state begins anew at
admission, a bucket's padding does not move it, an idle decode lane moves
none, the page transport skips it, prefix sharing is refused under it, and
the contract is not Mamba's alone: ``GravesLSTM`` is served through it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_jamba as ref
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.generation.paged_cache import PagedKVCache
from deeplearning4j_tpu.generation.programs import (
    GenerationPrograms, has_state_pools, map_pools, seed_paged_pools,
)
from deeplearning4j_tpu.helpers.paged_attention import pool_kind
from deeplearning4j_tpu.models.decode import generate
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    GravesLSTM, ResidualBlock, RMSNorm, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from tests.test_jamba import SEED, toy_net


def programs(net, slots=4):
    return GenerationPrograms(net, slots=slots, pages_per_slot=6, page_size=8,
                              num_pages=slots * 6 + 1,
                              prefill_buckets=(16, 32))


def prefill(progs, pools, ids, slot, pages, prompt):
    """One prompt through ``prefill_16`` into ``slot``."""
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :len(prompt)] = prompt
    block = np.zeros((1, 6), np.int32)
    block[0, :len(pages)] = pages
    return progs.prefill(
        16, progs.serving_params(), {}, pools, block, np.zeros(1, np.int32),
        np.int32(len(prompt) - 1), tokens, np.zeros((1, 2), np.uint32),
        np.zeros(1, np.int32), np.zeros(1, np.float32), np.zeros(1, np.int32),
        np.ones(1, np.float32), ids, np.int32(slot))


# ----------------------------------------------------------- (a) the pools
def test_the_three_kinds_of_pool_and_what_walks_them():
    net, _ = toy_net()
    assert has_state_pools(net)
    progs = programs(net)
    pools = progs.fresh_pools()
    kinds = {}
    map_pools(lambda c: kinds.setdefault(pool_kind(c), sorted(c)), pools)
    assert kinds == {"state": ["sc", "sh"], "global": ["pk", "pv"]}
    assert pools["layer_1"]["sub1"]["sh"].shape == (5, 8, 128)
    assert pools["layer_3"]["sub1"]["pk"].shape == (25, 1, 8, 16)
    # the page transport moves pages: the state kind is skipped by reading,
    # kept as it is by writing, and costs the host tier nothing
    payload = progs.read_page(pools, 3)
    assert sorted(payload) == ["layer_3", "layer_7"]
    state = np.asarray(pools["layer_1"]["sub1"]["sh"]) + 1.0
    pools["layer_1"]["sub1"]["sh"] = jnp.asarray(state)
    back = progs.write_page(pools, 5, payload)
    assert np.array_equal(np.asarray(back["layer_1"]["sub1"]["sh"]), state)
    assert progs.page_nbytes(back) == 2 * 2 * 8 * 16 * 4


def test_a_carry_without_a_pool_is_refused_by_the_method_it_lacks():
    class Carries(Layer):
        def apply_with_carry(self, *a, **k):
            raise AssertionError

    for block in (False, True):
        layer = Carries(name="c")
        if block:
            layer = ResidualBlock(name="b", layers=(RMSNorm(n_in=4), layer))
        net = MultiLayerNetwork.__new__(MultiLayerNetwork)
        net.layers = (layer,)
        with pytest.raises(ValueError, match="init_paged_cache") as e:
            seed_paged_pools(net, 4, 4, None, window_pages=2, state_slots=2)
        assert "transformer" not in str(e.value)


# ------------------------------------- (b) reset, padding, idle lanes
def test_padding_leaves_the_state_at_the_last_real_token():
    """An 11-token prompt in a bucket of 16: the slot's row holds the
    reference's state after token 11, and the tail its last three inputs."""
    net, cfg = toy_net()
    progs = programs(net)
    prompt = np.random.default_rng(3).integers(0, 97, 11)
    pools, _, _ = prefill(progs, progs.fresh_pools(), progs.fresh_ids(), 2,
                          [4, 9], prompt)
    w = ref.make_leaves(cfg, SEED, "L0.", ref.layer_shapes(cfg, 0))
    emb = ref.make_leaf(cfg, SEED, "emb.W", (97, 64))
    u = ref.rms_norm(emb[prompt], w["in_norm.g"], cfg["rms_norm_eps"])
    _, h = ref.mamba(u, w, cfg, "f32")
    row = pools["layer_1"]["sub1"]
    assert float(jnp.max(jnp.abs(row["sh"][3] - h.T))) < 1e-5
    x_in = ref.linear(u, w["in_proj"])[:, :128]
    assert float(jnp.max(jnp.abs(row["sc"][3] - x_in[-3:]))) < 1e-5
    # the other rows, the trash row among them, were not touched
    assert float(jnp.max(jnp.abs(row["sh"][jnp.array([0, 1, 2, 4])]))) == 0.0


def test_a_slot_begins_anew_whatever_its_row_held():
    net, _ = toy_net()
    progs = programs(net)
    prompt = np.random.default_rng(4).integers(0, 97, 9)
    clean, tok, _ = prefill(progs, progs.fresh_pools(), progs.fresh_ids(), 1,
                            [7, 8], prompt)
    dirty = map_pools(
        lambda c: {k: a + 3.0 if pool_kind(c) == "state" else a
                   for k, a in c.items()}, progs.fresh_pools())
    again, tok2, _ = prefill(progs, dirty, progs.fresh_ids(), 1, [7, 8],
                             prompt)
    for name in ("layer_1", "layer_5"):
        for leaf in ("sh", "sc"):
            assert np.array_equal(np.asarray(again[name]["sub1"][leaf][2]),
                                  np.asarray(clean[name]["sub1"][leaf][2]))
    assert int(tok[0]) == int(tok2[0])


def test_a_reused_slot_serves_what_a_fresh_engine_serves():
    net, _ = toy_net()
    rng = np.random.default_rng(5)
    first = (rng.integers(0, 97, 23).tolist(), 8)
    second = (rng.integers(0, 97, 6).tolist(), 9)

    def serve(requests):
        eng = GenerationEngine(net, slots=1, page_size=4, max_context=48,
                               prefill_buckets=(8, 32),
                               registry=MetricsRegistry()).start()
        try:
            return [eng.submit(p, max_new_tokens=n).result(timeout=120)
                    for p, n in requests]
        finally:
            eng.stop()

    assert serve([first, second])[1] == serve([second])[0]


def test_an_idle_lane_moves_no_state():
    net, _ = toy_net()
    progs = programs(net)
    pools = map_pools(
        lambda c: {k: a + 0.5 if pool_kind(c) == "state" else a
                   for k, a in c.items()}, progs.fresh_pools())
    before = jax.tree_util.tree_map(np.asarray, pools)
    block = np.zeros((4, 6), np.int32)
    block[1, :2] = [3, 4]                    # lane 1 runs a request
    pos = np.array([0, 5, 0, 0], np.int32)
    z = np.zeros
    pools, _ = progs.decode(
        progs.serving_params(), {}, pools, block, pos, progs.fresh_ids(),
        z((4, 2), np.uint32), z(4, np.int32), z(4, np.float32),
        z(4, np.int32), np.ones(4, np.float32))
    for name in ("layer_1", "layer_5"):
        for leaf in ("sh", "sc"):
            now = np.asarray(pools[name]["sub1"][leaf])
            was = before[name]["sub1"][leaf]
            assert np.array_equal(now[[0, 1, 3, 4]], was[[0, 1, 3, 4]])
            assert not np.array_equal(now[2], was[2])


# ----------------------------------------------------- (c) what is refused
def test_prefix_sharing_is_refused_under_state_layers():
    net, _ = toy_net()
    with pytest.raises(ValueError, match="recurrent state"):
        GenerationEngine(net, slots=2, page_size=4, max_context=32,
                         prefix_cache=True)
    eng = GenerationEngine(net, slots=2, page_size=4, max_context=32,
                           prefill_buckets=(16,), registry=MetricsRegistry())
    assert eng.cache.state_slots
    cache = PagedKVCache(9, 4, 4, state_slots=True)
    same = list(range(12))
    _, shared_first = cache.admit(same, 2)
    _, shared_second = cache.admit(same, 2)
    assert (shared_first, shared_second) == (0, 0) and cache.shared_pages == 0
    # a net without state layers cannot be deployed into this engine
    from tests.test_laguna import _accepted_toy_net

    eng.start()
    try:
        with pytest.raises(ValueError, match="state slots"):
            eng.deploy("default", _accepted_toy_net("starcoder2"))
    finally:
        eng.stop()


# --------------------------------------------- (d) not Mamba's alone: LSTM
def char_lstm(vocab=23, hidden=16):
    b = NeuralNetConfiguration.builder().seed(3).updater("sgd").list()
    b.layer(GravesLSTM(n_in=vocab, n_out=hidden))
    b.layer(GravesLSTM(n_in=hidden, n_out=hidden))
    b.layer(RnnOutputLayer(n_in=hidden, n_out=vocab, loss="mcxent",
                           activation="softmax"))
    net = MultiLayerNetwork(b.build())
    net.init()
    # sharper than a fresh init, so that the greedy tokens vary
    net.params = jax.tree_util.tree_map(lambda a: a * 4.0, net.params)
    return net


def test_a_character_lstm_is_served_as_generate_samples_it():
    """The source library's ``rnnTimeStep``, served: two LSTM layers through
    state slots emit ``models.decode.generate``'s tokens, five requests
    through two slots."""
    net = char_lstm()
    assert has_state_pools(net)
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, 23, n).tolist(), m)
                for n, m in ((5, 7), (11, 4), (3, 9), (16, 6), (8, 12))]
    eng = GenerationEngine(net, slots=2, page_size=4, max_context=32,
                           prefill_buckets=(8, 16),
                           registry=MetricsRegistry()).start()
    try:
        served = [h.result(timeout=120) for h in
                  [eng.submit(p, max_new_tokens=m) for p, m in requests]]
    finally:
        eng.stop()
    seen = set()
    for (prompt, m), toks in zip(requests, served):
        want = generate(net, np.asarray([prompt]), m, temperature=0.0)[0]
        assert list(toks) == want.tolist()
        seen.update(toks)
    assert len(seen) > 3
    pools = jax.eval_shape(programs(net).fresh_pools)
    assert pools["layer_0"]["sh"].shape == (5, 16)
    assert eng.metrics.registry.get_value(
        "dl4j_layer_path_steps_total", stage="decode", kind="recurrent",
        path="step") is None

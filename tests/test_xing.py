"""Manifold-constrained hyper-connections (``HyperConnectionBlock`` and the
two ends of its streams) around latent attention and an expert layer that
holds every expert, against the plain reference
``benchmark/reference_xing.py`` at a toy size on seeded random weights: the
coefficients alone, the whole model through ``net.output`` and through
``GenerationEngine`` (bucketed prefill, paged decode, the gauge), two faults
that must fail the same comparison, and the accepted configurations'
programs, unchanged."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model_xing, reference_xing as ref
from deeplearning4j_tpu.generation.programs import GenerationPrograms, map_pools
from deeplearning4j_tpu.nn.layers import (
    GatedMLP, HyperConnectionBlock, HyperStreamExpand, HyperStreamReduce,
    ResidualBlock, RMSNorm,
)
from deeplearning4j_tpu.nn.layers import composite
from tests.test_latent_moe import run_engine

# two leading dense layers and two expert layers that hold all 8 experts,
# as the configuration does; original_max_position_embeddings 16, so YaRN's
# blend is in every test
TOY = dict(
    model_type="xing4_0", hidden_size=64, intermediate_size=160,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    first_k_dense_replace=2, num_hidden_layers=4, n_routed_experts=8,
    first_expert_held=0, num_experts_per_tok=3, n_shared_experts=1,
    moe_intermediate_size=24, norm_topk_prob=True, routed_scaling_factor=2,
    scoring_func="sigmoid", n_group=1, topk_group=1, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, vocab_size=97, torch_dtype="float32",
    initializer_range=0.2)
SEED = 2**31 + 11
# float32 on both sides, the same mathematics in another order
TOL = 2e-4


def toy_net(**over):
    cfg = {**TOY, **over}
    net = model_xing.build_network(cfg)
    return model_xing.install_weights(net, cfg, SEED), cfg


def logit_gap(net, cfg=TOY, length=29):
    """Widest distance between the net's log-probabilities and the
    reference's over two sequences."""
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, length))
    got = np.log(np.asarray(net.output(ids), np.float64))
    w = ref.make_weights(TOY, SEED)
    want = np.stack([np.asarray(jax.nn.log_softmax(ref.forward(w, row, TOY)))
                     for row in ids])
    return np.abs(got - want).max()


# ------------------------------------------------- (a) the whole forward
def test_output_equals_the_reference_on_logits():
    net, cfg = toy_net()
    kinds = [type(l).__name__ for l in net.layers]
    assert kinds == (["EmbeddingLayer", "HyperStreamExpand"]
                     + ["HyperConnectionBlock"] * 8
                     + ["HyperStreamReduce", "RMSNorm", "RnnOutputLayer"])
    moe = net.layers[7].layers[1]
    assert moe.experts_held == (0, 8) and moe.n_experts == 8
    assert logit_gap(net) < TOL


@pytest.mark.parametrize("fault", ["one_sinkhorn_iteration",
                                   "h_post_unscaled"])
def test_a_faulty_program_fails_the_same_comparison(fault, monkeypatch):
    if fault == "one_sinkhorn_iteration":
        net, _ = toy_net(hc_sinkhorn_iters=1)
        assert net.layers[2].sinkhorn_iters == 1
    else:
        monkeypatch.setattr(composite, "POST_SCALE", 1.0)
        net, _ = toy_net()
    assert logit_gap(net) > 50 * TOL


# ------------------------------------------------- (b) the coefficients
def block_and_leaves(i=2, sub="attn_hc."):
    net, cfg = toy_net()
    blk = net.layers[i]
    w = ref.make_leaves(cfg, SEED, "L0." + sub, ref.hc_shapes(cfg))
    return blk, w, cfg


def test_coefficients_equal_the_reference_and_h_res_is_doubly_stochastic():
    blk, w, cfg = block_and_leaves()
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 4, 64))
    h_pre, h_post, h_res = blk.coefficients(w, x.reshape(37, -1))
    want_pre, want_post, want_res = ref.mhc_coefficients(x, w, cfg)
    assert np.abs(np.asarray(h_pre).T - np.asarray(want_pre)).max() < 1e-5
    assert np.abs(np.asarray(h_post).T - np.asarray(want_post)).max() < 1e-5
    got = np.moveaxis(np.asarray(h_res), 2, 0)                  # [T, n, n]
    assert np.abs(got - np.asarray(want_res)).max() < 1e-5
    assert np.abs(got.sum(axis=2) - 1).max() < 1e-3      # rows
    assert np.abs(got.sum(axis=1) - 1).max() < 1e-3      # columns
    assert got.std(axis=0).min() > 1e-3                  # H varies by token
    assert 0 < np.asarray(h_post).min() and np.asarray(h_post).max() < 2
    err = np.asarray(composite.doubly_stochastic_error(h_res))
    assert err.shape == (37,) and err.max() < 1e-3
    short = dataclasses.replace(blk, sinkhorn_iters=1)
    _, _, rough = short.coefficients(w, x.reshape(37, -1))
    assert np.asarray(composite.doubly_stochastic_error(rough)).max() > 0.05


def test_the_clamp_binds_before_the_exponential():
    blk, w, cfg = block_and_leaves()
    w = {**w, "beta": w["beta"].at[8].set(500.0)}        # B_res[0, 0]
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 4, 64))
    _, _, h_res = blk.coefficients(w, x.reshape(5, -1))
    got = np.moveaxis(np.asarray(h_res), 2, 0)
    assert np.isfinite(got).all()
    assert np.abs(got - np.asarray(ref.mhc_coefficients(x, w, cfg)[2])
                  ).max() < 1e-5


def test_the_gauge_leaves_out_padding_and_idle_rows():
    blk, w, _ = block_and_leaves()
    short = dataclasses.replace(blk, sinkhorn_iters=1)
    params = {**model_params(short), **w}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, 256))
    err = np.asarray(composite.doubly_stochastic_error(
        short.coefficients(w, x.reshape(12, -1))[2])).reshape(2, 6)
    valid = jnp.arange(6)[None] < jnp.asarray([[4], [0]])
    with composite.gauging(lambda: valid) as sink:
        short.apply(params, {}, x)
    assert len(sink) == 1
    assert float(sink[0]) == pytest.approx(err[0, :4].max(), rel=1e-5)
    with composite.gauging(lambda: valid) as sink:     # no such block: nothing
        RMSNorm(n_in=256).apply({"gamma": jnp.ones(256)}, {}, x)
    assert sink == []


def model_params(blk):
    return blk.init(jax.random.PRNGKey(0))


def test_a_fresh_block_is_close_to_a_plain_residual_connection():
    blk = HyperConnectionBlock(n_in=4 * 16, layers=(
        RMSNorm(n_in=16), GatedMLP(n_in=16, n_out=16, hidden=24)))
    plain = ResidualBlock(layers=blk.layers)
    p = blk.init(jax.random.PRNGKey(1))
    assert set(p) == {"sub0", "sub1", "phi", "alpha", "beta"}
    assert p["phi"].shape == (64, 24) and p["beta"].shape == (24,)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
    streams, _ = HyperStreamExpand(n_in=16).apply({}, {}, x)
    assert streams.shape == (2, 5, 64)
    y, _ = blk.apply(p, {}, streams)
    want, _ = plain.apply({k: p[k] for k in ("sub0", "sub1")}, {}, x)
    # H_pre = 1/n of n equal streams, H_post = 1, H_res near the identity
    for i in range(4):
        assert np.abs(np.asarray(y[..., 16 * i:16 * (i + 1)] - want)
                      ).max() < 0.02
    total, _ = HyperStreamReduce(n_in=64).apply({}, {}, y)
    assert np.abs(np.asarray(total - 4 * want)).max() < 0.08


# ------------------------------------------------------ (c) the DSL side
def test_composites_round_trip_through_the_config_json():
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.layers.base import layer_from_dict

    net, _ = toy_net()
    blk = net.layers[2]
    d = blk.to_dict()
    assert d["type"] == "HyperConnectionBlock" and d["streams"] == 4
    assert d["sinkhorn_iters"] == 20 and d["res_clamp"] == [-30.0, 30.0]
    assert layer_from_dict(d) == blk
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.layers == net.conf.layers
    assert again.layers[7].layers[1].experts_held == (0, 8)


def test_setup_infers_the_streams_width_from_the_input_type():
    from deeplearning4j_tpu.nn.inputs import InputType

    blk = HyperConnectionBlock(layers=(RMSNorm(), GatedMLP(hidden=24)))
    done = blk.setup(InputType.recurrent(64, 9))
    assert done.n_in == 64 and done.layers[0].n_in == 16
    assert done.layers[1].n_in == done.layers[1].n_out == 16
    assert done.output_type(InputType.recurrent(64, 9)).size == 64
    with pytest.raises(ValueError, match="streams"):
        dataclasses.replace(done, n_in=66).validate()
    assert HyperStreamExpand().setup(InputType.recurrent(16)).output_type(
        InputType.recurrent(16)).size == 64
    assert HyperStreamReduce().setup(InputType.recurrent(64)).output_type(
        InputType.recurrent(64)).size == 16


@pytest.mark.parametrize("kind", [ResidualBlock, HyperConnectionBlock])
def test_sublayer_calls_are_decided_when_the_composite_is_made(kind,
                                                               monkeypatch):
    """Which sublayer takes a carry or a mask is read off the classes once,
    not probed with ``inspect`` at every trace."""
    from deeplearning4j_tpu.nn.layers import SelfAttentionLayer

    extra = {"n_in": 4 * 16} if kind is HyperConnectionBlock else {}
    blk = kind(layers=(RMSNorm(n_in=16),
                       SelfAttentionLayer(n_in=16, n_out=16, n_heads=2,
                                          causal=True)),
               **extra)
    assert blk._forms == ((False, False), (True, True))
    assert dataclasses.replace(blk, name="again")._forms == blk._forms
    p = blk.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, 5, 64 if extra else 16))

    def no_probe(*a, **k):
        raise AssertionError("sublayers probed inside a trace")

    monkeypatch.setattr(composite, "_call_forms", no_probe)
    mask = jnp.ones((2, 5))
    y, _ = blk.apply(p, {}, x, mask=mask)
    carry = blk.init_cache(2)
    y2, _, new = blk.apply_with_carry(p, {}, x, carry, mask=None)
    assert y.shape == y2.shape == x.shape and set(new) == {"sub1"}


def test_pools_are_reached_through_the_new_composite():
    net, _ = toy_net()
    progs = GenerationPrograms(net, slots=2, pages_per_slot=6, page_size=8,
                               num_pages=13, prefill_buckets=(16,))
    pools = jax.eval_shape(progs.fresh_pools)
    seen = []
    map_pools(lambda c: seen.append(sorted(c)) or c, pools)
    assert seen == [["pc"]] * 4                       # one a decoder layer
    assert sorted(pools) == ["layer_2", "layer_4", "layer_6", "layer_8"]
    assert pools["layer_2"]["sub1"]["pc"].shape == (13, 8, 128)


# ---------------------------------------- (d) through the engine, paged
def test_engine_serves_the_toy_model_as_the_reference_and_gauges_it():
    net, cfg = toy_net()
    rng = np.random.default_rng(1)
    requests = [(rng.integers(0, 97, 21).tolist(), 9),
                (rng.integers(0, 97, 7).tolist(), 14)]
    eng, served = run_engine(net, requests)
    w = ref.make_weights(cfg, SEED)
    tokens = 0
    for (prompt, n), toks in zip(requests, served):
        assert len(toks) == n
        seq = np.asarray(prompt + toks.tolist())
        logits = np.asarray(ref.forward(w, seq, cfg))
        rows = logits[len(prompt) - 1:len(seq) - 1]
        gap = rows.max(axis=1) - rows[np.arange(n), toks]
        assert gap.max() < TOL, gap
        tokens += len(seq) - 1                      # the last is never fed
    reg = eng.metrics.registry
    err = reg.get_value("dl4j_mhc_row_sum_error",
                        engine=eng.metrics.engine_id)
    assert 0 < err < 1e-3
    # every expert held: each token routed lands top_k times, in both
    # expert layers
    assert reg.get_value("dl4j_moe_tokens_total") == 2 * tokens
    held = sum(reg.get_value("dl4j_moe_held_assignments_total",
                             expert=str(e)) or 0 for e in range(8))
    assert held == 3 * 2 * tokens


def test_the_engine_gauges_a_loop_cut_short():
    net, _ = toy_net(hc_sinkhorn_iters=1)
    eng, served = run_engine(net, [([5, 6, 7, 8, 9], 4)])
    assert len(served[0]) == 4
    assert eng.metrics.registry.get_value(
        "dl4j_mhc_row_sum_error", engine=eng.metrics.engine_id) > 0.05


def test_a_decoded_token_takes_the_blocks_whole_path():
    """Nothing of the hyper-connections is cached: the decode program holds
    the coefficients' product and the Sinkhorn loop of every block."""
    net, _ = toy_net()
    progs = GenerationPrograms(net, slots=4, pages_per_slot=6, page_size=8,
                               num_pages=25, prefill_buckets=(16,))
    text = progs.lowered()["decode"].as_text()
    assert text.count("stablehlo.while") >= 8         # Sinkhorn, 8 blocks
    assert text.count("precision = [HIGHEST") >= 8      # m = (vec(X) r) phi


# -------------------- (e) the accepted configurations' programs, unchanged
# Laguna's toy programs as the parent commit fe6a1f2 lowers them (tests/
# test_laguna.py's pattern; its own PARENT_PROGRAMS pin StarCoder2's and
# Kimi's and are checked again here, so the three older nets stand side by
# side in the file that adds the fourth block).  Since PR 36 with the
# streamed experts' kernel withheld from the seam, as there: the ``ragged``
# path is the parent's text.  Since PR 37 with the parent's module name put
# back on a ``prefill_<bucket>``, as there
LAGUNA_PARENT = {"prefill_16": "e13b53b397c337f7",
                 "prefill_32": "a1a2c4405c090765",
                 "decode": "a10054bc2e4fbff9"}


@pytest.mark.parametrize("family", ["starcoder2", "kimi", "laguna"])
def test_older_nets_lower_to_the_programs_of_the_parent(family, monkeypatch):
    from tests import test_laguna

    if family != "laguna":
        test_laguna.test_accepted_nets_lower_to_the_programs_of_the_parent(
            family, monkeypatch)
        return
    monkeypatch.setattr(test_laguna.GroupedExpertsHelper, "supports",
                        lambda self, *widths: False)
    net, _ = test_laguna.toy_net()
    progs = GenerationPrograms(net, slots=4, pages_per_slot=6, page_size=8,
                               num_pages=25, prefill_buckets=(16, 32))
    got = {name: hashlib.sha256(low.as_text().replace(
        f"@jit_{name} ", "@jit_prefill ").encode()).hexdigest()[:16]
           for name, low in progs.lowered().items()}
    assert got == LAGUNA_PARENT

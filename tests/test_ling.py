"""Kimi Delta Attention (``KimiDeltaAttentionLayer``), group-limited routing,
latent attention with no query latent and a head-wise gate, and
Ling-3.0-flash's net — KDA layers on state slots beside a latent attention
layer on pages, group-limited experts as one chip's share — against the
plain reference ``benchmark/reference_ling.py`` at a toy size on seeded
random weights: the per-channel forms of the delta rule against each other
(the decode step's Pallas kernel interpreted), the router against a loop
over tokens, the eight groups' shares against the uncut layer, each mixer
alone, the whole model through ``net.output`` and through
``GenerationEngine`` (bucketed prefill, then decode through slots and
latent pages; with and without the kernel), the faults that must fail the
same comparison, and the published parameter count."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_ling, model_ling
from benchmark import reference_ling as ref
from benchmark.jobs import serve_kda_moe, serve_latent_moe, serve_linear_attention
from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.helpers import delta_rule as dr
from deeplearning4j_tpu.nn.layers import (
    GatedDeltaNetLayer, KimiDeltaAttentionLayer, LatentAttentionLayer,
    RoutedMoELayer,
)
from deeplearning4j_tpu.nn.layers.base import layer_from_dict
from deeplearning4j_tpu.nn.layers.delta_net import (
    DELTA_RULE_PATHS, KDA_PATHS, delta_rule_path,
)
from deeplearning4j_tpu.nn.layers.moe import limit_to_groups
from deeplearning4j_tpu.observability.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                       "ling-3.0-flash-ep8.json")) as f:
    PUBLISHED = json.load(f)
# KDA (dense), KDA, MLA, KDA (experts): 16 experts in 4 groups of 4, the
# chip holding group 0, the top 4 inside the best 2 groups
TOY = {**PUBLISHED, **dict(
    hidden_size=64, intermediate_size=160, num_attention_heads=4,
    num_key_value_heads=4, head_dim=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=24, num_experts=4,
    num_experts_per_tok=4, n_group=4, topk_group=2, layer_group_size=3,
    first_k_dense_replace=1, num_hidden_layers=4, vocab_size=97,
    published={"num_hidden_layers": 42, "num_experts": 16,
               "vocab_size": 157184},
    torch_dtype="float32", initializer_range=0.2)}
SEED = 2**31 + 29
# float32 on both sides, the same mathematics in another order (the chunked
# form and the absorbed latent path against the step recurrence and the
# expanded form)
TOL = 2e-4


def toy_net(**over):
    cfg = {**TOY, **over}
    net = model_ling.build_network(cfg)
    return model_ling.install_weights(net, cfg, SEED), cfg


def mixer_and_leaves(i, **over):
    net, cfg = toy_net(**over)
    return (net.layers[1 + 2 * i].layers[1],
            net.params[f"layer_{1 + 2 * i}"]["sub1"],
            ref.make_leaves(cfg, SEED, f"L{i}.", ref.layer_shapes(cfg, i)), cfg)


def kda_inputs(b=2, t=37, h=3, dk=8, dv=12, seed=0, floor=-5.0):
    """Normalised q, k; v; per-channel log decays over the safe gate's
    range ``(floor, 0)``; beta in (0, 1); a state to start from."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = floor * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (b, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    return tuple(x.astype(jnp.float32) for x in (q, k, v, g, beta, s0))


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def served_gaps(net, cfg, requests, **engine_kw):
    """Each request served by a fresh engine of 3 slots: the gap of every
    served token under the reference's best logit at its position."""
    engine_kw.setdefault("registry", MetricsRegistry())
    plant = engine_kw.pop("plant", None)
    eng = GenerationEngine(net, slots=3, page_size=4, max_context=48,
                           prefill_buckets=(8, 16, 32), max_queue=32,
                           **engine_kw)
    if plant:
        plant(eng)
    eng.start()
    try:
        handles = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        served = [np.asarray(h.result(timeout=120)) for h in handles]
    finally:
        eng.stop()
    w = ref.make_weights(cfg, SEED)
    gaps = []
    for (prompt, n), toks in zip(requests, served):
        assert len(toks) == n
        seq = np.asarray(list(prompt) + toks.tolist())
        rows = np.asarray(ref.forward(w, seq, cfg))[len(prompt) - 1:-1]
        gaps.append(rows.max(axis=1) - rows[np.arange(n), toks])
    return eng, np.concatenate(gaps)


def some_requests(count=7, seed=1, vocab=97):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, int(rng.integers(3, 30))).tolist(),
             int(rng.integers(2, 10))) for _ in range(count)]


# ------------------------------------ (a) the per-channel forms of the rule
@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
def test_the_per_channel_chunked_form_is_the_step_recurrence(chunk):
    """The WY form with the decay inside every product, at chunks that
    divide the sequence, that do not, and that hold it whole: one output
    and one state, and the reference's own recurrence row by row."""
    q, k, v, g, beta, s0 = kda_inputs()
    want_o, want_s = dr.kda_stepwise(q, k, v, g, beta, s0)
    got_o, got_s = dr.kda_chunked(q, k, v, g, beta, s0, chunk=chunk)
    assert _gap(got_o, want_o) < 1e-5 and _gap(got_s, want_s) < 1e-5
    ro, rs = ref.kda_rule(q[0], k[0], v[0], g[0], beta[0], s0[0])
    assert _gap(got_o[0], ro) < 1e-5 and _gap(got_s[0], rs) < 1e-5


def test_the_chunked_form_holds_at_the_safe_gates_floor():
    """A chunk of 64 with every log decay at -4.99: the factored products
    ``(K exp(G)) (K exp(-G))^T`` overflow float32 (``exp(-G)`` reaches
    ``exp(319)``), the sub-blocks do not."""
    q, k, v, _, beta, s0 = kda_inputs(b=1, t=64, h=2, dk=16, dv=16, seed=4)
    g = jnp.full(q.shape, -4.99, jnp.float32)
    cum = jnp.cumsum(g, axis=1)
    naive = jnp.einsum("bihk,bjhk->bhij", k * jnp.exp(cum), k * jnp.exp(-cum))
    assert not bool(jnp.isfinite(naive).all())
    got_o, got_s = dr.kda_chunked(q, k, v, g, beta, s0, chunk=64)
    want_o, want_s = dr.kda_stepwise(q, k, v, g, beta, s0)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    assert _gap(got_o, want_o) < 1e-5 and _gap(got_s, want_s) < 1e-5


@pytest.mark.parametrize("form", ["kda_chunked", "kda_stepwise"])
def test_positions_past_live_leave_the_per_channel_state_alone(form):
    q, k, v, g, beta, s0 = kda_inputs()
    o, s = getattr(dr, form)(q, k, v, g, beta, s0, jnp.array([20, 37]))
    short_o, short_s = dr.kda_stepwise(
        *(x[:1, :20] for x in (q, k, v, g, beta)), s0[:1])
    assert _gap(s[0], short_s[0]) < 1e-5 and _gap(o[0, :20], short_o[0]) < 1e-5
    _, whole_s = dr.kda_stepwise(q, k, v, g, beta, s0)
    assert _gap(s[1], whole_s[1]) < 1e-5


@pytest.mark.parametrize("h,dv,group", [(4, 128, 1), (4, 64, 2), (3, 8, 1)])
def test_the_per_channel_decode_step_is_one_step(h, dv, group):
    q, k, v, g, beta, s0 = kda_inputs(t=1, h=h, dv=dv, seed=3)
    assert dr.slot_group(h, dv) == group
    o, s = dr.kda_single_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                              dr.to_slots(s0, group))
    want_o, want_s = dr.kda_stepwise(q, k, v, g, beta, s0)
    assert _gap(o, want_o[:, 0]) < 1e-5
    assert _gap(dr.to_heads(s, h), want_s) < 1e-5


@pytest.mark.parametrize("b,h,dk,dv", [(3, 4, 16, 64), (5, 3, 8, 128),
                                       (3, 32, 128, 128)])
def test_the_kda_kernel_steps_the_slots_as_the_jnp_step(b, h, dk, dv):
    """``kda_step_slots`` (the Pallas kernel, interpreted) against
    ``kda_single_step`` and the pool's selects at the toy's widths, at two
    heads to a row and at Ling's row ``[32, 128, 128]``: one output and
    one pool to float32 rounding; an idle lane's row and the trash row bit
    for bit; a fresh lane from zero state whatever its row holds."""
    q, k, v, g, beta, _ = kda_inputs(b=b, t=1, h=h, dk=dk, dv=dv, seed=b + h)
    one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    group = dr.slot_group(h, dv)
    sh = jax.random.normal(jax.random.PRNGKey(b), (b + 1, h // group, dk,
                                                   group * dv), jnp.float32)
    fresh = jnp.arange(b) % 3 == 1
    lanes = jnp.arange(b) % 4 != 2
    o, pool = dr.kda_step_slots(*one, sh, fresh, lanes)
    s_was = sh[1:]
    want_o, s = dr.kda_single_step(
        *one, jnp.where(fresh[:, None, None, None], 0.0, s_was))
    want_pool = sh.at[1:].set(jnp.where(lanes[:, None, None, None], s, s_was))
    assert pool.shape == sh.shape and pool.dtype == jnp.float32
    assert _gap(o, want_o) < 1e-5 and _gap(pool, want_pool) < 1e-5
    assert (np.asarray(pool[0]) == np.asarray(sh[0])).all()
    idle = np.flatnonzero(~np.asarray(lanes)) + 1
    assert len(idle) and (np.asarray(pool)[idle] == np.asarray(sh)[idle]).all()
    fresh_live = np.flatnonzero(np.asarray(fresh & lanes))
    noise = sh.at[1 + fresh_live].set(1e3)
    o2, pool2 = dr.kda_step_slots(*one, noise, fresh, lanes)
    assert _gap(o2[fresh_live], o[fresh_live]) == 0.0
    assert _gap(pool2[1 + fresh_live], pool[1 + fresh_live]) == 0.0
    with pytest.raises(ValueError, match="float32"):
        dr.kda_step_slots(*one, sh.astype(jnp.bfloat16), fresh, lanes)


# (the cases of the chunk kernel: rows, length, heads, the row's live
# length or None, every log decay at the safe gate's floor)
CHUNK_CASES = {"whole_chunks": (2, 128, 2, None, False),
               "part_chunk": (2, 100, 2, None, False),
               "live_shorter": (2, 150, 2, (90, 150), False),
               "at_the_floor": (1, 128, 2, None, True),
               "heads_of_a_block": (1, 70, 3, None, False),
               "blocks_of_heads": (1, 64, 8, None, False)}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_the_chunk_kernel_is_the_per_channel_chunked_form(case):
    """``kda_chunk_kernel`` (the Pallas kernel, interpreted) at whole lanes
    (d_k = d_v = 128) from a nonzero state: one output and one state with
    the ``lax`` WY form and the step recurrence, and the reference's own
    recurrence on one row; past ``live`` the state is left alone."""
    b, t, h, live, floor = CHUNK_CASES[case]
    q, k, v, g, beta, s0 = kda_inputs(b=b, t=t, h=h, dk=128, dv=128,
                                      seed=t + h)
    if floor:
        g = jnp.full(g.shape, -4.99, jnp.float32)
    assert float(jnp.abs(s0).min()) > 0
    live = None if live is None else jnp.array(live)
    got_o, got_s = dr.kda_chunk_kernel(q, k, v, g, beta, s0, live)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    for form in (dr.kda_chunked, dr.kda_stepwise):
        want_o, want_s = form(q, k, v, g, beta, s0, live)
        assert _gap(got_s, want_s) < 1e-5
        if live is None:
            assert _gap(got_o, want_o) < 1e-5
        else:
            for row, n in enumerate(np.asarray(live)):
                assert _gap(got_o[row, :n], want_o[row, :n]) < 1e-5
    n = t if live is None else int(live[0])
    ro, rs = ref.kda_rule(*(x[0, :n] for x in (q, k, v, g, beta)), s0[0])
    assert _gap(got_o[0, :n], ro) < 1e-5 and _gap(got_s[0], rs) < 1e-5


def test_the_chunk_kernel_takes_whole_lanes_alone():
    assert dr.kda_chunk_fits(128, 128) and dr.kda_chunk_fits(256, 128)
    assert not dr.kda_chunk_fits(32, 32) and not dr.kda_chunk_fits(128, 64)
    assert [dr.chunk_heads(h) for h in (32, 8, 3, 5, 1)] == [4, 4, 3, 1, 1]
    q, k, v, g, beta, s0 = kda_inputs(b=1, t=8, h=2, dk=32, dv=32)
    with pytest.raises(ValueError, match="whole lanes"):
        dr.kda_chunk_kernel(q, k, v, g, beta, s0)


def test_the_kda_paths_are_named_apart_from_the_delta_rules(monkeypatch):
    took = [delta_rule_path(t, k, seam, KDA_PATHS) for t, k, seam in
            ((1, False, True), (2, False, True), (1, True, True),
             (2, False, False))]
    assert took == ["kda_step", "kda_chunk", "kda_kernel", "kda_stepwise"]
    # the fifth, where the chunk kernel takes the widths; never for Olmo's
    assert delta_rule_path(2, True, True, KDA_PATHS, True) == \
        "kda_chunk_kernel"
    assert delta_rule_path(2, True, False, KDA_PATHS, True) == "kda_stepwise"
    assert delta_rule_path(1, True, True, KDA_PATHS, True) == "kda_kernel"
    assert delta_rule_path(2, True, True, DELTA_RULE_PATHS, True) == \
        "delta_chunk"
    assert KDA_PATHS[:4] == ("kda_step", "kda_chunk", "kda_kernel",
                             "kda_stepwise")
    layer = mixer_and_leaves(0)[0]
    assert isinstance(layer, KimiDeltaAttentionLayer)
    assert (layer.path(1), layer.path(64)) == ("kda_step", "kda_chunk")
    olmo = GatedDeltaNetLayer(n_in=8, n_out=8, n_heads=2, d_k=4, d_v=4)
    assert (olmo.path(1), olmo.path(64)) == ("delta_step", "delta_chunk")
    monkeypatch.setattr(dr.DeltaRuleHelper, "kernel", True)
    lanes = KimiDeltaAttentionLayer(n_in=8, n_heads=2, d_k=128, d_v=128)
    assert (lanes.path(1), lanes.path(64)) == ("kda_kernel",
                                               "kda_chunk_kernel")
    assert (layer.path(1), layer.path(64)) == ("kda_kernel", "kda_chunk")
    wide = GatedDeltaNetLayer(n_in=8, n_heads=2, d_k=128, d_v=128)
    assert (wide.path(1), wide.path(64)) == ("delta_kernel", "delta_chunk")


# ------------------------------------------------------- (b) the KDA layer
def test_the_kda_mixer_equals_the_reference():
    layer, params, w, cfg = mixer_and_leaves(0)
    assert layer.state_shape() == (1, 32, 128)      # four heads a row
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 64), jnp.float32)
    got, _ = layer.apply(params, {}, u)
    for b in range(2):
        assert _gap(got[b], ref.kda(u[b], w, cfg, "f32")) < 1e-5
    helpers.enable_helpers(False)
    try:
        off, _ = layer.apply(params, {}, u)
    finally:
        helpers.enable_helpers(True)
    assert _gap(off, got) < 1e-5


def test_the_kda_layer_round_trips_and_draws_slow_channels():
    layer = KimiDeltaAttentionLayer(n_in=12, n_out=10, n_heads=3, d_k=4,
                                    d_v=6, lower_bound=-5.0, name="k")
    again = layer_from_dict(layer.to_dict())
    assert again == layer and again.kind == "recurrent"
    p = layer.init(jax.random.PRNGKey(0))
    assert {k: p[k].shape for k in ("W_a", "W_b", "W_g", "A_log", "dt_bias",
                                    "o_norm")} == {
        "W_a": (12, 12), "W_b": (12, 3), "W_g": (12, 3), "A_log": (3,),
        "dt_bias": (12,), "o_norm": (18,)}
    with pytest.raises(ValueError, match="lower_bound"):
        KimiDeltaAttentionLayer(n_in=4, n_heads=1, d_k=4, d_v=4,
                                lower_bound=-6.0).validate()
    # the reference's draw: some channels keep most of their state a step
    layer0, params, _, _ = mixer_and_leaves(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
    g = layer0._rule(params, jax.nn.silu(x @ jnp.concatenate(
        [params["W_q"], params["W_k"], params["W_v"]], axis=1)),
        x @ params["W_a"], x @ params["W_b"])[3]
    assert float(g.max()) > -0.05 and float(g.min()) < -1.0
    assert float(g.min()) > -5.0


# ----------------------------------- (c) latent attention, no query latent
def test_latent_attention_without_a_query_latent_equals_the_reference():
    layer, params, w, cfg = mixer_and_leaves(2)
    assert isinstance(layer, LatentAttentionLayer)
    assert (layer.q_rank, layer.gate) == (0, "per_head")
    assert set(params) == {"Wq", "Wkva", "kv_norm", "Wkvb", "Wg", "Wo"}
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 19, 64), jnp.float32)
    got, _ = layer.apply(params, {}, u)
    for b in range(2):
        assert _gap(got[b], ref.mla(u[b], w, cfg, "f32")) < 1e-5
    again = layer_from_dict(layer.to_dict())
    assert again == layer


# --------------------------------------------------------- (d) the router
def _route_by_token(s, b, n_group, topk_group, top_k, scale):
    """Group-limited routing one token at a time in plain numpy."""
    ids, ws = [], []
    for row_s, row_c in zip(s, s + b):
        groups = row_c.reshape(n_group, -1)
        score = [sorted(gr)[-1] + sorted(gr)[-2] for gr in groups]
        kept = sorted(range(n_group), key=lambda j: -score[j])[:topk_group]
        size = groups.shape[1]
        cands = [e for j in kept for e in range(j * size, (j + 1) * size)]
        chosen = sorted(cands, key=lambda e: -row_c[e])[:top_k]
        w = row_s[chosen] / (row_s[chosen].sum() + 1e-20)
        ids.append(sorted(chosen))
        ws.append(dict(zip(chosen, w * scale)))
    return ids, ws


@pytest.mark.parametrize("n_group,topk_group,top_k", [(8, 4, 8), (4, 2, 4),
                                                      (4, 1, 3)])
def test_group_limited_routing_is_the_per_token_rule(n_group, topk_group,
                                                     top_k):
    d, n = 16, 64
    layer = RoutedMoELayer(n_in=d, n_out=d, n_experts=n, top_k=top_k,
                           hidden=8, n_group=n_group, topk_group=topk_group,
                           routed_scaling_factor=2.5)
    layer.validate()
    ks = jax.random.split(jax.random.PRNGKey(n_group + top_k), 3)
    params = {"W_router": jax.random.normal(ks[0], (d, n)),
              "b_router": 0.3 * jax.random.normal(ks[1], (n,))}
    x = jax.random.normal(ks[2], (40, d))
    ids, w = layer.route(params, x)
    s = np.asarray(jax.nn.sigmoid(x @ params["W_router"]))
    want_ids, want_w = _route_by_token(s, np.asarray(params["b_router"]),
                                       n_group, topk_group, top_k, 2.5)
    got_ids = np.asarray(ids)
    assert [sorted(r) for r in got_ids.tolist()] == want_ids
    for row, wrow, want in zip(got_ids, np.asarray(w), want_w):
        assert np.allclose([want[e] for e in row], wrow, atol=1e-6)
    groups = got_ids // (n // n_group)
    assert max(len(set(r)) for r in groups.tolist()) <= topk_group
    # the reference's sorts choose the same experts
    rid, rw = ref.route(x, {"router.W": params["W_router"],
                            "router.b": params["b_router"]},
                        {"n_group": n_group, "topk_group": topk_group,
                         "num_experts_per_tok": top_k,
                         "norm_topk_prob": True,
                         "routed_scaling_factor": 2.5})
    assert [sorted(r) for r in np.asarray(rid).tolist()] == want_ids
    # one group is the ungrouped rule
    assert (limit_to_groups(x, 1, 1) == x).all()


def test_the_groups_held_parts_add_up_to_the_uncut_layer():
    """The share: eight chips each holding one routing group of 8, the
    shared expert counted once, add up to the layer that holds all 64."""
    d, n, hidden = 16, 64, 8
    whole = RoutedMoELayer(n_in=d, n_out=d, n_experts=n, top_k=8,
                           hidden=hidden, shared=hidden, n_group=8,
                           topk_group=4, routed_scaling_factor=2.5)
    p = whole.init(jax.random.PRNGKey(5))
    p["b_router"] = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (n,))
    x = jax.random.normal(jax.random.PRNGKey(7), (24, d))
    want, _ = whole.apply(p, {}, x)
    total = 0.0
    for c in range(8):
        part = RoutedMoELayer(n_in=d, n_out=d, n_experts=n, top_k=8,
                              hidden=hidden, shared=hidden if c == 0 else 0,
                              experts_held=(8 * c, 8), n_group=8,
                              topk_group=4, routed_scaling_factor=2.5)
        held = {k: (a[8 * c:8 * c + 8] if k in ("W_gate", "W_up", "W_down")
                    else a) for k, a in p.items()
                if c == 0 or not k.startswith("Ws_")}
        total = total + part.apply(held, {}, x)[0]
    assert _gap(total, want) < 1e-5


def test_the_layers_without_groups_route_as_before():
    """The defaults leave k2's, Laguna's and Xing's routers as they were:
    one group, the top_k of s + b over all experts."""
    layer = RoutedMoELayer(n_in=8, n_out=8, n_experts=16, top_k=4, hidden=4)
    assert (layer.n_group, layer.topk_group) == (1, 1)
    p = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 8))
    ids, _ = layer.route(p, x)
    s = jax.nn.sigmoid(x @ p["W_router"]) + p["b_router"]
    assert (np.sort(np.asarray(ids), 1)
            == np.sort(np.asarray(jax.lax.top_k(s, 4)[1]), 1)).all()
    with pytest.raises(ValueError, match="n_group"):
        RoutedMoELayer(n_in=8, n_experts=16, top_k=8, hidden=4, n_group=4,
                       topk_group=1).validate()


# ------------------------------------------------- (e) the whole forward
def test_output_equals_the_reference_on_logits():
    net, cfg = toy_net()
    kinds = [type(l.layers[1]).__name__ for l in net.layers[1:9:2]]
    assert kinds == ["KimiDeltaAttentionLayer"] * 2 + [
        "LatentAttentionLayer", "KimiDeltaAttentionLayer"]
    ids = np.random.default_rng(0).integers(0, 97, (2, 29))
    got = np.log(np.asarray(net.output(ids), np.float64))
    w = ref.make_weights(cfg, SEED)
    want = np.stack([np.asarray(jax.nn.log_softmax(ref.forward(w, row, cfg)))
                     for row in ids])
    assert np.abs(got - want).max() < TOL


def test_the_published_layer_order_and_parameter_count():
    assert [i for i in range(8) if ref.is_mla(PUBLISHED, i)] == [5]
    assert [i for i in range(8) if ref.is_dense(PUBLISHED, i)] == [0, 1]
    shapes = ref.leaf_shapes(PUBLISHED)
    zero_biases = 2560 + 19648
    count = sum(int(np.prod(s)) for s in shapes.values()) - zero_biases
    assert count == 2_903_709_920
    net = model_ling.build_network(PUBLISHED)
    built = [jax.eval_shape(l.init, jax.random.PRNGKey(0))
             for l in net.layers if l.has_params()]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        built)) - zero_biases == count
    assert flops_ling.kda_state_bytes_per_slot(PUBLISHED) == 14_680_064
    assert flops_ling.held_assignments_per_token(PUBLISHED) == 1.0
    layer = net.layers[1].layers[1]
    pool = jax.eval_shape(lambda: layer.init_paged_cache(
        2, 64, jnp.bfloat16, state_slots=256))
    assert pool["sh"].shape == (257, 32, 128, 128)
    assert pool["sh"].dtype == jnp.float32


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    path = os.environ.get("MODEL_CATALOG", "")
    if not path or not os.path.exists(path):
        pytest.skip("the catalog is not here")
    with open(path) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "Ling-3.0-flash"' in line)
    for key, val in row["config"].items():
        if key not in PUBLISHED["reduced"]:
            assert PUBLISHED[key] == val, key
    assert {k: PUBLISHED["published"][k] for k in PUBLISHED["published"]} \
        == {k: row["config"][k] for k in PUBLISHED["published"]}


# ---------------------------------- (f) through the engine: slots and pages
def test_engine_serves_the_toy_model_as_the_reference():
    """Seven requests through three slots: every slot reused after another
    tenant, every bucket taken with padding; each served token is the
    reference's own at its position; the counter names the KDA paths."""
    net, cfg = toy_net()
    eng, gaps = served_gaps(net, cfg, some_requests())
    assert gaps.max() < TOL, gaps
    reg, eid = eng.metrics.registry, eng.metrics.engine_id
    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert reg.get_value(
        "dl4j_layer_path_steps_total", kind="recurrent", stage="decode",
        path="kda_step") == dispatched > 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="kda_chunk") == 7
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="decode", path="delta_step") is None
    assert reg.get_value("dl4j_state_slot_resets_total", engine=eid) == 7


def test_engine_serves_the_reference_through_the_kda_kernel(monkeypatch):
    """The seam made to offer the kernel (interpreted off the chip): every
    KDA layer's pool is stepped by ``kda_step_slots``, the served tokens
    stay the reference's, the counter reads ``kda_kernel`` once a
    dispatched decode step and ``kda_chunk`` once a prefill."""
    monkeypatch.setattr(dr.DeltaRuleHelper, "kernel", True)
    net, cfg = toy_net()
    eng, gaps = served_gaps(net, cfg, some_requests(count=5, seed=3))
    assert gaps.max() < TOL, gaps
    reg = eng.metrics.registry
    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert reg.get_value(
        "dl4j_layer_path_steps_total", kind="recurrent", stage="decode",
        path="kda_kernel") == dispatched > 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="decode", path="kda_step") is None
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="kda_chunk") == 5


def test_engine_serves_the_reference_through_the_kda_chunk_kernel(
        monkeypatch):
    """The toy at KDA heads of whole lanes (d_k = d_v = 128) with the seam
    made to offer its kernels (interpreted off the chip): every prefill's
    KDA layers run ``kda_chunk_kernel``, the served tokens stay the
    reference's, the counter reads ``kda_chunk_kernel`` once a prefill and
    ``kda_chunk`` not at all, ``kda_kernel`` once a dispatched decode
    step."""
    monkeypatch.setattr(dr.DeltaRuleHelper, "kernel", True)
    net, cfg = toy_net(head_dim=128)
    assert net.layers[1].layers[1].state_shape() == (4, 128, 128)
    eng, gaps = served_gaps(net, cfg, some_requests(count=4, seed=5))
    assert gaps.max() < TOL, gaps
    reg = eng.metrics.registry
    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert reg.get_value(
        "dl4j_layer_path_steps_total", kind="recurrent", stage="decode",
        path="kda_kernel") == dispatched > 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="kda_chunk_kernel") == 4
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="kda_chunk") is None


@pytest.mark.parametrize("fault", ["token_altered", "state_not_reset"])
def test_a_faulty_program_fails_the_same_comparison(fault):
    """A token altered, and a slot that keeps its last tenant's state (the
    reference draws ``A_log`` and ``dt_bias`` as FLA does, so some channels
    decay slowly and the stale state reaches the logits), each move the
    served tokens off the reference's."""
    net, cfg = toy_net()
    undo = serve_linear_attention._plant_state_fault(fault)
    try:
        plant = ((lambda eng: serve_latent_moe._plant_token_altered(eng, 97))
                 if fault == "token_altered" else None)
        _, gaps = served_gaps(net, cfg, some_requests(), plant=plant)
    finally:
        for u in undo:
            u()
    assert gaps.mean() > 10 * TOL, (fault, gaps.mean())
    assert GatedDeltaNetLayer.apply_with_carry.__name__ == "apply_with_carry"


def test_the_bf16_state_fault_rounds_the_state_on_every_step():
    """``state_bf16`` (the calibration's lower precision inside the
    program) makes the KDA pools bfloat16 and the decode step the ``jnp``
    form; the layer's outputs over a few steps then leave the float32
    pool's, and undoing it leaves the class as it was."""
    layer, params, _, _ = mixer_and_leaves(0)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 64), jnp.float32)

    def steps():
        pool = layer.init_paged_cache(1, 4, jnp.float32, state_slots=2)
        outs = []
        for t in range(6):
            carry = {**pool, "pos": jnp.full((2,), t, jnp.int32),
                     "lanes": jnp.ones((2,), bool)}
            y, _, new = layer.apply_with_carry(params, {}, u[:, t:t + 1],
                                               carry)
            pool = {k: new[k] for k in pool}
            outs.append(y)
        return jnp.concatenate(outs, 1), pool["sh"].dtype

    want, dtype = steps()
    undo = serve_kda_moe._plant_state_bf16()
    try:
        assert layer.path(1) == "kda_step"
        got, bf_dtype = steps()
    finally:
        for f in undo:
            f()
    assert (dtype, bf_dtype) == (jnp.float32, jnp.bfloat16)
    assert _gap(got, want) > 1e-4
    assert "path" not in vars(KimiDeltaAttentionLayer)
    assert "init_paged_cache" not in vars(KimiDeltaAttentionLayer)

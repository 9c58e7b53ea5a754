"""The Gated DeltaNet mixer (``GatedDeltaNetLayer``) and Olmo-Hybrid's net —
delta-rule layers on state slots beside multi-head full attention with
whole-width q/k norms on pages — against the plain reference
``benchmark/reference_olmo_hybrid.py`` at a toy size on seeded random
weights: the forms of the delta rule against each other (the decode step's
Pallas kernel on a pool of state slots interpreted), the layer alone, the
q/k-norm attention layer, the whole model through ``net.output``, through
``models.decode.generate`` / ``rnn_time_step`` and through
``GenerationEngine`` (bucketed prefill, then decode through slots and
pages, slots reused, bucket padding; with and without the kernel), the
faults that must fail the same comparison, the slot's bytes and the
parameter count."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_olmo_hybrid, model_olmo_hybrid
from benchmark import reference_olmo_hybrid as ref
from benchmark.jobs import serve, serve_linear_attention
from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.helpers import delta_rule as dr
from deeplearning4j_tpu.models.decode import generate
from deeplearning4j_tpu.nn.layers import GatedDeltaNetLayer, SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.base import layer_from_dict
from deeplearning4j_tpu.nn.layers.delta_net import (
    DELTA_RULE_PATHS, delta_rule_path,
)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                       "olmo-hybrid-7b-pp4.json")) as f:
    PUBLISHED = json.load(f)
# linear, full, linear, full: both kinds twice; 4 heads of d_v 64 lie two to
# a row of 128 lanes in the slot layout, as Olmo's 192 lie two to 384
TOY = {**PUBLISHED, **dict(
    hidden_size=64, intermediate_size=160, num_attention_heads=4,
    num_key_value_heads=4, linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=64, num_hidden_layers=4,
    layer_types=["linear_attention", "full_attention"] * 2, vocab_size=97,
    torch_dtype="float32", initializer_range=0.2)}
SEED = 2**31 + 17
# float32 on both sides, the same mathematics in another order
TOL = 2e-4


def toy_net(**over):
    cfg = {**TOY, **over}
    net = model_olmo_hybrid.build_network(cfg)
    return model_olmo_hybrid.install_weights(net, cfg, SEED), cfg


def mixer_and_leaves(i=0, **over):
    net, cfg = toy_net(**over)
    layer = net.layers[1 + 2 * i].layers[0]
    return (layer, net.params[f"layer_{1 + 2 * i}"]["sub0"],
            ref.make_leaves(cfg, SEED, f"L{i}.", ref.layer_shapes(cfg, i)), cfg)


def rule_inputs(b=2, t=37, h=3, dk=8, dv=12, seed=0):
    """Normalised q, k; v; log decays spanning fast and slow; beta up to 2
    (``allow_neg_eigval``); a state to start from."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -3.0 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, s0


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


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# --------------------------------------------- (a) the three forms of the rule
@pytest.mark.parametrize("chunk", [1, 5, 16, 37, 64])
def test_the_chunked_form_is_the_step_recurrence(chunk):
    """The WY form at chunk lengths that divide the sequence, that do not,
    and that hold it whole, with beta up to 2: one output, one state."""
    q, k, v, g, beta, s0 = rule_inputs()
    want_o, want_s = dr.stepwise(q, k, v, g, beta, s0)
    got_o, got_s = dr.chunked(q, k, v, g, beta, s0, chunk=chunk)
    assert _gap(got_o, want_o) < 1e-5 and _gap(got_s, want_s) < 1e-5
    # the same against the reference's own recurrence, row by row
    ro, rs = ref.delta_rule(q[0], k[0], v[0], g[0], beta[0], s0[0])
    assert _gap(got_o[0], ro) < 1e-5 and _gap(got_s[0], rs) < 1e-5


@pytest.mark.parametrize("form", ["chunked", "stepwise"])
def test_positions_past_live_leave_the_state_alone(form):
    q, k, v, g, beta, s0 = rule_inputs()
    live = jnp.array([20, 37])
    rule = getattr(dr, form)
    o, s = rule(q, k, v, g, beta, s0, live)
    short_o, short_s = dr.stepwise(*(x[:1, :20] for x in (q, k, v, g, beta)),
                                   s0[:1])
    assert _gap(s[0], short_s[0]) < 1e-5
    assert _gap(o[0, :20], short_o[0]) < 1e-5
    whole_o, whole_s = dr.stepwise(q, k, v, g, beta, s0)
    assert _gap(s[1], whole_s[1]) < 1e-5


@pytest.mark.parametrize("h,dv,group", [(4, 64, 2), (2, 192, 2), (4, 8, 1),
                                        (3, 128, 1)])
def test_the_decode_step_on_the_slot_layout_is_one_step(h, dv, group):
    """The slot layout groups heads to whole lanes of 128 (two of 192: 384,
    Olmo's) and the single step there is the recurrence's step."""
    assert dr.slot_group(h, dv) == group
    q, k, v, g, beta, s0 = rule_inputs(t=1, h=h, dv=dv, seed=3)
    slots = dr.to_slots(s0, group)
    assert slots.shape == (2, h // group, 8, group * dv)
    assert _gap(dr.to_heads(slots, h), s0) == 0.0
    o, s = dr.single_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                          slots)
    want_o, want_s = dr.stepwise(q, k, v, g, beta, s0)
    assert _gap(o, want_o[:, 0]) < 1e-5
    assert _gap(dr.to_heads(s, h), want_s) < 1e-5


def test_the_path_rule_is_pure():
    took = [delta_rule_path(t, k, seam) for t, k, seam in
            ((1, False, True), (2, False, True), (512, True, True),
             (1, True, True), (2, False, False))]
    assert took == ["delta_step", "delta_chunk", "delta_chunk",
                    "delta_kernel", "delta_stepwise"]
    assert set(took) == set(DELTA_RULE_PATHS)
    assert [delta_rule_path(1, True) for _ in range(3)] == ["delta_kernel"] * 3
    # off the TPU the seam offers no kernel
    layer, _, _, _ = mixer_and_leaves()
    assert (layer.path(1), layer.path(256)) == ("delta_step", "delta_chunk")


def slot_pool_inputs(b, h, dk, dv, seed):
    """One token a lane, a pool of ``b`` slots behind a trash row on the slot
    layout, and ``fresh`` / ``lanes`` with both values among the lanes."""
    one = rule_inputs(b=b, t=1, h=h, dk=dk, dv=dv, seed=seed)[:5]
    group = dr.slot_group(h, dv)
    sh = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (b + 1, h // group, dk, group * dv), jnp.float32)
    fresh = jnp.arange(b) % 3 == 1
    lanes = jnp.arange(b) % 4 != 2
    return (tuple(x[:, 0].astype(jnp.float32) for x in one), sh, fresh,
            lanes)


def jnp_step_on_slots(one, sh, fresh, lanes):
    """The ``delta_step`` path's sequence on the pool: ``single_step`` from
    the rows (zero where ``fresh``), written back where ``lanes``."""
    s_was = sh[1:]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, s_was)
    o, s = dr.single_step(*one, s0)
    return o, sh.at[1:].set(jnp.where(lanes[:, None, None, None], s, s_was))


@pytest.mark.parametrize("b,h,dk,dv", [(3, 4, 16, 64), (5, 4, 8, 8),
                                       (4, 3, 8, 128), (3, 30, 96, 192),
                                       (5, 30, 96, 192)])
def test_the_kernel_steps_the_slots_as_the_jnp_step(b, h, dk, dv):
    """``step_slots`` (the Pallas kernel, interpreted) against
    ``single_step`` and the pool's ``where`` / ``.at[1:].set`` at the toy's
    widths, at groups of one head, and at Olmo's row ``[15, 96, 384]``:
    one output and one pool to float32 rounding; an idle lane's row and the
    trash row bit for bit; a fresh lane from zero state."""
    one, sh, fresh, lanes = slot_pool_inputs(b, h, dk, dv, seed=b + h)
    o, pool = dr.step_slots(*one, sh, fresh, lanes)
    want_o, want_pool = jnp_step_on_slots(one, sh, fresh, lanes)
    assert pool.shape == sh.shape and pool.dtype == jnp.float32
    assert _gap(o, want_o) < 1e-5 and _gap(pool, want_pool) < 1e-5
    assert (np.asarray(pool[0]) == np.asarray(sh[0])).all()
    idle = np.flatnonzero(~np.asarray(lanes)) + 1
    assert len(idle) and (np.asarray(pool)[idle] == np.asarray(sh)[idle]).all()
    # a fresh lane's step does not read its row: any row gives one result
    fresh_live = np.flatnonzero(np.asarray(fresh & lanes))
    assert len(fresh_live)
    noise = sh.at[1 + fresh_live].set(1e3)
    o2, pool2 = dr.step_slots(*one, noise, fresh, lanes)
    assert _gap(o2[fresh_live], o[fresh_live]) == 0.0
    assert _gap(pool2[1 + fresh_live], pool[1 + fresh_live]) == 0.0
    _, zero_s = dr.single_step(*(x[fresh_live] for x in one),
                               jnp.zeros_like(sh[1 + fresh_live]))
    assert _gap(pool[1 + fresh_live], zero_s) < 1e-5


def test_the_kernel_refuses_a_pool_it_cannot_step():
    one, sh, fresh, lanes = slot_pool_inputs(3, 4, 16, 64, seed=0)
    with pytest.raises(ValueError, match="trash row"):
        dr.step_slots(*one, sh[1:], fresh, lanes)
    with pytest.raises(ValueError, match="float32"):
        dr.step_slots(*one, sh.astype(jnp.bfloat16), fresh, lanes)


def test_the_seam_offers_the_kernel_on_the_tpu(monkeypatch):
    helper = helpers.get_helper("delta_rule")
    layer, _, _, _ = mixer_and_leaves()
    assert not helper.kernel and layer.path(1) == "delta_step"     # the CPU
    monkeypatch.setattr(dr, "_interpret", lambda: False)
    assert helper.kernel
    assert (layer.path(1), layer.path(2)) == ("delta_kernel", "delta_chunk")
    helpers.enable_helpers(False)
    try:
        assert layer.path(1) == "delta_step"
    finally:
        helpers.enable_helpers(True)
    assert dr.step_vmem_bytes((15, 96, 384)) == 4 * 2_211_840


# ----------------------------------------------------- (b) the layer alone
def test_the_mixer_equals_the_reference():
    layer, params, w, cfg = mixer_and_leaves()
    assert isinstance(layer, GatedDeltaNetLayer) and layer.group == 2
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 64), jnp.float32)
    got, _ = layer.apply(params, {}, u)
    for b in range(2):
        want, _ = ref.gated_deltanet(u[b], w, cfg, "f32")
        assert _gap(got[b], want) < 1e-5


def test_every_carry_gives_the_same_sequence():
    """``step`` a token at a time, two chunks through the contiguous carry,
    and the whole sequence with helpers off (the stepwise rule): one output,
    one state."""
    layer, params, _, _ = mixer_and_leaves()
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 29, 64), jnp.float32)
    carry, outs = layer.initial_carry(2), []
    for t in range(29):
        y, carry = layer.step(params, carry, u[:, t])
        outs.append(y)
    want = jnp.stack(outs, axis=1)
    got, _, (s, tail) = layer.apply_with_carry(params, {}, u, None)
    assert _gap(got, want) < 1e-5 and _gap(s, carry[0]) < 1e-5
    assert _gap(tail, carry[1]) == 0.0
    a, _, mid = layer.apply_with_carry(params, {}, u[:, :11], None)
    b, _, _ = layer.apply_with_carry(params, {}, u[:, 11:], mid)
    assert _gap(jnp.concatenate([a, b], 1), want) < 1e-5
    helpers.enable_helpers(False)
    try:
        off, _ = layer.apply(params, {}, u)
    finally:
        helpers.enable_helpers(True)
    assert _gap(off, want) < 1e-5


def test_the_state_is_float32_under_a_bfloat16_net():
    layer, params, _, _ = mixer_and_leaves()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 64), jnp.bfloat16)
    y, _, (s, tail) = layer.apply_with_carry(params, {}, u, None)
    assert (y.dtype, s.dtype, tail.dtype) == (jnp.bfloat16, jnp.float32,
                                              jnp.bfloat16)
    pool = layer.init_paged_cache(9, 4, jnp.bfloat16, state_slots=5)
    assert pool["sh"].shape == (6, 2, 16, 128)
    assert pool["sh"].dtype == jnp.float32
    assert pool["sc"].shape == (6, 3, 2 * 64 + 256)
    assert pool["sc"].dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="state_slots"):
        layer.init_paged_cache(9, 4)


def test_a_slot_holds_the_published_state_unpadded():
    """At Olmo-Hybrid's widths a row of a layer's state is 30 heads x [96,
    192] float32 = 2,211,840 B, laid as 15 pairs of heads x 96 x 384: whole
    sublanes and whole lanes of 128, so the device holds exactly that."""
    layer = GatedDeltaNetLayer(n_in=3840, n_out=3840, n_heads=30, d_k=96,
                               d_v=192, allow_neg_eigval=True, name="g")
    pool = jax.eval_shape(lambda: layer.init_paged_cache(
        2, 64, jnp.bfloat16, state_slots=128))
    sh = pool["sh"]
    assert sh.shape == (129, 15, 96, 384)
    assert sh.shape[-1] % 128 == 0 and sh.shape[-2] % 8 == 0
    assert sh.dtype.itemsize * int(np.prod(sh.shape[1:])) == 2_211_840
    assert flops_olmo_hybrid.delta_state_bytes_per_slot(PUBLISHED) == \
        6 * 2_211_840


def test_the_layer_round_trips_through_its_dict_and_the_builder():
    layer = GatedDeltaNetLayer(n_in=12, n_out=10, n_heads=3, d_k=4, d_v=6,
                               allow_neg_eigval=True, eps=1e-5, name="g")
    again = layer_from_dict(layer.to_dict())
    assert again == layer and again.kind == "recurrent"
    p = layer.init(jax.random.PRNGKey(0))
    assert set(p) == {"W_q", "W_k", "W_v", "W_a", "W_b", "W_g", "W_o",
                      "conv_q", "conv_k", "conv_v", "A_log", "dt_bias",
                      "o_norm"}
    # FLA's start: A in (0, 16], steps in [1e-3, 1e-1]
    a = np.exp(np.asarray(p["A_log"]))
    assert (0 < a).all() and (a <= 16 * 1.0001).all()
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    net, _ = toy_net()
    conf = type(net.conf).from_json(net.conf.to_json())
    assert [type(l).__name__ for l in conf.layers] == [
        type(l).__name__ for l in net.layers]
    assert conf.layers[1].layers[0] == net.layers[1].layers[0]
    assert conf.layers[3].layers[0] == net.layers[3].layers[0]


# ---------------------------------- (c) full attention with q/k norms
@pytest.mark.parametrize("qk_norm", [True, False])
def test_whole_width_qk_norm_attention_equals_the_reference(qk_norm):
    net, cfg = toy_net()
    layer = net.layers[3].layers[0]
    assert isinstance(layer, SelfAttentionLayer)
    assert (layer.qk_norm_eps, layer.rope, layer.bias, layer._kv_heads) == (
        cfg["rms_norm_eps"], False, False, 4)
    params = dict(net.params["layer_3"]["sub0"])
    w = ref.make_leaves(cfg, SEED, "L1.", ref.layer_shapes(cfg, 1))
    if not qk_norm:      # off, the layer is the one it was: no norm leaves
        import dataclasses

        layer = dataclasses.replace(layer, qk_norm_eps=None)
        assert set(layer.init(jax.random.PRNGKey(0))) == {"Wq", "Wk", "Wv",
                                                           "Wo"}
        w = {**w, "q_norm.g": jnp.ones(64), "k_norm.g": jnp.ones(64)}
        params = {k: params[k] for k in ("Wq", "Wk", "Wv", "Wo")}
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64), jnp.float32)
    got, _ = layer.apply(params, {}, u)
    for b in range(2):
        want = ref.attention(u[b], w, cfg, "f32")
        if not qk_norm:
            assert _gap(got[b], want) > 1e-3     # the norm is not a no-op
        else:
            assert _gap(got[b], want) < 1e-5


# ------------------------------------------------- (d) the whole forward
def test_output_equals_the_reference_on_logits():
    net, cfg = toy_net()
    kinds = [type(l.layers[0]).__name__ for l in net.layers[1:9:2]]
    assert kinds == ["GatedDeltaNetLayer", "SelfAttentionLayer"] * 2
    ids = np.random.default_rng(0).integers(0, 97, (2, 29))
    got = np.log(np.asarray(net.output(ids), np.float64))
    w = ref.make_weights(cfg, SEED)
    want = np.stack([np.asarray(jax.nn.log_softmax(ref.forward(w, row, cfg)))
                     for row in ids])
    assert np.abs(got - want).max() < TOL


def test_the_published_layer_order_and_parameter_count():
    assert [i for i in range(8) if ref.is_full(PUBLISHED, i)] == [3, 7]
    assert flops_olmo_hybrid.parameter_count(PUBLISHED) == 2_435_748_072
    shapes = ref.leaf_shapes(PUBLISHED)
    zero_biases = 3840 + 100352
    assert sum(int(np.prod(s)) for s in shapes.values()) - zero_biases == \
        flops_olmo_hybrid.parameter_count(PUBLISHED)
    net = model_olmo_hybrid.build_network(PUBLISHED)
    built = [jax.eval_shape(l.init, jax.random.PRNGKey(0))
             for l in net.layers if l.has_params()]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        built)) - zero_biases == 2_435_748_072


def test_generate_and_rnn_time_step_take_the_layer_as_they_take_an_lstm():
    net, cfg = toy_net()
    prompt = np.random.default_rng(2).integers(0, 97, (2, 11))
    toks = generate(net, prompt, 6, temperature=0.0)
    w = ref.make_weights(cfg, SEED)
    for b in range(2):
        seq = np.concatenate([prompt[b], toks[b]])
        rows = np.asarray(ref.forward(w, seq, cfg))[10:-1]
        assert (rows.max(axis=1) - rows[np.arange(6), toks[b]]).max() < TOL
    net.rnn_clear_previous_state()
    first = net.rnn_time_step(prompt[:, :7])
    rest = [net.rnn_time_step(prompt[:, t]) for t in range(7, 11)]
    whole = np.asarray(net.output(prompt))
    assert np.abs(np.asarray(first) - whole[:, :7]).max() < 1e-5
    assert np.abs(np.stack(rest, 1) - whole[:, 7:]).max() < 1e-5


# ---------------------------------- (e) through the engine: slots and pages
def test_engine_serves_the_toy_model_as_the_reference():
    """Seven requests of different lengths through three slots: every slot
    is reused after another tenant (its row begun anew at position 0),
    every bucket is taken with padding behind the prompt; each served token
    is the reference's own at its position."""
    net, cfg = toy_net()
    eng, gaps = served_gaps(net, cfg, some_requests())
    assert gaps.max() < TOL, gaps
    reg, eid = eng.metrics.registry, eng.metrics.engine_id
    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert reg.get_value(
        "dl4j_layer_path_steps_total", kind="recurrent", stage="decode",
        path="delta_step") == dispatched > 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="delta_chunk") == 7
    assert reg.get_value("dl4j_state_slot_resets_total", engine=eid) == 7
    assert reg.get_value("dl4j_state_slots_in_use", engine=eid) == 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="decode", path="step") is None


def test_engine_serves_the_reference_through_the_kernel(monkeypatch):
    """The seam made to offer the kernel (interpreted off the chip): the
    decode program steps every delta-rule layer's pool by ``step_slots``,
    the served tokens stay the reference's, and the counter reads
    ``delta_kernel`` once a dispatched decode step, ``delta_step`` never."""
    monkeypatch.setattr(dr.DeltaRuleHelper, "kernel", True)
    net, cfg = toy_net()
    eng, gaps = served_gaps(net, cfg, some_requests(count=5, seed=3))
    assert gaps.max() < TOL, gaps
    reg = eng.metrics.registry
    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert reg.get_value(
        "dl4j_layer_path_steps_total", kind="recurrent", stage="decode",
        path="delta_kernel") == dispatched > 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="decode", path="delta_step") is None
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="delta_chunk") == 5


# The toy net's programs as the parent commit d30b33b lowers them, where the
# seam offers no kernel (every backend but the TPU): the decode step's jnp
# form is the same program instruction for instruction
OLMO_PARENT = {"prefill_16": "163a72a5cd7d33ee",
               "prefill_32": "b04af1423a6cf4c9",
               "decode": "ad5408fd14b19165"}


def test_without_the_kernel_the_programs_are_the_parents():
    import hashlib

    from deeplearning4j_tpu.generation.programs import GenerationPrograms

    net, _ = toy_net()
    progs = GenerationPrograms(net, slots=4, pages_per_slot=6, page_size=8,
                               num_pages=25, prefill_buckets=(16, 32))
    assert {name: tuple(p for k, p in paths if k == "recurrent")
            for (name, _), paths in progs.paths.items()} == {
        "decode": ("delta_step",), 16: ("delta_chunk",),
        32: ("delta_chunk",)}
    got = {name: hashlib.sha256(low.as_text().replace(
        f"@jit_{name} ", "@jit_prefill ").encode()).hexdigest()[:16]
           for name, low in progs.lowered().items()}
    assert got == OLMO_PARENT


@pytest.mark.parametrize("fault", ["token_altered", "state_not_reset",
                                   "padding_advances_state"])
def test_a_faulty_program_fails_the_same_comparison(fault):
    net, cfg = toy_net()
    undo = serve_linear_attention._plant_state_fault(fault)
    try:
        plant = ((lambda eng: serve._plant_token_altered(eng, 97))
                 if fault == "token_altered" else None)
        _, gaps = served_gaps(net, cfg, some_requests(), plant=plant)
    finally:
        for u in undo:
            u()
    assert gaps.mean() > 50 * TOL, (fault, gaps.mean())
    assert GatedDeltaNetLayer.apply_with_carry.__name__ == "apply_with_carry"

"""The Mamba-1 mixer (``MambaLayer``) and Jamba's hybrid net — Mamba layers
on state slots beside multi-query attention layers on pages — against the
plain reference ``benchmark/reference_jamba.py`` at a toy size on seeded
random weights: the layer alone, the three forms of its scan, attention at
one kv head under 20 query heads with no position term, the whole model
through ``net.output``, through ``models.decode.generate`` /
``rnn_time_step`` and through ``GenerationEngine`` (bucketed prefill, then
decode through slots and pages, requests of different lengths joining and
leaving), the faults and the control that must fail the same comparison, and
the operation count."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_jamba, model_jamba, reference_jamba as ref
from benchmark.jobs import serve, serve_state_space
from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.helpers import selective_scan as ss
from deeplearning4j_tpu.models.decode import generate
from deeplearning4j_tpu.nn.layers import MambaLayer, SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.base import layer_from_dict
from deeplearning4j_tpu.nn.layers.state_space import (
    STATE_SPACE_PATHS, state_space_path,
)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                       "jamba2-3b.json")) as f:
    PUBLISHED = json.load(f)
# Mamba, attention, Mamba, attention: both kinds twice, as the published
# period has them side by side
TOY = {**PUBLISHED, **dict(
    hidden_size=64, intermediate_size=160, num_attention_heads=4,
    num_key_value_heads=1, head_dim=16, mamba_dt_rank=4, mamba_d_state=8,
    num_hidden_layers=4, attn_layer_period=2, attn_layer_offset=1,
    vocab_size=97, torch_dtype="float32", initializer_range=0.2)}
SEED = 2**31 + 13
# float32 on both sides, the same mathematics in another order
TOL = 2e-4


def toy_net(**over):
    cfg = {**TOY, **over}
    net = model_jamba.build_network(cfg)
    return model_jamba.install_weights(net, cfg, SEED), cfg


def mixer_and_leaves(cfg=TOY, i=0):
    net, cfg = toy_net(**{k: v for k, v in cfg.items() if TOY.get(k) != v})
    layer = net.layers[1 + 2 * i].layers[1]
    return (layer, net.params[f"layer_{1 + 2 * i}"]["sub1"],
            ref.make_leaves(cfg, SEED, f"L{i}.", ref.layer_shapes(cfg, i)), cfg)


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


# --------------------------------------------------- (a) the layer alone
def test_the_mixer_equals_the_reference():
    layer, params, w, cfg = mixer_and_leaves()
    assert isinstance(layer, MambaLayer) and layer.d_inner == 128
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 64), jnp.float32)
    got, _ = layer.apply(params, {}, u)
    for b in range(2):
        want, _ = ref.mamba(u[b], w, cfg, "f32")
        assert float(jnp.max(jnp.abs(got[b] - want))) < 1e-5


@pytest.mark.parametrize("chunk", [1, 5, 16, 37])
def test_every_form_of_the_scan_is_the_token_by_token_step(chunk,
                                                           monkeypatch):
    """The chunked scan at several chunk lengths (one that does not divide
    the sequence, one that holds it whole), the built-in path with the
    helpers off, and ``step`` a token at a time: one output, one state."""
    layer, params, _, _ = mixer_and_leaves()
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 37, 64), jnp.float32)
    carry, outs = layer.initial_carry(2), []
    for t in range(37):
        y, carry = layer.step(params, carry, u[:, t])
        outs.append(y)
    want = jnp.stack(outs, axis=1)
    monkeypatch.setattr(ss, "SCAN_CHUNK", chunk)
    got, _, (h, tail) = layer.apply_with_carry(params, {}, u, None)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(h - carry[0]))) < 1e-5
    assert float(jnp.max(jnp.abs(tail - carry[1]))) == 0.0
    if chunk == 1:
        helpers.enable_helpers(False)
        try:
            assert layer.path(37) == "stepwise"
            off, _ = layer.apply(params, {}, u)
        finally:
            helpers.enable_helpers(True)
        assert float(jnp.max(jnp.abs(off - want))) < 1e-5
    # two chunks through the contiguous carry are the sequence whole
    a, _, mid = layer.apply_with_carry(params, {}, u[:, :20], None)
    b, _, _ = layer.apply_with_carry(params, {}, u[:, 20:], mid)
    assert float(jnp.max(jnp.abs(jnp.concatenate([a, b], 1) - want))) < 1e-5


def test_the_path_rule_is_pure():
    took = [state_space_path(t, seam) for t, seam in
            ((1, False), (1, True), (2, True), (512, False))]
    assert took == ["step", "step", "scan", "stepwise"]
    assert set(took) == set(STATE_SPACE_PATHS)
    layer, _, _, _ = mixer_and_leaves()
    assert (layer.path(1), layer.path(256)) == ("step", "scan")


def test_the_state_is_float32_under_a_bfloat16_net():
    layer, params, _, _ = mixer_and_leaves()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 64), jnp.bfloat16)
    y, _, (h, tail) = layer.apply_with_carry(params, {}, u, None)
    assert (y.dtype, h.dtype, tail.dtype) == (jnp.bfloat16, jnp.float32,
                                              jnp.bfloat16)
    pool = layer.init_paged_cache(9, 4, jnp.bfloat16, state_slots=5)
    assert pool["sh"].shape == (6, 8, 128) and pool["sh"].dtype == jnp.float32
    assert pool["sc"].shape == (6, 3, 128) and pool["sc"].dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="state_slots"):
        layer.init_paged_cache(9, 4)


def test_the_layer_round_trips_through_its_dict_and_the_builder():
    layer = MambaLayer(n_in=12, n_out=12, d_state=4, dt_rank=3,
                       inner_norms=False, conv_bias=False, name="m")
    again = layer_from_dict(layer.to_dict())
    assert again == layer and again.kind == "recurrent"
    p = layer.init(jax.random.PRNGKey(0))
    assert set(p) == {"W_in", "conv_W", "W_x", "W_dt", "b_dt", "A_log", "D",
                      "W_out"}
    # Mamba's own start: A = -(1..N), D = 1, steps in [1e-3, 1e-1]
    assert np.allclose(np.exp(np.asarray(p["A_log"]))[0], [1, 2, 3, 4])
    step = np.asarray(jax.nn.softplus(p["b_dt"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    net, _ = toy_net()
    conf = type(net.conf).from_json(net.conf.to_json())
    assert [type(l).__name__ for l in conf.layers] == [
        type(l).__name__ for l in net.layers]
    assert conf.layers[1].layers[1] == net.layers[1].layers[1]


# ------------------------------------- (b) attention at Jamba's shape
@pytest.mark.parametrize("heads", [4, 20])
def test_one_kv_head_and_no_position_term_equal_the_reference(heads):
    cfg = {**TOY, "num_attention_heads": heads, "head_dim": 8}
    net, cfg = toy_net(num_attention_heads=heads, head_dim=8)
    layer = net.layers[3].layers[1]
    assert isinstance(layer, SelfAttentionLayer)
    assert (layer.n_heads, layer._kv_heads, layer.rope, layer.bias) == (
        heads, 1, False, False)
    params = net.params["layer_3"]["sub1"]
    assert params["Wk"].shape == (64, 8) and set(params) == {
        "Wq", "Wk", "Wv", "Wo"}
    w = ref.make_leaves(cfg, SEED, "L1.", ref.layer_shapes(cfg, 1))
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64), jnp.float32)
    got, _ = layer.apply(params, {}, u)
    for b in range(2):
        want = ref.attention(u[b], w, cfg, "f32")
        assert float(jnp.max(jnp.abs(got[b] - want))) < 1e-5
    # no position term: the first token's output does not move with what
    # follows, and a permutation of the earlier tokens leaves the last alone
    perm = jnp.concatenate([u[:, 17::-1], u[:, 18:]], axis=1)
    moved, _ = layer.apply(params, {}, perm)
    assert float(jnp.max(jnp.abs(moved[:, -1] - got[:, -1]))) < 1e-5


# ------------------------------------------------- (c) the whole forward
def test_output_equals_the_reference_on_logits():
    net, cfg = toy_net()
    kinds = [type(l.layers[1]).__name__ for l in net.layers[1:9:2]]
    assert kinds == ["MambaLayer", "SelfAttentionLayer"] * 2
    ids = np.random.default_rng(0).integers(0, 97, (2, 29))
    got = np.log(np.asarray(net.output(ids), np.float64))
    w = ref.make_weights(cfg, SEED)
    want = np.stack([np.asarray(jax.nn.log_softmax(ref.forward(w, row, cfg)))
                     for row in ids])
    assert np.abs(got - want).max() < TOL
    # the head is the embedding: the second leaf holds its values
    assert np.array_equal(np.asarray(net.params["layer_10"]["W"]),
                          np.asarray(net.params["layer_0"]["W"]).T)


def test_the_published_layer_order_and_parameter_count():
    attn = [i for i in range(28) if ref.is_attention(PUBLISHED, i)]
    assert attn == [7, 21]
    assert flops_jamba.parameter_count(PUBLISHED) == 3_029_337_472
    shapes = ref.leaf_shapes(PUBLISHED)
    tied = int(np.prod(shapes["head.W"])) + 65536 + 2560   # and zero biases
    assert sum(int(np.prod(s)) for s in shapes.values()) - tied == \
        flops_jamba.parameter_count(PUBLISHED)


def test_generate_and_rnn_time_step_take_the_layer_as_they_take_an_lstm():
    net, cfg = toy_net()
    prompt = np.random.default_rng(2).integers(0, 97, (2, 11))
    toks = generate(net, prompt, 6, temperature=0.0)
    w = ref.make_weights(cfg, SEED)
    for b in range(2):
        seq = np.concatenate([prompt[b], toks[b]])
        rows = np.asarray(ref.forward(w, seq, cfg))[10:-1]
        assert (rows.max(axis=1) - rows[np.arange(6), toks[b]]).max() < TOL
    # the source library's rnnTimeStep: a chunk, then a token at a time
    net.rnn_clear_previous_state()
    first = net.rnn_time_step(prompt[:, :7])
    rest = [net.rnn_time_step(prompt[:, t]) for t in range(7, 11)]
    whole = np.asarray(net.output(prompt))
    assert np.abs(np.asarray(first) - whole[:, :7]).max() < 1e-5
    assert np.abs(np.stack(rest, 1) - whole[:, 7:]).max() < 1e-5


# ---------------------------------- (d) through the engine: slots and pages
@pytest.mark.parametrize("heads", [4, 20])
def test_engine_serves_the_toy_model_as_the_reference(heads):
    """Seven requests of different lengths through three slots: every slot
    is reused, requests join and leave a running batch, every bucket is
    taken; each served token is the reference's own at its position."""
    net, cfg = toy_net(num_attention_heads=heads, head_dim=64 // heads)
    eng, gaps = served_gaps(net, cfg, some_requests())
    assert gaps.max() < TOL, gaps
    reg, eid = eng.metrics.registry, eng.metrics.engine_id
    # counted at dispatch: every decode step dispatched, one ahead or not
    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="decode", path="step") == dispatched
    assert dispatched >= reg.get_value("dl4j_decode_steps_total") > 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="prefill", path="scan") == 7
    assert reg.get_value("dl4j_state_slot_resets_total", engine=eid) == 7
    assert reg.get_value("dl4j_state_slots_in_use", engine=eid) == 0
    assert reg.get_value("dl4j_layer_path_steps_total", kind="recurrent",
                         stage="decode", path="stepwise") is None


@pytest.mark.parametrize("fault", ["token_altered", "state_not_reset",
                                   "padding_advances_state"])
def test_a_faulty_program_fails_the_same_comparison(fault):
    net, cfg = toy_net()
    undo = serve_state_space._plant_state_fault(fault)
    try:
        plant = ((lambda eng: serve._plant_token_altered(eng, 97))
                 if fault == "token_altered" else None)
        _, gaps = served_gaps(net, cfg, some_requests(), plant=plant)
    finally:
        for u in undo:
            u()
    assert gaps.mean() > 50 * TOL, (fault, gaps.mean())
    assert MambaLayer.apply_with_carry.__name__ == "apply_with_carry"


def test_the_lower_precision_control_is_told_apart():
    """The comparison the cell's ``correct`` makes, at toy size: the token
    the reference puts first at fp8 lies further under the float32
    reference's best than the program's and bfloat16's do."""
    net, cfg = toy_net()
    requests = some_requests(4)
    _, gaps = served_gaps(net, cfg, requests)
    w = ref.make_weights(cfg, SEED)
    means = {}
    for precision in ("bf16", "fp8"):
        out = []
        for prompt, n in requests:
            seq = np.asarray(prompt + [0] * n)
            rows = np.asarray(ref.forward(w, seq, cfg))[len(prompt) - 1:-1]
            low = np.asarray(ref.forward(w, seq, cfg, precision))
            pick = low[len(prompt) - 1:-1].argmax(axis=1)
            out.append(rows.max(axis=1) - rows[np.arange(n), pick])
        means[precision] = np.concatenate(out).mean()
    assert gaps.mean() <= means["bf16"] + TOL < means["fp8"]
    assert means["fp8"] > 10 * max(means["bf16"], TOL)


# -------------------- (e) the accepted configurations' programs, unchanged
# Xing's toy programs as the parent commit c0c1861 lowers them (tests/
# test_laguna.py's pattern, the streamed experts' kernel withheld from the
# seam and the parent's module name put back on a prefill, as there); the
# older three are pinned in tests/test_laguna.py and tests/test_xing.py and
# checked again here, so all of the benchmark's older nets stand beside the
# one this file adds: state slots changed nothing in a program without them
XING_PARENT = {"prefill_16": "018b4ebe0b9e82b4",
               "prefill_32": "4fd7393933e6e582",
               "decode": "fb808e0a0d9609d1"}


@pytest.mark.parametrize("family", ["starcoder2", "kimi", "laguna", "xing"])
def test_older_nets_lower_to_the_programs_of_the_parent(family, monkeypatch):
    import hashlib

    from deeplearning4j_tpu.generation.programs import GenerationPrograms
    from tests import test_laguna, test_xing

    if family != "xing":
        test_xing.test_older_nets_lower_to_the_programs_of_the_parent(
            family, monkeypatch)
        return
    monkeypatch.setattr(test_laguna.GroupedExpertsHelper, "supports",
                        lambda self, *widths: False)
    net, _ = test_xing.toy_net()
    progs = GenerationPrograms(net, slots=4, pages_per_slot=6, page_size=8,
                               num_pages=25, prefill_buckets=(16, 32))
    assert not progs.state and all(
        k != "recurrent" for paths in progs.paths.values() for k, _ in paths)
    got = {name: hashlib.sha256(low.as_text().replace(
        f"@jit_{name} ", "@jit_prefill ").encode()).hexdigest()[:16]
           for name, low in progs.lowered().items()}
    assert got == XING_PARENT

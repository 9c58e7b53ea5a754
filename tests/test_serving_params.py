"""The serving snapshot of a version's parameters
(``GenerationPrograms.serving_params``): cast to the compute dtype once,
passed to every ``prefill_<bucket>`` and ``decode`` execution, never stale,
owned by the version's programs.  Every case runs for both facades."""

import gc
import re
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.generation import GenerationEngine
from deeplearning4j_tpu.generation.programs import (
    GenerationPrograms, _attach,
)
from deeplearning4j_tpu.models import graph as graph_mod
from deeplearning4j_tpu.models import sequential as sequential_mod
from deeplearning4j_tpu.models.decode import generate
from deeplearning4j_tpu.models.zoo import transformer_char_lm
from deeplearning4j_tpu.observability.metrics import MetricsRegistry

pytestmark = pytest.mark.generation

VOCAB = 29
FACADES = ("mln", "cg")
CASTS = "dl4j_decode_param_casts_total"


def mln_lm(seed, compute_dtype):
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=seed,
                               compute_dtype=compute_dtype)


def cg_lm(seed, compute_dtype):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, LayerNorm, RnnOutputLayer, SelfAttentionLayer,
    )

    g = (NeuralNetConfiguration.builder().seed(seed)
         .updater("sgd", learning_rate=0.1).graph())
    if compute_dtype:
        g.compute_dtype(compute_dtype)
    conf = (g.add_inputs("ids")
            .add_layer("emb", EmbeddingLayer(n_in=VOCAB, n_out=16,
                                             collapse_column=False), "ids")
            .add_layer("attn", SelfAttentionLayer(n_in=16, n_out=16,
                                                  n_heads=2, causal=True,
                                                  max_cache=128), "emb")
            .add_layer("ln", LayerNorm(n_in=16), "attn")
            .add_layer("out", RnnOutputLayer(n_in=16, n_out=VOCAB,
                                             loss="mcxent",
                                             activation="softmax"), "ln")
            .set_outputs("out").build())
    return graph_mod.ComputationGraph(conf).init()


def lm(facade, seed=7, compute_dtype="bfloat16"):
    return (mln_lm if facade == "mln" else cg_lm)(seed, compute_dtype)


def programs(net):
    return GenerationPrograms(net, slots=2, pages_per_slot=16, page_size=4,
                              num_pages=33, prefill_buckets=(8,))


def engine(net, **kw):
    return GenerationEngine(net, slots=2, page_size=4, max_context=64,
                            prefill_buckets=(8,),
                            registry=MetricsRegistry(), **kw)


def casts(eng):
    return eng.metrics.registry.get_value(CASTS, model="default") or 0.0


def scan_tokens(net, prompt, n):
    return generate(net, np.asarray(prompt)[None], n,
                    temperature=0.0)[0].tolist()


def main_of(lowered):
    """(argument types, body) of a lowered program's ``@main``."""
    text = lowered.as_text()
    head = re.search(r"func\.func public @main\((.*?)\) -> ", text, re.S)
    body = text[head.end():]
    nxt = body.find("func.func ")
    return (re.findall(r"%arg\d+: tensor<([^>]*)>", head.group(1)),
            body if nxt < 0 else body[:nxt])


# ---------------------------------------------- (a) what the programs take
@pytest.mark.parametrize("facade", FACADES)
@pytest.mark.parametrize("program", ("decode", "prefill_8"))
def test_bf16_model_programs_take_bf16_parameters(facade, program):
    net = lm(facade)
    n = len(jax.tree_util.tree_leaves(net.params))
    types, body = main_of(programs(net).lowered()[program])
    assert all(t.endswith("bf16") for t in types[:n]), types[:n]
    converted = {int(k) for k in
                 re.findall(r"stablehlo\.convert %arg(\d+)\b", body)}
    assert not converted & set(range(n)), sorted(converted)


@pytest.mark.parametrize("facade", FACADES)
def test_f32_tree_would_be_cast_inside_the_program(facade):
    """The control of the test above: fed the f32 tree, as before the
    snapshot, the same program converts every parameter it takes."""
    net = lm(facade)
    progs = programs(net)
    jitted, tail = progs._compute_programs()["decode"]
    lowered = jitted.lower(net.params, net.net_state,
                           jax.eval_shape(progs.fresh_pools), *tail)
    n = len(jax.tree_util.tree_leaves(net.params))
    types, body = main_of(lowered)
    assert all(t.endswith("f32") for t in types[:n])
    converted = {int(k) for k in
                 re.findall(r"stablehlo\.convert %arg(\d+)\b", body)}
    assert converted >= set(range(n))


# ------------------------------------------------ (b) no copy where no cast
@pytest.mark.parametrize("facade", FACADES)
def test_without_compute_dtype_the_snapshot_is_the_nets_own_tree(facade):
    net = lm(facade, compute_dtype=None)
    progs = programs(net)
    snap = progs.serving_params()
    own = jax.tree_util.tree_leaves(net.params)
    assert all(s is p for s, p in
               zip(jax.tree_util.tree_leaves(snap), own))
    assert progs.serving_params() is snap


@pytest.mark.parametrize("facade", FACADES)
def test_a_leaf_already_in_the_compute_dtype_is_not_copied(facade):
    net = lm(facade)
    name = next(iter(net.params))
    net.params[name] = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), net.params[name])
    snap = programs(net).serving_params()
    for s, p in zip(jax.tree_util.tree_leaves(snap[name]),
                    jax.tree_util.tree_leaves(net.params[name])):
        assert s is p
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(snap))


@pytest.mark.parametrize("compute_dtype,share", (("bfloat16", 0.5),
                                                 (None, 0.0)))
def test_warmup_ledger_lists_what_the_snapshot_costs(compute_dtype, share):
    from deeplearning4j_tpu.observability import shardstats

    programs(lm("mln", compute_dtype=compute_dtype)).warm()
    trees = shardstats.latest_ledgers()["generation"]["trees"]
    assert (trees["serving_params"]["logical_bytes"]
            == share * trees["params"]["logical_bytes"])


# ----------------------------------------------------- (c) the same numbers
@pytest.mark.parametrize("facade", FACADES)
def test_logits_from_the_snapshot_equal_those_from_the_f32_tree(facade):
    net = lm(facade)
    progs = programs(net)
    pools = _attach(progs.fresh_pools(),
                    np.arange(1, 33, dtype=np.int32).reshape(2, 16),
                    np.zeros(2, np.int32))
    x = progs._encode(jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]] * 2, jnp.int32))
    fwd = jax.jit(progs._fwd)
    snap, _ = fwd(progs.serving_params(), net.net_state, x, pools)
    f32, _ = fwd(net.params, net.net_state, x, pools)
    snap, f32 = (np.asarray(a, np.float32) for a in (snap, f32))
    ulp = 2.0 ** -8 * np.abs(f32).max()      # one bf16 ulp of the scale
    assert np.abs(snap - f32).max() <= ulp


@pytest.mark.parametrize("facade", FACADES)
def test_greedy_tokens_equal_the_compiled_scan(facade):
    net = lm(facade)
    eng = engine(net).start()
    try:
        prompt = [3, 1, 4, 1, 5]
        assert (eng.generate(prompt, 12).tolist()
                == scan_tokens(net, prompt, 12))
    finally:
        eng.stop()


# ---------------------------------------------------------- (d) never stale
@pytest.mark.parametrize("facade", FACADES)
def test_rebound_params_are_served_at_the_next_step(facade):
    net, other = lm(facade, seed=7), lm(facade, seed=8)
    eng = engine(net).start()
    try:
        prompt = [3, 1, 4, 1, 5]
        before = eng.generate(prompt, 8).tolist()
        n = casts(eng)
        net.params = other.params          # what fit / set_params_vector do
        after = eng.generate(prompt, 8).tolist()
        assert after == scan_tokens(other, prompt, 8) != before
        assert casts(eng) == n + 1
    finally:
        eng.stop()


@pytest.mark.parametrize("facade", FACADES)
def test_a_subtree_replaced_in_place_is_served_at_the_next_step(facade):
    net, other = lm(facade, seed=7), lm(facade, seed=8)
    eng = engine(net).start()
    try:
        prompt = [3, 1, 4, 1, 5]
        before = eng.generate(prompt, 8).tolist()
        n = casts(eng)
        for name in net.params:            # what the pretrain loops do
            net.params[name] = other.params[name]
            break
        after = eng.generate(prompt, 8).tolist()
        assert after == scan_tokens(net, prompt, 8) != before
        assert casts(eng) == n + 1
    finally:
        eng.stop()


@pytest.mark.parametrize("facade", FACADES)
def test_an_unchanged_tree_is_cast_once(facade):
    eng = engine(lm(facade)).start()
    try:
        assert casts(eng) == 1
        reg = eng.metrics.registry
        steps = reg.get_value("dl4j_decode_steps_total") or 0.0
        eng.generate([3, 1, 4], 55)
        assert reg.get_value("dl4j_decode_steps_total") - steps >= 50
        assert casts(eng) == 1
    finally:
        eng.stop()


# -------------------------------------------------- (e) owned by the version
@pytest.mark.parametrize("facade", FACADES)
def test_deploy_casts_once_and_retire_drops_the_snapshot(facade):
    eng = engine(lm(facade, seed=7)).start()
    try:
        v1 = eng.models.active("default")
        leaf = weakref.ref(jax.tree_util.tree_leaves(
            eng._programs[v1.key].serving_params())[0])
        eng.deploy("default", lm(facade, seed=8), retain_old=True)
        assert casts(eng) == 2
        gc.collect()
        assert leaf() is not None          # retained: keeps its snapshot
        eng.commit_swap()                  # -> _retire(v1)
        assert v1.key not in eng._programs
        gc.collect()
        assert leaf() is None
        eng.generate([3, 1, 4], 4)
        assert casts(eng) == 2
    finally:
        eng.stop()


@pytest.mark.parametrize("facade", FACADES)
def test_clearing_the_programs_frees_the_snapshot(facade):
    eng = engine(lm(facade)).start()
    eng.stop()
    leaf = weakref.ref(jax.tree_util.tree_leaves(
        next(iter(eng._programs.values())).serving_params())[0])
    assert leaf() is not None
    eng._programs.clear()
    gc.collect()
    assert leaf() is None


# ------------------------------------- (g) the shared rule, the same program
def _old_rule(scoped):
    """The cast as both ``_forward``s spelt it out before it moved to
    ``models/common.py`` (the graph's had no scope)."""
    import contextlib

    def rule(tree, cd):
        params, x = tree
        dt = jnp.dtype(cd)

        def _cast(a):
            return (a.astype(dt)
                    if hasattr(a, "dtype")
                    and jnp.issubdtype(a.dtype, jnp.floating) else a)

        with (jax.named_scope("param_cast") if scoped
              else contextlib.nullcontext()):
            params = jax.tree_util.tree_map(_cast, params)
            x = ([_cast(v) for v in x] if isinstance(x, list)
                 else _cast(x))
        return params, x
    return rule


def _train_step_text(facade):
    net = lm(facade)
    x = np.zeros((2, 8), np.int32)
    y = np.zeros((2, 8, VOCAB), np.float32)
    net.fit(x, y)           # builds the updater state the step takes
    step = net._get_train_step()
    if facade == "mln":
        args = (jnp.asarray(0, jnp.float32), jnp.asarray(x), jnp.asarray(y),
                jax.random.PRNGKey(0), None, None, None)
    else:
        args = (jnp.asarray(0, jnp.float32), {"ids": jnp.asarray(x)},
                {"out": jnp.asarray(y)}, jax.random.PRNGKey(0), None, None,
                None)
    return step.lower(net.params, net.updater_state, net.net_state,
                      *args).as_text()


@pytest.mark.parametrize("facade", FACADES)
def test_fit_step_lowers_to_the_text_of_the_inline_cast(facade, monkeypatch):
    now = _train_step_text(facade)
    assert "bf16" in now
    mod = sequential_mod if facade == "mln" else graph_mod
    monkeypatch.setattr(mod, "cast_to_compute", _old_rule(facade == "mln"))
    assert _train_step_text(facade) == now

"""Latent attention (MLA), the routed expert layer and its share, RMS norm
and the gated MLP, against the plain reference ``benchmark/reference_k2.py``
at a toy size on seeded random weights: the layers alone, and the whole
model through ``GenerationEngine`` (paged latent cache, bucketed prefill,
absorbed decode, counters)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model_k2, reference_k2 as ref
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.generation.programs import GenerationPrograms
from deeplearning4j_tpu.nn.layers import GatedMLP, RMSNorm
from deeplearning4j_tpu.nn.layers.moe import counting
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from tests.test_paged_kernel import _gather

# original_max_position_embeddings 16: the sequences below run past it, so
# YaRN's blended frequencies and its softmax temperature are in every test
TOY = dict(
    hidden_size=64, intermediate_size=160, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=12, rms_norm_eps=1e-5, rope_theta=50000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    first_k_dense_replace=1, num_hidden_layers=3, n_routed_experts=4,
    first_expert_held=4, published=dict(n_routed_experts=16),
    num_experts_per_tok=3, n_shared_experts=1, moe_intermediate_size=24,
    norm_topk_prob=True, routed_scaling_factor=2.827, scoring_func="sigmoid",
    n_group=1, topk_group=1, hidden_act="silu", attention_bias=False,
    vocab_size=97, torch_dtype="float32", initializer_range=0.2)
SEED = 2**31 + 11


def _kv_lm():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    return transformer_char_lm(vocab_size=29, d_model=32, n_heads=4,
                               layers=2, max_cache=64, seed=3)


def toy_net(**over):
    cfg = {**TOY, **over}
    net = model_k2.build_network(cfg)
    return model_k2.install_weights(net, cfg, SEED), cfg


def attention_layer(cfg):
    net = model_k2.build_network(cfg)
    return net.layers[1].layers[1]


def layer_leaves(cfg, i, names, dtype=jnp.float32):
    """Layer ``i``'s reference leaves and the same under the program's
    names (``names``: program name -> reference name)."""
    w = ref.make_leaves(cfg, SEED, f"L{i}.", ref.layer_shapes(cfg, i))
    return w, {k: w[v].astype(dtype) for k, v in names.items()}


# ------------------------------------------------ (a) latent attention
@pytest.mark.parametrize("dtype,tol,relative", [
    # float32: the two paths are the same mathematics in another order
    (jnp.float32, 1e-4, False),
    # bfloat16: operands, q_lat, the cached latent rows and the softmax
    # weights are each rounded to 8 bits of mantissa (2^-8 relative) on
    # another route than the expanded one; about eight such roundings in a
    # row bound the worst of 40 x 64 outputs by 3% of the largest output
    (jnp.bfloat16, 0.03, True)])
def test_absorbed_decode_over_latent_pages_equals_the_expanded_reference(
        dtype, tol, relative):
    cfg = dict(TOY)
    layer = attention_layer(cfg)
    w, params = layer_leaves(cfg, 0, model_k2._ATTN, dtype)
    t_all, real, bucket, ps = 40, 11, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (t_all, cfg["hidden_size"]))
    x = x.astype(dtype).astype(jnp.float32)
    want = np.asarray(ref.mla(x, w, cfg, "f32"))

    pool = layer.init_paged_cache(8, ps, dtype)
    assert set(pool) == {"pc"} and pool["pc"].shape == (8, ps, 128)   # 40 in lanes
    block = jnp.asarray([[3, 1, 5, 2, 6]], jnp.int32)       # 5 pages: 40
    chunk = jnp.zeros((1, bucket, x.shape[1]), dtype).at[0, :real].set(
        x[:real].astype(dtype))
    step = jax.jit(lambda xx, carry: layer.apply_with_carry(
        params, {}, xx, carry)[::2])
    y, carry = step(chunk, {**pool, "block": block,
                            "pos": jnp.zeros((1,), jnp.int32)})
    got = [np.asarray(y[0, :real], np.float32)]
    for t in range(real, t_all):      # padding rows are overwritten here
        y, carry = step(x[None, t:t + 1].astype(dtype),
                        {"pc": carry["pc"], "block": block,
                         "pos": jnp.asarray([t], jnp.int32)})
        got.append(np.asarray(y[0], np.float32))
    if relative:
        tol *= np.abs(want).max()
    assert np.abs(np.concatenate(got) - want).max() < tol


def test_a_chunk_behind_a_shared_prefix_takes_the_absorbed_path():
    """A paged chunk that does not start at 0 (prefix cache) attends to
    the pages before it: equal to the reference's rows of the whole."""
    cfg = dict(TOY)
    layer = attention_layer(cfg)
    w, params = layer_leaves(cfg, 0, model_k2._ATTN)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, cfg["hidden_size"]))
    want = np.asarray(ref.mla(x, w, cfg, "f32"))
    block = jnp.asarray([[2, 4, 1, 3]], jnp.int32)
    carry = {**layer.init_paged_cache(6, 8), "block": block,
             "pos": jnp.zeros((1,), jnp.int32)}
    y0, _, carry = layer.apply_with_carry(params, {}, x[None, :16], carry)
    y1, _, _ = layer.apply_with_carry(
        params, {}, x[None, 16:], {"pc": carry["pc"], "block": block,
                                   "pos": jnp.asarray([16], jnp.int32)})
    got = np.concatenate([np.asarray(y0[0]), np.asarray(y1[0])])
    assert np.abs(got - want).max() < 1e-4


# ------------------------------------------------- (b) the expert layer
def moe_layer(cfg):
    return model_k2.build_network(cfg).layers[4].layers[1]


def routed_to(cfg, experts):
    """A selection bias that sends every token to ``experts``."""
    b = np.zeros(ref.router_width(cfg), np.float32)
    b[list(experts)] = 10.0
    return jnp.asarray(b)


@pytest.mark.parametrize("case", ["uniform", "all_on_one_held", "none_held",
                                  "all_held_many_blocks"])
def test_expert_layer_equals_the_reference(case):
    cfg = dict(TOY)
    if case == "all_held_many_blocks":
        # 4 of 64 held: a block is a quarter of the assignments, and every
        # assignment falls on a held expert, so four blocks run
        cfg["published"] = dict(n_routed_experts=64)
    layer = moe_layer(cfg)
    w, params = layer_leaves(cfg, 1, model_k2._MOE)
    if case == "all_held_many_blocks":
        w["router.b"] = params["b_router"] = routed_to(cfg, (4, 6, 7))
    elif case == "all_on_one_held":        # held: 4..7
        w["router.b"] = params["b_router"] = routed_to(cfg, (5, 0, 9))
    elif case == "none_held":
        w["router.b"] = params["b_router"] = routed_to(cfg, (1, 2, 12))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 19, cfg["hidden_size"]))
    want = np.stack([np.asarray(ref.moe(r, w, cfg, "f32")) for r in x])
    got, _ = jax.jit(lambda p, xx: layer.apply(p, {}, xx))(params, x)
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    ids, _ = layer.route(params, x.reshape(-1, x.shape[-1]))
    held = np.isin(np.asarray(ids), np.arange(4, 8)).sum(axis=1)
    if case == "all_held_many_blocks":
        assert (held == 3).all()
    elif case == "all_on_one_held":
        assert (held == 1).all()
    elif case == "none_held":
        assert (held == 0).all()
        shared = ref.swiglu(x, w["shared.w_gate"], w["shared.w_up"],
                            w["shared.w_down"], "f32")
        assert np.abs(np.asarray(got) - np.asarray(shared)).max() < 1e-5
    else:
        assert 0 < held.sum() < held.size * 3


def test_router_is_float32_whatever_the_compute_dtype():
    cfg = dict(TOY)
    layer = moe_layer(cfg)
    w, params = layer_leaves(cfg, 1, model_k2._MOE, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(6), (33, cfg["hidden_size"]),
                          jnp.bfloat16)
    ids, weights = layer.route(params, x)
    assert weights.dtype == jnp.float32
    rounded = {k: a.astype(jnp.bfloat16).astype(jnp.float32)
               for k, a in w.items() if k.startswith("router.")}
    want_ids, want_w = ref.route(x.astype(jnp.float32), rounded, cfg)
    assert (np.sort(np.asarray(ids)) == np.sort(np.asarray(want_ids))).all()
    assert np.abs(np.sort(np.asarray(weights))
                  - np.sort(np.asarray(want_w))).max() < 1e-5


# ------------------------------------- (c) the shares sum to the whole
def test_all_shares_of_one_expert_layer_sum_to_the_uncut_reference():
    n, held = 16, 4
    whole = {**TOY, "n_routed_experts": n, "first_expert_held": 0}
    w = ref.make_leaves(whole, SEED, "L1.", ref.layer_shapes(whole, 1))
    x = jax.random.normal(jax.random.PRNGKey(7), (23, TOY["hidden_size"]))
    want = np.asarray(ref.moe(x, w, whole, "f32"))
    shared = np.asarray(ref.swiglu(x, w["shared.w_gate"], w["shared.w_up"],
                                   w["shared.w_down"], "f32"))
    total = np.zeros_like(want)
    for first in range(0, n, held):
        cfg = {**TOY, "first_expert_held": first}
        layer = moe_layer(cfg)
        assert layer.experts_held == (first, held) and layer.n_experts == n
        params = {k: w[v] for k, v in model_k2._MOE.items()}
        for k in ("W_gate", "W_up", "W_down"):
            params[k] = params[k][first:first + held]
        y, _ = layer.apply(params, {}, x)
        total += np.asarray(y) - shared
        share = {**w, **{k: w[k][first:first + held] for k in
                         ("experts.w_gate", "experts.w_up", "experts.w_down")}}
        assert np.abs(np.asarray(y)
                      - np.asarray(ref.moe(x, share, cfg, "f32"))).max() < 1e-4
    assert np.abs(total + shared - want).max() < 1e-4


# ------------------------------------------------ the small new layers
def test_rms_norm_and_gated_mlp_equal_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, 32))
    g = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (32,))
    y, _ = RMSNorm(n_in=32, eps=1e-5).apply({"gamma": g}, {}, x)
    assert np.abs(np.asarray(y) - np.asarray(ref.rms_norm(x, g, 1e-5))
                  ).max() < 1e-6
    mlp = GatedMLP(n_in=32, n_out=32, hidden=48)
    p = mlp.init(jax.random.PRNGKey(10))
    assert set(p) == {"W_gate", "W_up", "W_down"}       # no bias anywhere
    y, _ = mlp.apply(p, {}, x)
    want = ref.swiglu(x, p["W_gate"], p["W_up"], p["W_down"], "f32")
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5


def test_new_layers_round_trip_through_the_config_json():
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

    net, _ = toy_net()
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.layers == net.conf.layers
    assert again.layers[4].layers[1].experts_held == (4, 4)


# ---------------------------------------- (d), (f) through the engine
def run_engine(net, requests, **kw):
    # a registry of its own: the counters below are read as totals, and the
    # process-wide one holds what other files' engines counted before
    kw.setdefault("registry", MetricsRegistry())
    eng = GenerationEngine(net, slots=4, page_size=8, max_context=48,
                           prefill_buckets=(16, 32), **kw).start()
    try:
        handles = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        return eng, [np.asarray(h.result(), np.int32) for h in handles]
    finally:
        eng.stop()


def reference_counts(cfg, seq):
    """[tokens routed, assignments per held expert] of one sequence over
    the reference's expert layers."""
    x = ref.make_leaf(cfg, SEED, "emb.W", (cfg["vocab_size"],
                                           cfg["hidden_size"]))[seq]
    first, held = cfg["first_expert_held"], cfg["n_routed_experts"]
    counts = np.zeros(1 + held, np.int64)
    for i in range(cfg["num_hidden_layers"]):
        w = ref.make_leaves(cfg, SEED, f"L{i}.", ref.layer_shapes(cfg, i))
        if not ref.is_dense(cfg, i):
            eps = cfg["rms_norm_eps"]
            xa = x + ref.mla(ref.rms_norm(x, w["in_norm.g"], eps), w, cfg,
                             "f32")
            ids, _ = ref.route(ref.rms_norm(xa, w["post_norm.g"], eps), w,
                               cfg)
            counts[0] += len(seq)
            counts[1:] += np.bincount(
                np.asarray(ids).ravel() - first + held * 4,
                minlength=held * 9)[held * 4:held * 5]
        x = ref.block(x, w, cfg, "f32")
    return counts


def test_engine_serves_the_toy_model_as_the_reference_and_counts_it():
    net, cfg = toy_net()
    rng = np.random.default_rng(1)
    requests = [(rng.integers(0, 97, 21).tolist(), 9),
                (rng.integers(0, 97, 7).tolist(), 14)]
    eng, served = run_engine(net, requests)
    w = ref.make_weights(cfg, SEED)
    want = np.zeros(1 + cfg["n_routed_experts"], np.int64)
    for (prompt, n), toks in zip(requests, served):
        assert len(toks) == n
        seq = np.asarray(prompt + toks.tolist())
        logits = np.asarray(ref.forward(w, seq, cfg))
        rows = logits[len(prompt) - 1:len(seq) - 1]
        gap = rows.max(axis=1) - rows[np.arange(n), toks]
        assert gap.max() < 1e-4, gap
        want += reference_counts(cfg, seq[:-1])     # the last is never fed
    reg = eng.metrics.registry
    assert reg.get_value("dl4j_moe_tokens_total") == want[0]
    got = [reg.get_value("dl4j_moe_held_assignments_total", expert=str(e))
           or 0 for e in range(cfg["n_routed_experts"])]
    assert got == want[1:].tolist()
    assert eng._moe_pending is None             # flushed when the loop ended
    stages = eng.phases.as_dict()["stages"]
    assert "moe_counters" in stages["admit"]
    assert "moe_counters" in stages["decode"]
    assert "moe_counters" not in eng.phases.as_dict()["phases"]


# ------------------------------------- (d') the latent pages, read in place
def test_engine_serves_the_same_tokens_in_place_and_through_the_gather():
    """The toy model through the engine with the single-token attention
    over the pages where they lie (here the lax page loop; the kernel on a
    TPU) and with the paged helper withheld (``pool[block]`` +
    ``_absorbed``):
    the same greedy tokens, joins and leaves included."""
    rng = np.random.default_rng(2)
    requests = [(rng.integers(0, 97, n).tolist(), m)
                for n, m in ((21, 9), (7, 14), (30, 5), (12, 11), (3, 8))]

    def serve():
        eng, served = run_engine(toy_net()[0], requests)
        progs = next(iter(eng._programs.values()))
        return _latent(progs.paths[("decode", False)]), served

    path, in_place = serve()
    oracle_path, gathered = _gather(serve)
    assert path == ("paged",) and oracle_path == ("gathered",)
    for a, b in zip(in_place, gathered):
        assert a.tolist() == b.tolist()


def _latent(paths):
    """The latent layers' paths out of a program's (kind, path) pairs."""
    from deeplearning4j_tpu.nn.layers.latent_attention import LATENT_PATHS

    return tuple(p for k, p in paths if k == "attention" and p in LATENT_PATHS)


def test_the_engine_counts_each_dispatch_by_its_latent_path():
    """``dl4j_layer_path_steps_total`` of kind ``attention``: ``paged`` for
    every decode
    dispatch, ``expanded`` for a prompt prefilled whole, ``gathered`` for
    a suffix behind a shared prefix — what the host rule says, which is
    what the programs were traced to do."""
    net, _ = toy_net()
    eng = GenerationEngine(net, slots=4, page_size=8, max_context=48,
                           prefill_buckets=(16, 32), prefix_cache=True,
                           registry=MetricsRegistry()).start()
    try:
        rng = np.random.default_rng(3)
        first = rng.integers(0, 97, 21).tolist()
        served = [eng.submit(first, max_new_tokens=5).result(),
                  eng.submit(rng.integers(0, 97, 9).tolist(),
                             max_new_tokens=4).result()]
        again = eng.submit(first[:18] + [5, 6], max_new_tokens=3)
        served.append(again.result())
    finally:
        eng.stop()
    assert [len(t) for t in served] == [5, 4, 3] and again.shared_len == 16
    progs = next(iter(eng._programs.values()))
    assert {key: _latent(paths) for key, paths in progs.paths.items()} == {
        ("decode", False): ("paged",), ("decode", True): ("paged",),
        (16, True): ("expanded",), (16, False): ("gathered",),
        (32, True): ("expanded",), (32, False): ("gathered",)}
    reg = eng.metrics.registry

    def count(stage, path):
        return reg.get_value("dl4j_layer_path_steps_total", stage=stage,
                             kind="attention", path=path) or 0

    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert count("decode", "paged") == dispatched > 0
    assert count("decode", "gathered") == count("decode", "expanded") == 0
    assert count("prefill", "expanded") == 2
    assert count("prefill", "gathered") == 1
    assert count("prefill", "paged") == 0


@pytest.mark.parametrize("mode", ["fused", "gather"])
def test_the_traced_branch_is_the_one_the_host_rule_names(mode, monkeypatch):
    """What ``LatentAttentionLayer.path`` says on the host is what
    ``apply_with_carry`` traces: lowered for a TPU, a single-token call
    holds the kernel exactly when the rule says ``paged``, and a chunk
    never does."""
    from deeplearning4j_tpu.helpers import paged_attention as pa

    monkeypatch.setattr(pa, "default_impl", lambda: "pallas")
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    cfg = dict(TOY)
    layer = attention_layer(cfg)
    _, params = layer_leaves(cfg, 0, model_k2._ATTN, jnp.bfloat16)
    ps = 16
    pool = layer.init_paged_cache(9, ps, jnp.bfloat16)

    def lowered(t):
        carry = {**pool, "block": jnp.zeros((2, 4), jnp.int32),
                 "pos": jnp.zeros((2,), jnp.int32)}
        fn = jax.jit(lambda x, c: layer.apply_with_carry(params, {}, x, c))
        with jax.enable_x64(False):
            return fn.trace(jnp.zeros((2, t, cfg["hidden_size"]),
                                      jnp.bfloat16), carry).lower(
                lowering_platforms=("tpu",)).as_text()

    def run():
        return (layer.path(1, False, ps, jnp.bfloat16), lowered(1),
                lowered(16))

    said, decode, chunk = run() if mode == "fused" else _gather(run)
    assert said == {"fused": "paged", "gather": "gathered"}[mode]
    assert (decode.count('kernel_name = "latent_paged_attention"')
            == (said == "paged"))
    assert "latent_paged_attention" not in chunk


def test_programs_log_the_latent_tiling_once(monkeypatch, caplog):
    import logging

    from deeplearning4j_tpu.helpers import paged_attention as pa

    progs = GenerationPrograms(toy_net()[0], slots=4, pages_per_slot=6,
                               page_size=8, num_pages=25,
                               prefill_buckets=(16, 32))

    def lines():
        caplog.clear()
        with caplog.at_level(logging.INFO,
                             logger="deeplearning4j_tpu.generation"):
            progs._log_tiling()
        return [m for r in caplog.records
                if "latent_paged_attention" in (m := r.getMessage())]

    assert lines() == []                      # CPU: the lax page loop
    monkeypatch.setattr(pa, "default_impl", lambda: "pallas")
    said = lines()
    assert len(said) == 1 and said[0].startswith(
        "generation.decode: latent_paged_attention q [4, 1, 4, 128] over 6 "
        "pages of 8, the value the first 32 columns: 6 pages a block")
    kv = GenerationPrograms(_kv_lm(), slots=2, pages_per_slot=4,
                            page_size=4, num_pages=9, prefill_buckets=(8,))
    assert not any(_latent(paths) for paths in kv.paths.values())


def test_counting_leaves_out_padding_and_idle_rows():
    cfg = dict(TOY)
    layer = moe_layer(cfg)
    _, params = layer_leaves(cfg, 1, model_k2._MOE)
    x = jax.random.normal(jax.random.PRNGKey(12), (3, 6, cfg["hidden_size"]))
    valid = jnp.arange(6)[None] < jnp.asarray([[6], [2], [0]])
    with counting(lambda: valid) as sink:
        layer.apply(params, {}, x)
    ids, _ = layer.route(params, x.reshape(-1, x.shape[-1]))
    ids = np.asarray(ids).reshape(3, 6, -1)
    real = np.concatenate([ids[0].ravel(), ids[1, :2].ravel()])
    want = [8] + [(real == 4 + e).sum() for e in range(4)]
    assert np.asarray(sink[0]).tolist() == want
    with counting(lambda: valid) as sink:       # no expert layer: nothing
        RMSNorm(n_in=64).apply({"gamma": jnp.ones(64)}, {}, x)
    assert sink == []


def test_served_leaves_in_the_compute_dtype_are_the_nets_own_buffers():
    net, _ = toy_net(torch_dtype="bfloat16")
    assert net.conf.compute_dtype == "bfloat16"
    leaves = jax.tree_util.tree_leaves(net.params)
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    eng, served = run_engine(net, [([5, 6, 7, 8, 9], 4)])
    progs = next(iter(eng._programs.values()))
    snap = jax.tree_util.tree_leaves(progs.serving_params())
    assert all(a is b for a, b in zip(leaves, snap))
    assert not eng.metrics.registry.get_value(
        "dl4j_decode_param_casts_total", model="default")
    assert len(served[0]) == 4


def test_a_net_without_expert_layers_returns_what_it_did():
    progs = GenerationPrograms(_kv_lm(), slots=2, pages_per_slot=4, page_size=4,
                               num_pages=9, prefill_buckets=(8,))
    out = jax.eval_shape(lambda *a: progs._decode(*a),
                         progs.serving_params(), progs.net.net_state,
                         jax.eval_shape(progs.fresh_pools),
                         *progs._compute_programs()["decode"][1])
    assert isinstance(out[1], jax.ShapeDtypeStruct)         # ids alone


# ------------------------------------------- (e) one walker, both pools
def _pool_programs(kind):
    if kind == "latent":
        net, _ = toy_net()
    else:
        net = _kv_lm()
    return GenerationPrograms(net, slots=2, pages_per_slot=4, page_size=4,
                              num_pages=9, prefill_buckets=(8,))


@pytest.mark.parametrize("kind,keys", [("latent", {"pc"}),
                                       ("kv", {"pk", "pv"})])
def test_page_transport_round_trips_both_kinds_of_pool(kind, keys):
    progs = _pool_programs(kind)
    pools = progs.fresh_pools()
    flat = jax.tree_util.tree_leaves_with_path(pools)
    assert {p[-1].key for p, _ in flat} == keys
    rng = np.random.default_rng(2)
    pools = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), pools)
    payload = progs.read_page(pools, 3)
    per_page = sum(a.nbytes for a in jax.tree_util.tree_leaves(payload))
    assert progs.page_nbytes(pools) == per_page
    before = jax.tree_util.tree_map(np.asarray, pools)
    pools = progs.write_page(pools, 5, payload)
    for (_, old), new in zip(jax.tree_util.tree_leaves_with_path(before),
                             jax.tree_util.tree_leaves(pools)):
        new = np.asarray(new)
        assert (new[5] == old[3]).all()
        keep = [i for i in range(9) if i != 5]
        assert (new[keep] == old[keep]).all()

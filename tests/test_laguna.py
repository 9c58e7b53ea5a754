"""Window and full attention layers of different head counts over a paged
cache that keeps only the window for window layers, the per-head output
gate, softmax top-k routing and its share, against the plain reference
``benchmark/reference_laguna.py`` at a toy size on seeded random weights:
the layers alone, the page manager, and the whole model through
``GenerationEngine`` (pools by layer kind, bucketed prefill longer than the
ring, ring decode, gauges)."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import model_laguna, reference_laguna as ref
from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.generation.paged_cache import (
    PagedKVCache, PageExhaustedError,
)
from deeplearning4j_tpu.generation.programs import (
    GenerationPrograms, window_ring_pages,
)
from deeplearning4j_tpu.helpers import paged_attention as pa
from deeplearning4j_tpu.helpers.grouped_experts import GroupedExpertsHelper
from deeplearning4j_tpu.nn.layers import SelfAttentionLayer

# a period of 4 with different head counts (groups of 6 and 9 over 2 kv
# heads), dense layer 0, a window of 12 and original_max_position_embeddings
# 16: the sequences below run several windows long and past the toy
# original length, so the ring wraps and YaRN's blend is in every test
TOY = dict(
    model_type="laguna", hidden_size=64, intermediate_size=160, head_dim=16,
    num_key_value_heads=2, num_hidden_layers=4,
    num_attention_heads_per_layer=[12, 18, 18, 18],
    layer_types=["full_attention"] + ["sliding_attention"] * 3,
    mlp_only_layers=[0], sliding_window=12,
    rope_parameters=dict(
        full_attention=dict(rope_theta=500000, rope_type="yarn", factor=128,
                            original_max_position_embeddings=16, beta_slow=1,
                            beta_fast=32,
                            attention_factor=0.1 * math.log(128) + 1.0,
                            partial_rotary_factor=0.5),
        sliding_attention=dict(rope_type="default", rope_theta=10000,
                               partial_rotary_factor=1)),
    rms_norm_eps=1e-6, num_experts=4, first_expert_held=4,
    published=dict(num_experts=16), num_experts_per_tok=3,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    norm_topk_prob=True, moe_routed_scaling_factor=2.5, gating="per-head",
    attention_bias=False, tie_word_embeddings=False,
    moe_router_logit_softcapping=0, moe_apply_router_weight_on_input=False,
    vocab_size=97, torch_dtype="float32", initializer_range=0.2)
SEED = 2**31 + 11


def toy_net(**over):
    cfg = {**TOY, **over}
    net = model_laguna.build_network(cfg)
    return model_laguna.install_weights(net, cfg, SEED), cfg


def layer_leaves(cfg, i, names, dtype=jnp.float32):
    """Layer ``i``'s reference leaves and the same under the program's
    names (``names``: program name -> reference name)."""
    w = ref.make_leaves(cfg, SEED, f"L{i}.", ref.layer_shapes(cfg, i))
    return w, {k: w[v].astype(dtype) for k, v in names.items()}


@pytest.fixture
def paged_impl(request, monkeypatch):
    """Route the layers' paged attention through one implementation: the
    gather where the seam withholds the paged helper alone."""
    impl = request.param
    if impl == "gather":
        get = helpers.get_helper
        monkeypatch.setattr(helpers, "get_helper", lambda kind: (
            None if kind == "paged_attention" else get(kind)))
    else:
        monkeypatch.setattr(pa, "default_impl", lambda: impl)
    return impl


# -------------------------- (a) both layer kinds through their paged pools
@pytest.mark.parametrize("dtype,tol", [
    # float32: the same mathematics in another order
    (jnp.float32, 1e-4),
    # bfloat16: operands, cached K and V and the softmax weights are each
    # rounded to 8 bits of mantissa on another route than the reference's
    # (rounded inputs, float32 inside); about six such roundings in a row
    # bound the worst of 56 x 64 outputs by 3% of the largest output
    (jnp.bfloat16, 0.03)])
@pytest.mark.parametrize("paged_impl", ["lax", "gather", "pallas"],
                         indirect=True)
@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_paged_decode_after_a_bucketed_prefill_equals_the_reference(
        kind, paged_impl, dtype, tol):
    """Sliding (group of 9): a 32-token bucket holding 27 real tokens, twice
    the ring of 4 pages of 4, then token by token through the ring to 56,
    4.7 windows.  Full (group of 6): partial-rotary YaRN through absolute
    pages, 3.5 times the toy original length."""
    cfg = dict(TOY)
    i = 1 if kind == "sliding" else 0
    layer = model_laguna.attention_layer(cfg, i).setup(None)
    assert layer.n_heads // layer._kv_heads == (9 if kind == "sliding" else 6)
    w, params = layer_leaves(cfg, i, model_laguna._ATTN, dtype)
    t_all, real, bucket, ps = 56, 27, 32, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (t_all, cfg["hidden_size"]))
    x = x.astype(dtype).astype(jnp.float32)
    wr = {k: a.astype(dtype).astype(jnp.float32) for k, a in w.items()}
    want = np.asarray(ref.attention(x, wr, cfg, i, "f32"))

    if kind == "sliding":
        ring = layer.paged_ring(ps)
        assert ring == 4 and bucket > ring * ps
        pool = layer.init_paged_cache(99, ps, dtype, window_pages=6)
        assert set(pool) == {"wk", "wv"} and pool["wk"].shape == (6, 2, ps, 16)
        block = jnp.asarray([[3, 1, 5, 2]], jnp.int32)
        first = {"live": jnp.asarray([real], jnp.int32)}
    else:
        pool = layer.init_paged_cache(16, ps, dtype)
        assert set(pool) == {"pk", "pv"} and layer.paged_ring(ps) is None
        block = jnp.asarray([[3, 1, 5, 2, 6, 9, 4, 8, 7, 10, 12, 11, 14, 13]],
                            jnp.int32)
        first = {}
    chunk = jnp.zeros((1, bucket, x.shape[1]), dtype).at[0, :real].set(
        x[:real].astype(dtype))
    step = jax.jit(lambda xx, carry: layer.apply_with_carry(
        params, {}, xx, carry)[::2])
    y, carry = step(chunk, {**pool, "block": block, **first,
                            "pos": jnp.zeros((1,), jnp.int32)})
    got = [np.asarray(y[0, :real], np.float32)]
    for t in range(real, t_all):      # padding rows never reached the ring
        y, carry = step(x[None, t:t + 1].astype(dtype),
                        {**{k: carry[k] for k in pool}, "block": block,
                         "pos": jnp.asarray([t], jnp.int32)})
        got.append(np.asarray(y[0], np.float32))
    err = np.abs(np.concatenate(got) - want).max()
    assert err < tol * (np.abs(want).max() if dtype == jnp.bfloat16 else 1)


@pytest.mark.parametrize("impl", ["lax", "gather", "pallas"])
def test_ring_attention_masks_a_table_that_is_not_whole_blocks(impl):
    """9 pages of 64 make 5 blocks of 2 pages: the kernel's last block
    repeats page 8, whose second copy must count for nothing; a row that
    has not wrapped yet sees only what it has written."""
    b, hkv, g, d, ps, window, ring = 2, 2, 3, 32, 64, 512, 9
    rng = np.random.default_rng(0)
    qpos = np.asarray([[700], [130]], np.int32)
    ks = rng.normal(size=(b, 701, hkv, d)).astype(np.float32)
    vs = rng.normal(size=(b, 701, hkv, d)).astype(np.float32)
    tbl = 1 + np.arange(b * ring, dtype=np.int32).reshape(b, ring)
    pk = rng.normal(size=(1 + b * ring, hkv, ps, d)).astype(np.float32)
    pv = rng.normal(size=pk.shape).astype(np.float32)     # garbage unwritten
    for r in range(b):
        for p in range(max(0, qpos[r, 0] - ring * ps + 1), qpos[r, 0] + 1):
            pk[tbl[r, (p // ps) % ring], :, p % ps] = ks[r, p]
            pv[tbl[r, (p // ps) % ring], :, p % ps] = vs[r, p]
    q = rng.normal(size=(b, 1, hkv * g, d)).astype(np.float32)
    got = np.asarray(pa.paged_decode_attention(
        *map(jnp.asarray, (q, pk, pv, tbl, qpos)), window=window, impl=impl,
        interpret=True if impl == "pallas" else None))
    for r in range(b):
        lo = max(0, qpos[r, 0] - window + 1)
        for h in range(hkv * g):
            s = ks[r, lo:qpos[r, 0] + 1, h // g] @ q[r, 0, h] / math.sqrt(d)
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vs[r, lo:qpos[r, 0] + 1, h // g]
            assert np.abs(got[r, 0, h] - want).max() < 1e-4


def test_ring_kernel_lowers_for_tpu_at_the_serving_shape():
    """The decode call of a sliding layer at the published widths: 32 rows,
    72 heads over 8 kv heads of 128, a ring of 9 pages of 64."""
    b, hq, hkv, d, ps, ring = 32, 72, 8, 128, 64, 9
    sds = jax.ShapeDtypeStruct
    pool = sds((b * ring + 1, hkv, ps, d), jnp.bfloat16)
    fn = jax.jit(lambda *a: pa.paged_decode_attention(
        *a, window=512, impl="pallas", interpret=False))
    with jax.enable_x64(False):   # as on the chip (the TPU tier has no x64)
        text = fn.trace(sds((b, 1, hq, d), jnp.bfloat16), pool, pool,
                        sds((b, ring), jnp.int32),
                        sds((b, 1), jnp.int32)).lower(
                            lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "fused_paged_attention"') == 1


# ------------------------- (b) the gate and a head width of its own
def test_per_head_gate_and_free_head_width_equal_the_reference():
    cfg = dict(TOY)
    layer = model_laguna.attention_layer(cfg, 0).setup(None)
    assert layer._d_head == 16 != layer.n_out // layer.n_heads
    p = layer.init(jax.random.PRNGKey(1))
    assert set(p) == {"Wq", "Wk", "Wv", "Wo", "Wg"}       # no bias anywhere
    assert p["Wq"].shape == (64, 12 * 16) and p["Wo"].shape == (12 * 16, 64)
    assert p["Wk"].shape == (64, 2 * 16) and p["Wg"].shape == (64, 12)
    w, params = layer_leaves(cfg, 0, model_laguna._ATTN)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 23, 64))
    got, _ = layer.apply(params, {}, x)
    want = np.stack([np.asarray(ref.attention(r, w, cfg, 0, "f32"))
                     for r in x])
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    # the gate does something: shut, the layer gives nothing
    shut = {**params, "Wg": jnp.full_like(params["Wg"], -1e3)}
    got, _ = layer.apply(shut, {}, jnp.abs(x))
    assert np.abs(np.asarray(got)).max() < 1e-6


def test_defaults_keep_the_biased_block_and_its_leaves():
    layer = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, causal=True)
    p = layer.init(jax.random.PRNGKey(0))
    assert set(p) == {"Wq", "Wk", "Wv", "Wo", "bq", "bk", "bv", "bo"}
    assert layer._d_head == 8 and layer.paged_ring(8) is None
    with pytest.raises(ValueError, match="window_pages"):
        SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, causal=True,
                           window=8).init_paged_cache(4, 8)
    with pytest.raises(ValueError, match="gate"):
        SelfAttentionLayer(n_in=32, n_out=32, gate="per_token").validate()


# ----------------------------------------- (c) softmax routing, the share
def moe_layer(cfg):
    return model_laguna.build_network(cfg).layers[4].layers[1]


@pytest.mark.parametrize("case", ["uniform", "all_on_one_held", "none_held"])
def test_softmax_routing_equals_the_reference(case):
    cfg = dict(TOY)
    layer = moe_layer(cfg)
    assert layer.scoring == "softmax" and layer.experts_held == (4, 4)
    w, params = layer_leaves(cfg, 1, model_laguna._MOE)
    assert "b_router" not in layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 19, cfg["hidden_size"]))
    if case != "uniform":
        # there is no selection bias to steer by: columns of the router
        # that every token scores highest (x has a constant first feature)
        x = x.at[..., 0].set(8.0)
        chosen = (5, 0, 9) if case == "all_on_one_held" else (1, 2, 12)
        rw = np.asarray(w["router.W"]).copy()
        rw[0, list(chosen)] += 4.0
        w["router.W"] = params["W_router"] = jnp.asarray(rw)
    want = np.stack([np.asarray(ref.moe(r, w, cfg, "f32")) for r in x])
    got, _ = jax.jit(lambda p, xx: layer.apply(p, {}, xx))(params, x)
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    ids, weights = layer.route(params, x.reshape(-1, x.shape[-1]))
    assert np.allclose(np.asarray(weights).sum(axis=1), 2.5, atol=1e-5)
    held = np.isin(np.asarray(ids), np.arange(4, 8)).sum(axis=1)
    if case == "all_on_one_held":
        assert (held == 1).all()
    elif case == "none_held":
        assert (held == 0).all()
        shared = ref.swiglu(x, w["shared.w_gate"], w["shared.w_up"],
                            w["shared.w_down"], "f32")
        assert np.abs(np.asarray(got) - np.asarray(shared)).max() < 1e-5
    else:
        assert 0 < held.sum() < held.size * 3


def test_all_shares_of_one_expert_layer_sum_to_the_uncut_reference():
    """Guide section 4's test: 4 shares of 4 experts, the shared expert
    counted once, add up to the whole layer."""
    n, held = 16, 4
    whole = {**TOY, "num_experts": n, "first_expert_held": 0}
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
        params = {k: w[v] for k, v in model_laguna._MOE.items()}
        for k in ("W_gate", "W_up", "W_down"):
            params[k] = params[k][first:first + held]
        y, _ = layer.apply(params, {}, x)
        total += np.asarray(y) - shared
        share = {**w, **{k: w[k][first:first + held] for k in
                         ("experts.w_gate", "experts.w_up", "experts.w_down")}}
        assert np.abs(np.asarray(y)
                      - np.asarray(ref.moe(x, share, cfg, "f32"))).max() < 1e-4
    assert np.abs(total + shared - want).max() < 1e-4


def test_new_fields_round_trip_through_the_config_json():
    from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

    net, _ = toy_net()
    again = MultiLayerConfiguration.from_json(net.conf.to_json())
    assert again.layers == net.conf.layers
    attn = again.layers[3].layers[1]
    assert (attn.window, attn.head_dim, attn.gate, attn.bias) == (
        12, 16, "per_head", False)
    assert again.layers[1].layers[1].rotary_dim == 8
    assert again.layers[4].layers[1].scoring == "softmax"


# ------------------------------ (d), (f) the whole model through the engine
def run_engine(net, requests, **kw):
    eng = GenerationEngine(net, slots=4, page_size=4, max_context=48,
                           prefill_buckets=(16, 32), **kw).start()
    try:
        handles = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        return eng, [np.asarray(h.result(), np.int32) for h in handles]
    finally:
        eng.stop()


def test_engine_serves_the_toy_model_as_the_reference():
    """Short and long requests interleaved over 4 slots, contexts up to 4
    windows long, a 27-token prompt in a 32 bucket against a ring of 16."""
    net, cfg = toy_net()
    rng = np.random.default_rng(1)
    requests = [(rng.integers(0, 97, n).tolist(), m) for n, m in
                ((27, 15), (5, 30), (18, 20), (9, 8), (31, 17), (2, 40))]
    eng, served = run_engine(net, requests)
    w = ref.make_weights(cfg, SEED)
    for (prompt, n), toks in zip(requests, served):
        assert len(toks) == n
        seq = np.asarray(prompt + toks.tolist())
        logits = np.asarray(ref.forward(w, seq, cfg))
        rows = logits[len(prompt) - 1:len(seq) - 1]
        gap = rows.max(axis=1) - rows[np.arange(n), toks]
        assert gap.max() < 1e-4, gap
    ledger = eng.kv_numerics()       # no page held: nothing to report,
    assert set(ledger["layer_3/sub1"]) == {"wk", "wv"}    # by kind of pool
    assert ledger["layer_1/sub1"]["pk"]["pages"] == []
    assert eng.cache.pages_in_use("window") == 0          # all released
    assert eng.cache.pages_in_use("global") == 0
    assert not eng.metrics.registry.get_value(
        "dl4j_decode_param_casts_total", model="default")


def test_page_gauges_count_both_kinds_against_a_count_by_hand():
    net, cfg = toy_net()
    eng = GenerationEngine(net, slots=4, page_size=4, max_context=48,
                           prefill_buckets=(16, 32))
    assert eng.cache.window_pages_per_slot == 4           # ceil(12 / 4) + 1
    assert eng.cache.num_window_pages == 4 * 4 + 1
    reg, eid = eng.metrics.registry, eng.metrics.engine_id

    def gauges():
        eng._refresh_gauges()
        return {(name, kind): reg.get_value(f"dl4j_kv_pages_{name}",
                                            engine=eid, kind=kind)
                for name in ("in_use", "total")
                for kind in ("global", "window")}

    # 30 + 10 - 1 = 39 positions: 10 global pages, a whole ring of 4;
    # 5 + 3 - 1 = 7 positions: 2 global pages, 2 of the ring
    a, _ = eng.cache.admit(list(range(30)), 10)
    b, _ = eng.cache.admit(list(range(5)), 3)
    got = gauges()
    assert eng.cache.allocated_pages("global") == sorted(a + b)
    assert len(eng.cache.allocated_pages("window")) == 6
    assert got[("in_use", "global")] == 12 and got[("total", "global")] == 48
    assert got[("in_use", "window")] == 6 and got[("total", "window")] == 16
    # the share kv_window_page_share reads: 3 window layers, 1 global
    assert 3 * 6 / (3 * 6 + 1 * 12) == pytest.approx(0.6)
    assert eng.cache.utilization() == pytest.approx(18 / 64)
    row = eng.cache.block_row(a)
    assert row.shape == (12 + 4,) and (row[:10] == a).all()
    assert (row[10:12] == 0).all() and (row[12:] > 0).all()
    assert (eng.cache.block_row(b)[14:] == 0).all()
    eng.cache.free(a)
    eng.cache.free(b)
    got = gauges()
    assert got[("in_use", "global")] == 0 and got[("in_use", "window")] == 0


def test_a_net_without_window_layers_reports_the_global_kind_alone():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=29, d_model=32, n_heads=4, layers=2,
                              max_cache=64, seed=3)
    eng = GenerationEngine(net, slots=2, page_size=4, max_context=16,
                           prefill_buckets=(8,))
    eng._refresh_gauges()
    reg, eid = eng.metrics.registry, eng.metrics.engine_id
    assert reg.get_value("dl4j_kv_pages_total", engine=eid,
                         kind="global") == 8
    assert reg.get_value("dl4j_kv_pages_total", engine=eid,
                         kind="window") is None
    assert eng.cache.table_width == eng.cache.pages_per_slot == 4


# --------------------------------------------------- (e) the page manager
def test_window_pages_never_exceed_slots_times_ring():
    cache = PagedKVCache(200, 4, 12, window_pages_per_slot=4,
                         num_window_pages=4 * 4 + 1)
    held = [cache.admit(list(range(40)), 8)[0] for _ in range(4)]
    assert cache.pages_in_use("window") == 16 == 4 * 4
    assert cache.pages_in_use("global") == 4 * 12
    with pytest.raises(PageExhaustedError, match="window pages"):
        cache.admit(list(range(4)), 1)        # global pages left, no ring
    assert cache.pages_in_use("global") == 48           # nothing half taken
    cache.free(held.pop())
    assert cache.pages_in_use("window") == 12
    short, _ = cache.admit(list(range(6)), 1)           # 2 pages each kind
    assert cache.pages_in_use("window") == 14
    for pages in held + [short]:
        cache.free(pages)
    assert cache.pages_in_use("window") == cache.pages_in_use("global") == 0
    assert sorted(cache._window_free) == list(range(1, 17))


def test_prefix_sharing_is_off_and_the_prefix_cache_refused_under_windows():
    cache = PagedKVCache(100, 4, 12, window_pages_per_slot=4,
                         num_window_pages=17)
    prompt = list(range(24))
    a, shared_a = cache.admit(prompt, 4)
    b, shared_b = cache.admit(prompt, 4)          # the identical prompt
    assert shared_a == shared_b == 0 and not set(a) & set(b)
    assert cache.shared_pages == 0 and not cache._prefix
    plain = PagedKVCache(100, 4, 12)
    plain.admit(prompt, 4)
    assert plain.admit(prompt, 4)[1] == 20        # shared without windows
    net, _ = toy_net()
    with pytest.raises(ValueError, match="sliding-window"):
        GenerationEngine(net, slots=2, page_size=4, max_context=48,
                         prefill_buckets=(16,), prefix_cache=True)
    assert window_ring_pages(net, 4) == 4 and window_ring_pages(net, 16) == 2


def test_page_transport_round_trips_both_pool_kinds():
    net, _ = toy_net()
    progs = GenerationPrograms(net, slots=2, pages_per_slot=4, page_size=4,
                               num_pages=9, prefill_buckets=(8,))
    assert progs.ring == 4 and progs.num_window_pages == 9
    pools = progs.fresh_pools()
    keys = {p[-1].key for p, _ in jax.tree_util.tree_leaves_with_path(pools)}
    assert keys == {"pk", "pv", "wk", "wv"}
    rng = np.random.default_rng(2)
    pools = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), pools)
    payload = progs.read_page(pools, 3)
    assert progs.page_nbytes(pools) == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(payload))
    before = jax.tree_util.tree_map(np.asarray, pools)
    pools = progs.write_page(pools, 5, payload)
    for old, new in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(pools)):
        new = np.asarray(new)
        assert (new[5] == old[3]).all()
        keep = [i for i in range(9) if i != 5]
        assert (new[keep] == old[keep]).all()


def test_a_model_whose_ring_the_manager_was_not_built_for_is_refused():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    plain = transformer_char_lm(vocab_size=97, d_model=32, n_heads=4,
                                layers=1, max_cache=64, seed=3)
    eng = GenerationEngine(plain, slots=2, page_size=4, max_context=48,
                           prefill_buckets=(16,))
    net, _ = toy_net()
    with pytest.raises(ValueError, match="ring"):
        eng._build_programs(eng.models.new_version("default", net))


# -------------------- (g) the accepted configurations' programs, unchanged
# sha256 (first 16 hex) of the lowered text of each generation program, as
# the parent commit e499f6f lowers it under this suite's settings (CPU
# lowering: the lax page loop; x64 on).  A later PR that means to change
# these programs replaces the hashes with its own parent's.  StarCoder2's
# differ from the parent's in ONE thing, how a chunk's K/V reach the pool
# (``write_token_rows``, the one write since this PR; the parent's was the
# 4-D scatter): with the parent's write put back, the text is the parent's.
# PR 34 (the loop one step ahead): ``decode`` is still e499f6f's text, hash
# for hash; a ``prefill_<bucket>`` takes the ids vector and a lane and
# returns the vector with its sample there (one dynamic-update-slice more),
# so its hashes are that PR's own.  PR 36 (the streamed experts): kimi's
# programs call ``grouped_experts`` wherever a call has few rows (all three
# here); with the kernel withheld from the seam the text is still the
# parent's, hash for hash, so the ``ragged`` path is the parent's own.
# PR 37 (a kind scope on every layer; ``prefill_<bucket>`` names its module
# ``jit_prefill_<bucket>``): scopes are metadata and leave the text alone;
# with the parent's module name put back the prefills' text is the parent's.
# PR 38 (the latent pages read in place): kimi's ``decode`` takes the page
# loop (the kernel on a TPU) for its single-token attention; with that
# withheld from the seam (``supports_latent``) the gather + ``_absorbed``
# fallback is the parent's text, hash for hash
PARENT_PROGRAMS = {
    "starcoder2": {"prefill_16": "487447505f7cdceb",
                   "prefill_32": "b7803529ed6ac46d",
                   "decode": "0def4fd672d19a09"},
    "kimi": {"prefill_16": "6fdc1d06e6124d0d",
             "prefill_32": "5b986d6eeb6cebf8",
             "decode": "9efb82807bff073f"},
}


def _accepted_toy_net(family):
    import json
    import os

    here = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark")
    if family == "kimi":
        from benchmark import model_k2
        from tests import test_latent_moe

        cfg = dict(test_latent_moe.TOY)
        return model_k2.install_weights(model_k2.build_network(cfg), cfg,
                                        SEED)
    from benchmark import model

    cfg = model.load_config(os.path.join(here, "configs",
                                         "starcoder2-7b.json"))
    with open(os.path.join(here, "rehearsal.json")) as f:
        cfg.update(json.load(f)["config"])
    net = model.build_network(cfg, max_seq=48, updater="sgd", max_cache=48)
    net.init()
    return net


@pytest.mark.parametrize("family", ["starcoder2", "kimi"])
def test_accepted_nets_lower_to_the_programs_of_the_parent(family,
                                                           monkeypatch):
    monkeypatch.setattr(
        pa, "write_token_rows", lambda pool, page, off, rows:
        pool.at[page, :, off].set(rows.astype(pool.dtype)))
    monkeypatch.setattr(GroupedExpertsHelper, "supports",
                        lambda self, *widths: False)
    monkeypatch.setattr(pa.PagedAttentionHelper, "supports_latent",
                        lambda self, *pool: False)
    progs = GenerationPrograms(_accepted_toy_net(family), slots=4,
                               pages_per_slot=6, page_size=8, num_pages=25,
                               prefill_buckets=(16, 32))
    assert progs.ring == 0 and progs.num_window_pages == 0
    got = {name: hashlib.sha256(low.as_text().replace(
        f"@jit_{name} ", "@jit_prefill ").encode()).hexdigest()[:16]
           for name, low in progs.lowered().items()}
    assert got == PARENT_PROGRAMS[family]


def test_the_kernel_without_a_window_traces_as_on_the_parent():
    """The Pallas kernel's own jaxpr at sc2-7b.serve-complete's decode shape
    (its serialized form in a TPU lowering carries source lines, so the
    jaxpr is what can be compared)."""
    b, t, hq, hkv, d, ps, maxp = 32, 1, 36, 4, 128, 16, 36
    sds = jax.ShapeDtypeStruct
    pool = sds((b * maxp + 1, hkv, ps, d), jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda *a: pa._pallas_paged(*a, False))(
            sds((b, t, hq, d), jnp.bfloat16), pool, pool,
            sds((b, maxp), jnp.int32), sds((b, t), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "8f0219ef0ac40d19"

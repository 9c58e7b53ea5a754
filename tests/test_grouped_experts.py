"""The ``streamed`` and ``sorted`` paths of ``RoutedMoELayer``
(``helpers/grouped_experts.py``: every touched held expert's weights read
once, dense over a few rows or each expert's sorted rows alone) against the
layer's ``ragged`` path and the plain reference of
``tests/test_latent_moe.py``, at toy widths in interpret mode; the rule that
picks the path, the gradient, and the engine's counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.generation.engine import GenerationEngine
from deeplearning4j_tpu.helpers.grouped_experts import (
    SORTED_TILE, GroupedExpertsHelper, combine_matrix, expert_tiling,
    grouped_experts, sorted_block, sorted_experts, sorted_tiling)
from deeplearning4j_tpu.nn.layers import RoutedMoELayer
from deeplearning4j_tpu.nn.layers.moe import (
    EXPERT_PATHS, STREAMED_ROWS, expert_path)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from tests.test_latent_moe import (
    TOY, layer_leaves, model_k2, moe_layer, ref, routed_to, toy_net)

HELD = np.arange(4, 8)          # TOY holds experts 4..7 of 16


@pytest.fixture
def calls(monkeypatch):
    """How often the kernel's seam was taken while the test ran."""
    seen = []
    apply = GroupedExpertsHelper.apply

    def spy(self, tokens, *a):
        seen.append(tokens.shape[0])
        return apply(self, tokens, *a)

    monkeypatch.setattr(GroupedExpertsHelper, "apply", spy)
    return seen


@pytest.fixture
def sorted_calls(monkeypatch):
    """How often the sorted kernel's seam was taken while the test ran."""
    seen = []
    apply = GroupedExpertsHelper.apply_sorted

    def spy(self, tokens, *a):
        seen.append(tokens.shape[0])
        return apply(self, tokens, *a)

    monkeypatch.setattr(GroupedExpertsHelper, "apply_sorted", spy)
    return seen


def ragged(layer, params, x):
    """The layer's result with its held experts through the sorted groups."""
    tokens = x.reshape(-1, x.shape[-1])
    ids, w = layer.route(params, tokens)
    y = layer._held_ragged(params["W_gate"], params["W_up"],
                           params["W_down"], tokens, ids, w)
    if layer.shared:
        from deeplearning4j_tpu.nn.layers.dense import gated_mlp

        y = y + gated_mlp(tokens, params["Ws_gate"], params["Ws_up"],
                          params["Ws_down"], layer.activation)
    return y.astype(x.dtype).reshape(x.shape[:-1] + (layer.n_out,))


def plain(layer, params, x):
    """float64, one expert at a time, from the layer's own routing: neither
    path's code."""
    tokens = np.asarray(x.reshape(-1, x.shape[-1]), np.float64)
    ids, w = (np.asarray(a) for a in layer.route(
        params, x.reshape(-1, x.shape[-1])))
    first, count = layer.held
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}

    def mlp(wg, wu, wd):
        g = tokens @ wg
        return (g / (1.0 + np.exp(-g)) * (tokens @ wu)) @ wd

    y = np.zeros((tokens.shape[0], layer.n_out))
    for e in range(count):
        mine = np.where(ids == first + e, w, 0.0).sum(axis=1)
        y += mine[:, None] * mlp(p["W_gate"][e], p["W_up"][e],
                                 p["W_down"][e])
    if layer.shared:
        y += mlp(p["Ws_gate"], p["Ws_up"], p["Ws_down"])
    return y.reshape(x.shape[:-1] + (layer.n_out,))


# what the router's bias sends every token to; None: where its scores fall
CASES = {
    "uniform": None,
    "all_on_one_held": (5, 0, 9),
    "none_held": (1, 2, 12),
    "skewed_two_held_untouched": (4, 5, 1),
    "all_held": (4, 6, 7),
}


def case(name, dtype=jnp.float32, rows=(2, 19)):
    cfg = dict(TOY)
    layer = moe_layer(cfg)
    w, params = layer_leaves(cfg, 1, model_k2._MOE, dtype)
    if CASES[name] is not None:
        w["router.b"] = routed_to(cfg, CASES[name])
        params["b_router"] = w["router.b"].astype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(5), (*rows, cfg["hidden_size"]),
                          dtype)
    return cfg, layer, w, params, x


@pytest.mark.parametrize("name", list(CASES))
def test_streamed_equals_ragged_and_the_reference(name, calls):
    cfg, layer, w, params, x = case(name)
    got, _ = jax.jit(lambda p, xx: layer.apply(p, {}, xx))(params, x)
    assert calls == [38]                  # the kernel, once, on all the rows
    want = np.stack([np.asarray(ref.moe(r, w, cfg, "f32")) for r in x])
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert np.abs(np.asarray(got) - np.asarray(ragged(layer, params, x))
                  ).max() < 1e-4
    ids, _ = layer.route(params, x.reshape(-1, x.shape[-1]))
    touched = np.isin(HELD, np.asarray(ids))
    assert touched.sum() == {"all_on_one_held": 1, "none_held": 0,
                             "skewed_two_held_untouched": 2,
                             "all_held": 3}.get(name, 4)


@pytest.mark.parametrize("name", ["uniform", "skewed_two_held_untouched"])
def test_bfloat16_agrees_within_its_rounding(name):
    _, layer, _, params, x = case(name, jnp.bfloat16)
    got, _ = layer.apply(params, {}, x)
    assert got.dtype == jnp.bfloat16
    want = plain(layer, params, x)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float64) - want).max() < 3e-2 * scale
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(ragged(layer, params, x), np.float32)
                  ).max() < 3e-2 * scale


@pytest.mark.parametrize("rows,path", [
    (1, "streamed"), (STREAMED_ROWS, "streamed"), (STREAMED_ROWS + 1, "sorted")])
def test_the_row_count_picks_the_path(rows, path, calls, sorted_calls):
    _, layer, _, params, x = case("uniform", rows=(rows,))
    assert layer.path(rows) == path
    got, _ = layer.apply(params, {}, x)
    assert calls == ([rows] if path == "streamed" else [])
    assert sorted_calls == ([rows] if path == "sorted" else [])
    assert np.abs(np.asarray(got) - plain(layer, params, x)).max() < 1e-4


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_softmax_scoring_takes_the_same_kernel(dtype, tol, calls):
    layer = RoutedMoELayer(n_in=32, n_out=32, n_experts=12, top_k=4,
                           hidden=40, shared=16, experts_held=(3, 5),
                           routed_scaling_factor=2.5, scoring="softmax")
    params = layer.init(jax.random.PRNGKey(2), dtype)
    assert "b_router" not in params
    x = jax.random.normal(jax.random.PRNGKey(3), (23, 32), dtype)
    got, _ = layer.apply(params, {}, x)
    assert calls == [23]
    want = plain(layer, params, x)
    assert (np.abs(np.asarray(got, np.float64) - want).max()
            < tol * max(1.0, np.abs(want).max()))


def test_an_untouched_expert_is_not_read():
    """Held experts 6 and 7 are chosen by no row and hold NaN: a kernel that
    multiplied them, or copied them and masked, would not stay finite."""
    _, layer, _, params, x = case("skewed_two_held_untouched")
    clean, _ = layer.apply(params, {}, x)
    poisoned = dict(params)
    for k in ("W_gate", "W_up", "W_down"):
        poisoned[k] = params[k].at[2:].set(jnp.nan)
    got, _ = layer.apply(poisoned, {}, x)
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(got) == np.asarray(clean)).all()


def test_a_row_takes_nothing_of_an_expert_it_did_not_choose():
    """Select, not multiply: expert 0's product is non-finite on EVERY row
    (an infinite weight), and only row 0 chose it."""
    t, d, h, count = 5, 16, 128, 3
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (t, d))
    wg, wu = (jax.random.normal(k, (count, d, h)) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (count, h, d)).at[0, 0, 0].set(jnp.inf)
    ids = jnp.asarray([[0, 1]] + [[1, 2]] * (t - 1), jnp.int32)
    c, touched = combine_matrix(ids, jnp.full((t, 2), 0.5), 0, count)
    assert touched.all() and (np.asarray(c[1:, 0]) == 0).all()
    out = np.asarray(grouped_experts(x, wg, wu, wd, c, touched))
    assert not np.isfinite(out[0]).all() and np.isfinite(out[1:]).all()


def test_hidden_tiles_accumulate_to_the_untiled_result(monkeypatch):
    from deeplearning4j_tpu.helpers import grouped_experts as ge

    t, d, h, count = 9, 32, 512, 4
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(ks[0], (t, d))
    wg, wu = (0.2 * jax.random.normal(k, (count, d, h)) for k in ks[1:3])
    wd = 0.1 * jax.random.normal(ks[3], (count, h, d))
    ids = jax.random.randint(ks[4], (t, 2), 0, 6).astype(jnp.int32)
    c, touched = combine_matrix(ids, jnp.full((t, 2), 0.7), 1, count)
    whole = grouped_experts(x, wg, wu, wd, c, touched)
    assert expert_tiling(t, d, h, d, jnp.float32)[1] == h
    monkeypatch.setattr(ge, "VMEM_BUDGET", 150_000)
    jax.clear_caches()
    rows, tf, vmem = expert_tiling(t, d, h, d, jnp.float32)
    assert (rows, tf) == (16, 128) and vmem <= 150_000
    tiled = grouped_experts(x, wg, wu, wd, c, touched)
    jax.clear_caches()
    assert np.abs(np.asarray(tiled) - np.asarray(whole)).max() < 1e-5


def test_tiling_at_the_served_widths_fits_the_budget():
    from deeplearning4j_tpu.helpers.grouped_experts import VMEM_BUDGET

    for t, d, hidden, tile in [(64, 3584, 1024, 1024), (48, 7168, 2048, 512),
                               (32, 3072, 1024, 1024),
                               (STREAMED_ROWS, 7168, 2048, 128)]:
        rows, tf, vmem = expert_tiling(t, d, hidden, d, jnp.bfloat16)
        assert (rows, tf) == (t, tile) and vmem <= VMEM_BUDGET
        assert hidden % tf == 0 and tf % 128 == 0


def test_grad_through_an_inference_call_is_the_ragged_paths(monkeypatch,
                                                            calls):
    _, layer, _, params, x = case("uniform", rows=(11,))
    cot = jax.random.normal(jax.random.PRNGKey(9), (11, layer.n_out))

    def loss(p, xx):
        return jnp.sum(layer.apply(p, {}, xx)[0] * cot)

    got = jax.grad(loss, argnums=(0, 1))(params, x)
    assert calls == [11]                       # the forward was the kernel's
    monkeypatch.setattr(GroupedExpertsHelper, "supports",
                        lambda self, *widths: False)
    want = jax.grad(loss, argnums=(0, 1))(params, x)
    assert calls == [11]
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < 1e-5
    assert np.abs(np.asarray(got[0]["W_gate"])).max() > 0
    assert np.abs(np.asarray(got[0]["W_router"])).max() > 0


def test_a_training_call_keeps_the_sorted_groups(calls):
    _, layer, _, params, x = case("uniform", rows=(11,))
    y, _ = layer.apply(params, {}, x, train=True)
    assert calls == [] and layer.path(11, train=True) == "ragged"
    assert np.abs(np.asarray(y) - plain(layer, params, x)).max() < 1e-4


def test_expert_path_is_a_pure_function_of_rows_train_and_kernel():
    assert EXPERT_PATHS == ("streamed", "sorted", "ragged")
    for rows in (1, 32, 48, 64, STREAMED_ROWS):
        assert expert_path(rows) == "streamed"
        assert expert_path(rows, train=True) == "ragged"
        assert expert_path(rows, kernel=False) == "ragged"
    for rows in (STREAMED_ROWS + 1, 512, 8192):
        assert expert_path(rows) == "sorted"
        assert expert_path(rows, train=True) == "ragged"
        assert expert_path(rows, kernel=False) == "ragged"
    assert {expert_path(r, t, k) for r in (1, 8192) for t in (False, True)
            for k in (False, True)} == set(EXPERT_PATHS)


def test_the_kernel_gives_way_like_every_helper():
    layer = moe_layer(dict(TOY))
    assert layer.path(4) == "streamed" and layer.path(512) == "sorted"
    helpers.enable_helpers(False)
    try:
        assert layer.path(4) == layer.path(512) == "ragged"
    finally:
        helpers.enable_helpers(True)
    mesh = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])
    with helpers.auto_partitioned(mesh):
        assert layer.path(4) == layer.path(512) == "ragged"
    assert layer.path(4) == "streamed"
    # compiled, a tile is whole lanes of every width; interpreted, any width
    assert GroupedExpertsHelper().supports(64, 24, 64) == helpers.interpret_mode()


def test_shapes_that_do_not_fit_are_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="grouped_experts"):
        grouped_experts(z((4, 8)), z((2, 8, 16)), z((2, 8, 16)),
                        z((2, 12, 8)), z((4, 2)), z((2,), bool))


def test_the_engine_counts_each_dispatch_by_its_path():
    """Decode steps (4 rows) and a prefill bucket under the bound stream
    their experts; a bucket past it takes the sorted kernel."""
    net, _ = toy_net()
    big = STREAMED_ROWS + 32
    eng = GenerationEngine(net, slots=4, page_size=8, max_context=big + 16,
                           prefill_buckets=(32, big),
                           registry=MetricsRegistry()).start()
    try:
        rng = np.random.default_rng(1)
        handles = [eng.submit(rng.integers(0, 97, n).tolist(),
                              max_new_tokens=6) for n in (21, 40, 9)]
        served = [h.result() for h in handles]
    finally:
        eng.stop()
    assert [len(s) for s in served] == [6, 6, 6]
    progs = next(iter(eng._programs.values()))
    experts = {name: tuple(p for k, p in paths if k == "experts")
               for (name, _), paths in progs.paths.items()}
    assert experts == {"decode": ("streamed",), 32: ("streamed",),
                       big: ("sorted",)}
    reg = eng.metrics.registry

    def count(stage, path):
        return reg.get_value("dl4j_layer_path_steps_total", stage=stage,
                             kind="experts", path=path) or 0

    dispatched = sum(reg.get_value("dl4j_decode_dispatch_total", mode=m) or 0
                     for m in ("ahead", "sync"))
    assert count("decode", "streamed") == dispatched > 0
    assert count("decode", "sorted") == count("decode", "ragged") == 0
    assert count("prefill", "streamed") == 2      # 21 and 9 tokens
    assert count("prefill", "sorted") == 1        # 40 tokens
    assert count("prefill", "ragged") == 0


def test_a_net_without_expert_layers_counts_nothing():
    from tests.test_latent_moe import _kv_lm
    from deeplearning4j_tpu.generation.programs import GenerationPrograms

    progs = GenerationPrograms(_kv_lm(), slots=2, pages_per_slot=4,
                               page_size=4, num_pages=9, prefill_buckets=(8,))
    assert all(k != "experts" for paths in progs.paths.values()
               for k, _ in paths)


# ------------------------------------------------- the sorted path (PR 40)
SORTED_ROWS = (2, 150)        # 300 rows: past the bound, not whole tiles


@pytest.mark.parametrize("name", list(CASES))
def test_sorted_equals_ragged_and_the_reference(name, calls, sorted_calls):
    cfg, layer, w, params, x = case(name, rows=SORTED_ROWS)
    got, _ = jax.jit(lambda p, xx: layer.apply(p, {}, xx))(params, x)
    assert sorted_calls == [300] and calls == []
    want = np.stack([np.asarray(ref.moe(r, w, cfg, "f32")) for r in x])
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert np.abs(np.asarray(got) - np.asarray(ragged(layer, params, x))
                  ).max() < 1e-4


@pytest.mark.parametrize("scoring,held", [
    ("sigmoid", (3, 5)), ("sigmoid", None), ("softmax", (3, 5)),
    ("softmax", None)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_sorted_takes_both_scorings_and_any_share(scoring, held, dtype, tol,
                                                  sorted_calls):
    """``count < n_experts`` (blocks of twice the held share) and every
    expert held (one block, all the assignments)."""
    layer = RoutedMoELayer(n_in=32, n_out=32, n_experts=12, top_k=4,
                           hidden=40, shared=16, experts_held=held,
                           routed_scaling_factor=2.5, scoring=scoring)
    params = layer.init(jax.random.PRNGKey(2), dtype)
    x = jax.random.normal(jax.random.PRNGKey(3), (290, 32), dtype)
    got, _ = layer.apply(params, {}, x)
    assert sorted_calls == [290] and got.dtype == dtype
    want = plain(layer, params, x)
    assert (np.abs(np.asarray(got, np.float64) - want).max()
            < tol * max(1.0, np.abs(want).max()))


def test_no_held_assignment_reads_nothing_and_adds_nothing():
    """Every token routed elsewhere: the grid has no step, so even weights
    that are all NaN leave the result zero."""
    _, layer, _, params, x = case("none_held", rows=SORTED_ROWS)
    tokens = x.reshape(-1, x.shape[-1])
    ids, w = layer.route(params, tokens)
    nan = {k: jnp.full_like(params[k], jnp.nan)
           for k in ("W_gate", "W_up", "W_down")}
    got = sorted_experts(tokens, nan["W_gate"], nan["W_up"], nan["W_down"],
                         ids, w, first=4, n_experts=16)
    assert got.shape == (300, layer.n_out) and not np.asarray(got).any()


def test_sorted_reads_no_untouched_expert():
    _, layer, _, params, x = case("skewed_two_held_untouched",
                                  rows=SORTED_ROWS)
    clean, _ = layer.apply(params, {}, x)
    poisoned = dict(params)
    for k in ("W_gate", "W_up", "W_down"):
        poisoned[k] = params[k].at[2:].set(jnp.nan)
    got, _ = layer.apply(poisoned, {}, x)
    assert (np.asarray(got) == np.asarray(clean)).all()


@pytest.mark.parametrize("budget,tiling", [
    (None, (SORTED_TILE, 512, 1)),     # one hidden tile: weights resident
    (700_000, (SORTED_TILE, 128, 2)),  # four, y held for 2 row tiles
    (400_000, (SORTED_TILE, 128, 1))])  # four, a visit a row tile
@pytest.mark.parametrize("route", [None, (5, 0, 9, 1), (4, 5, 6, 7)])
def test_hidden_tiles_visits_and_blocks_agree(budget, tiling, route,
                                              monkeypatch):
    """Every combination of the kernel's schedules on one layer of 16
    experts, 4 held, top 4, 600 rows (150 an expert on average: visits of
    two row tiles where they fit): uniform routing (one block), every
    token on held expert 5 (600 rows: one expert over five tiles, three
    visits), every assignment held (2,400 rows over blocks of 1,280: two
    blocks, the second added to the first)."""
    from deeplearning4j_tpu.helpers import grouped_experts as ge

    if budget:
        monkeypatch.setattr(ge, "VMEM_BUDGET", budget)
    jax.clear_caches()
    layer = RoutedMoELayer(n_in=32, n_out=32, n_experts=16, top_k=4,
                           hidden=512, experts_held=(4, 4))
    params = layer.init(jax.random.PRNGKey(1), jnp.float32)
    if route is not None:
        params["b_router"] = jnp.zeros(16).at[jnp.asarray(route)].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (600, 32))
    ids, w = layer.route(params, x)
    assert sorted_tiling(32, 512, 32, jnp.float32, 150)[:3] == tiling
    assert sorted_block(2400, 4, 16) == 1280
    got = sorted_experts(x, params["W_gate"], params["W_up"],
                         params["W_down"], ids, w, first=4, n_experts=16)
    jax.clear_caches()
    want = layer._held_ragged(params["W_gate"], params["W_up"],
                              params["W_down"], x, ids, w)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 0.05


@pytest.mark.parametrize("cell,d,hidden,rows,tiling", [
    ("xing", 3584, 1024, 128, (128, 1024, 1)),
    ("laguna", 3072, 1024, 320, (128, 1024, 1)),
    ("k2", 7168, 2048, 85, (128, 256, 1)),      # bucket 4,096: 85 an expert
    ("k2", 7168, 2048, 400, (128, 256, 4))])    # more rows: y for 4 tiles
def test_sorted_tiling_at_the_served_widths_fits_the_budget(cell, d, hidden,
                                                            rows, tiling):
    from deeplearning4j_tpu.helpers.grouped_experts import VMEM_BUDGET

    tm, tf, r, vmem = sorted_tiling(d, hidden, d, jnp.bfloat16, rows)
    assert (tm, tf, r) == tiling and vmem <= VMEM_BUDGET
    assert hidden % tf == 0 and tf % 128 == 0


@pytest.mark.parametrize("assignments,count,n,block", [
    (4096 * 8, 12, 384, 2048),      # k2's bucket 4096: twice 1,024
    (8192 * 10, 32, 256, 20480),    # Laguna's 8192
    (2048 * 4, 64, 64, 8192),       # Xing holds all: every assignment
    (300, 1, 384, 128)])            # at least one tile
def test_a_block_is_twice_the_held_share(assignments, count, n, block):
    assert sorted_block(assignments, count, n) == block


def test_grad_through_a_sorted_call_is_the_ragged_paths(monkeypatch,
                                                        sorted_calls):
    _, layer, _, params, x = case("uniform", rows=(STREAMED_ROWS + 11,))
    cot = jax.random.normal(jax.random.PRNGKey(9),
                            (STREAMED_ROWS + 11, layer.n_out))

    def loss(p, xx):
        return jnp.sum(layer.apply(p, {}, xx)[0] * cot)

    got = jax.grad(loss, argnums=(0, 1))(params, x)
    assert sorted_calls == [STREAMED_ROWS + 11]   # the forward: the kernel
    monkeypatch.setattr(GroupedExpertsHelper, "supports",
                        lambda self, *widths: False)
    want = jax.grad(loss, argnums=(0, 1))(params, x)
    assert sorted_calls == [STREAMED_ROWS + 11]
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < 1e-5
    assert np.abs(np.asarray(got[0]["W_down"])).max() > 0


@pytest.mark.parametrize("cell,t,d,hidden,count,n,k,calls", [
    # every expert held: one block, the kernel once; a share: the first
    # block, and the loop over the blocks a skewed batch adds
    ("xing", 512, 3584, 1024, 64, 64, 4, 1),
    ("laguna", 1024, 3072, 1024, 32, 256, 10, 2),
    ("k2", 1024, 7168, 2048, 12, 384, 8, 2)])
def test_sorted_kernel_lowers_for_tpu_at_the_served_widths(cell, t, d,
                                                           hidden, count, n,
                                                           k, calls):
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    fn = jax.jit(lambda *a: sorted_experts(*a, n_experts=n,
                                           interpret=False))
    with jax.enable_x64(False):
        text = fn.trace(
            sds((t, d), bf), sds((count, d, hidden), bf),
            sds((count, d, hidden), bf), sds((count, hidden, d), bf),
            sds((t, k), jnp.int32), sds((t, k), jnp.float32)).lower(
                lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "sorted_experts"') == calls

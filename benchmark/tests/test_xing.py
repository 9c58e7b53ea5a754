"""``xing4.0-29b-a4b-pp6``'s and ``sc2-3b.train-dp4``'s yardstick: the
manifest's new entries against their files, ``flops_xing`` against the count
by hand in its docstring, the configuration file against what the count
assumes, ``reference_xing`` layer by layer against itself whole at a toy
size, the scope map against a compiled program's own text, the collective
reader against a hand-made trace, and — with the timed path of each new job
broken underneath — ``correct`` false.  (``test_rehearsal.py`` rehearses every
cell of the manifest, these two among them, and holds each cell's control to
be not ``correct``.)

Every expert is held (``experts_held = (0, 64)`` of 64), so the guide's test
that all the shares of an expert layer add up to the uncut reference has
nothing to add here: the one share is the whole, and ``tests/test_xing.py``
compares it with the reference directly.

Run by hand with the rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import flops_xing, reference_xing as ref
from benchmark import run as harness
from benchmark.metrics import _scopes
from benchmark.trace_reduce import Event, Reduced

ROOT = harness.ROOT
XING, DP4 = "xing.serve-reason", "sc2-3b.train-dp4"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(
            ROOT, "benchmark/configs/xing4.0-29b-a4b-pp6.json")) as f:
        return json.load(f)


def test_the_manifests_new_entries_resolve(cfg):
    m = harness.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[XING]["chips"] == 1 and cells[DP4]["chips"] == 4
    assert cells[XING]["config"] == cfg["name"] == "xing4.0-29b-a4b-pp6"
    assert cells[DP4]["config"] == "starcoder2-3b"
    entry = harness.find(m["configs"], cfg["name"], "configuration")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    reported = {cell: {x["name"] for kind in ("end_to_end", "per_layer")
                       for x in harness.metrics_of_cell(m, kind, cell)}
                for cell in (XING, DP4)}
    assert {"serve_tokens_per_s", "setup_s", "serve_step_mfu.xing",
            "mhc_share_of_window", "mhc_row_sum_error",
            "moe_held_assignments_per_token", "decode_step_device_ms",
            "device_idle_share.serve"} <= reported[XING]
    assert {"train_tokens_per_s", "setup_s", "allreduce_exposed_share",
            "train_step_mfu.dp4", "window_compiles.train",
            "device_idle_share.train"} <= reported[DP4]
    # read per device, a kernel's time against the whole batch's operations
    # would come out four times too high
    assert "flash_attention_roofline" not in reported[DP4]
    for cell in (XING, DP4):
        for name in reported[cell] - {"serve_tokens_per_s", "setup_s",
                                      "train_tokens_per_s"}:
            assert hasattr(harness.load_reader(name), "read"), name


def test_the_configuration_is_the_published_one_cut_in_depth_alone(cfg):
    published = dict(
        hidden_size=3584, intermediate_size=9216, moe_intermediate_size=1024,
        num_attention_heads=32, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=64, num_experts_per_tok=4, n_shared_experts=1,
        first_k_dense_replace=2, vocab_size=131072, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, routed_scaling_factor=2, rope_theta=10000,
        rms_norm_eps=1e-6, num_nextn_predict_layers=1, ep_size=1,
        tie_word_embeddings=False, scoring_func="sigmoid")
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 7
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert set(cfg["reduced_why"]) == {"num_hidden_layers"}
    assert ref.router_width(cfg) == 64 and cfg["first_expert_held"] == 0


def test_flops_agree_with_the_count_by_hand(cfg):
    assert flops_xing.attention_macs(cfg) == 28_409_856
    assert flops_xing.expert_macs(cfg) == 11_010_048
    assert flops_xing.held_assignments_per_token(cfg) == 4.0
    assert flops_xing.mhc_sublayer_flops(cfg) == 860_800
    assert flops_xing.layer_macs(cfg, 0) == 128_361_088
    assert flops_xing.layer_macs(cfg, 2) == 84_550_272
    assert flops_xing.token_macs(cfg) == 679_473_536
    assert flops_xing.head_macs(cfg) == 469_762_048
    assert flops_xing.expanded_pair_flops(cfg) == 20_480
    assert flops_xing.absorbed_pair_flops(cfg) == 69_632
    assert flops_xing.prompt_flops(cfg, 1024) == pytest.approx(
        2 * (1024 * 679_473_536 + 469_762_048)
        + 7 * 20_480 * 1024 * 1025 / 2)
    assert flops_xing.prompt_flops(cfg, 1024) == pytest.approx(1.468e12,
                                                               rel=1e-3)
    assert flops_xing.decode_flops(cfg, 1500) == pytest.approx(3.030e9,
                                                               rel=1e-3)


def test_parameters_held_agree_with_the_configuration_files_arithmetic(cfg):
    def size(shape):
        return int(np.prod(shape))
    dense = sum(size(s) for s in ref.layer_shapes(cfg, 0).values())
    moe = sum(size(s) for s in ref.layer_shapes(cfg, 2).values())
    total = sum(size(s) for s in ref.leaf_shapes(cfg).values())
    hc = 2 * (14_336 * 24 + 3 + 24)
    norms = 2 * 3584 + 768 + 512
    assert dense == 28_409_856 + 99_090_432 + hc + norms
    assert moe == (28_409_856 + 11_010_048 + 229_376 + 64 * 11_010_048
                   + 64 + hc + norms)
    assert total == pytest.approx(4.92e9, rel=2e-3)
    assert 2 * total == pytest.approx(9.84e9, rel=2e-3)


TOY = dict(
    hidden_size=48, intermediate_size=96, num_attention_heads=2,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    first_k_dense_replace=1, num_hidden_layers=3, n_routed_experts=4,
    first_expert_held=0, num_experts_per_tok=2, n_shared_experts=1,
    moe_intermediate_size=16, norm_topk_prob=True, routed_scaling_factor=2,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, vocab_size=61, torch_dtype="bfloat16",
    initializer_range=0.1)


@pytest.mark.parametrize("precision", ["f32", "bf16", "fp8"])
def test_reference_layer_by_layer_equals_itself_whole(precision):
    ids = [np.random.default_rng(i).integers(0, 61, 37) for i in range(2)]
    w = ref.make_weights(TOY, 2**31 + 5)
    hidden = ref.hidden_states(TOY, 2**31 + 5, ids, (precision,))[precision]
    head_w, head_b = ref.head_leaves(TOY, 2**31 + 5)
    for seq, h in zip(ids, hidden):
        got = np.asarray(ref.logits_in_blocks(h, head_w, head_b, precision))
        want = np.asarray(ref.forward(w, seq, TOY, precision))
        assert np.abs(got - want).max() < 1e-5


def test_mhc_leaves_are_drawn_at_their_own_scales_and_stored_rounded():
    import jax.numpy as jnp

    shapes = ref.hc_shapes(TOY)
    assert shapes == {"phi": (192, 24), "alpha": (3,), "beta": (24,)}
    w = ref.make_leaves(TOY, 3, "L1.ffn_hc.", shapes)
    assert abs(float(w["phi"].std()) - 192 ** -0.5) < 0.15 * 192 ** -0.5
    assert np.abs(np.asarray(w["alpha"]) - 0.7).max() < 0.35
    for a in w.values():
        assert a.dtype == jnp.float32
        assert (a == a.astype(jnp.bfloat16).astype(jnp.float32)).all()
    other = ref.make_leaves(TOY, 3, "L1.attn_hc.", shapes)
    assert not (other["phi"] == w["phi"]).all()


# ------------------------------------------------------- the scope map
def test_scope_map_reads_a_compiled_programs_own_text():
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("layer_2"):
            with jax.named_scope("mhc_coeffs"):
                m = jnp.tanh(x @ w)
            with jax.named_scope("mhc_sinkhorn"):
                m = jax.lax.fori_loop(0, 3, lambda _, a: a / (1e-6 + jnp.sum(
                    a, axis=0, keepdims=True)), jnp.exp(m))
        with jax.named_scope("sample"):
            return jnp.argmax(m, axis=-1)

    text = jax.jit(f).lower(jnp.ones((8, 16)), jnp.ones((16, 4))
                            ).compile().as_text()
    scopes = {s for s, _ in _scopes.program_map(text).values()}
    assert {"mhc_coeffs", "mhc_sinkhorn", "sample"} <= scopes
    assert _scopes.scope_of("jit(f)/layer_2/mhc_mix/mul") == "mhc_mix"
    assert _scopes.scope_of("jit(f)/layer_2/sub1/dot_general") is None
    maps = _scopes.of_programs({"decode": text, "prefill_16": text,
                                "prefill_32": text})
    assert set(maps) == {"decode", "prefill"}
    assert maps["decode"] == maps["prefill"]


def test_seconds_by_scope_sums_a_traces_events_by_program_kind():
    maps = {"decode": {"fusion.1 f32[4]": ("mhc_mix", False),
                       "fusion.2 f32[4]": ("moe_experts", True),
                       "fusion.3 f32[4]": (None, False)},
            "prefill": {"fusion.1 f32[4]": ("sample", False)}}
    ops = [Event("fusion.1 f32[4]", 10, 30),      # decode: mhc_mix 20
           Event("fusion.3 f32[4]", 12, 18),      # inside it: not again
           Event("fusion.2 f32[4]", 30, 40),      # decode: experts, mixed
           Event("fusion.3 f32[4]", 40, 45),      # decode: no scope
           Event("fusion.9 f32[4]", 45, 50),      # decode: not in the map
           Event("fusion.1 f32[4]", 110, 150),    # prefill: sample 40
           Event("fusion.1 f32[4]", 300, 310)]    # outside any program
    mods = [Event("jit_decode_step(1)", 0, 100),
            Event("jit_prefill(2)", 100, 200)]
    trace = Reduced((0, 1000), {"/device:TPU:0": ops},
                    {"/device:TPU:0": mods}, [])
    got = _scopes.seconds_by_scope(trace, maps)
    assert got == pytest.approx({"mhc_mix": 20e-9, "moe_experts": 10e-9,
                                 "mixed_mhc": 10e-9, "unmapped": 5e-9,
                                 "no_scope": 5e-9, "sample": 40e-9})


def test_allreduce_exposed_share_counts_the_collectives_events():
    reader = harness.load_reader("allreduce_exposed_share")
    ops = [Event("all-reduce-start.3 f32[1024]", 0, 10),
           Event("fusion.7 f32[1024]", 10, 500),
           Event("all-reduce-done.3 f32[1024]", 500, 700),
           Event("all-gather.1 f32[8]", 700, 720),
           Event("reduce_fusion.2 f32[8]", 720, 900)]     # not a collective
    trace = Reduced((0, 1000), {"/device:TPU:0": ops,
                                "/device:TPU:1": [Event("x", 0, 1000)]},
                    {}, [])
    ctx = argparse.Namespace(trace=trace)
    assert reader.read(ctx) == pytest.approx(23.0)
    quiet = Reduced((0, 1000), {"/device:TPU:0": ops[1:2]}, {}, [])
    assert reader.read(argparse.Namespace(trace=quiet)) is None


# ---------------------------------------------------------- the faults
def args_for(cell, seed=2147483659):
    return argparse.Namespace(workload=cell, seed=seed, seconds=1.0, trace=0,
                              rehearsal=True, describe=None)


@pytest.mark.parametrize("cell,fault", [(XING, "token_altered"),
                                        (DP4, "chip_left_out")])
def test_a_broken_timed_path_is_not_correct(cell, fault):
    line = harness.run(args_for(cell), fault=fault)
    assert line["would_be_correct"] is False, line["compared"]


@pytest.mark.parametrize("cell", [XING, DP4])
def test_a_sound_run_is_correct_and_its_control_is_not(cell):
    _, _, job, ctx = harness.make_context(args_for(cell, seed=4000000007))
    try:
        job.setup(ctx)
        job.window(ctx, 1.0)
    finally:
        job.release(ctx)
    checks = job.check(ctx)
    assert all(v <= lim for _, v, lim in checks), checks
    control = job.calibrate(ctx, with_control=True)["control_fp8"]
    assert any(control[name] > limit for name, limit in ctx.limits.items()), \
        (control, ctx.limits)
    if cell == DP4:
        fault = job.calibrate(ctx, with_control=True)["fault_chip_left_out"]
        assert any(fault[name] > limit
                   for name, limit in ctx.limits.items()), fault

"""``kimi-k2.5-ep32``'s yardstick: ``flops_k2`` against the count by hand in
its docstring, the configuration file against what the count assumes, and
``reference_k2`` layer by layer against itself whole at a toy size.

Run by hand with the rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import json
import os

import numpy as np
import pytest

from benchmark import flops_k2, reference_k2 as ref
from benchmark import run as harness

ROOT = harness.ROOT


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark/configs/kimi-k2.5-ep32.json")) as f:
        return json.load(f)


def test_flops_agree_with_the_count_by_hand(cfg):
    assert flops_k2.attention_macs(cfg) == 101_122_048
    assert flops_k2.expert_macs(cfg) == 44_040_192
    assert flops_k2.held_assignments_per_token(cfg) == 0.25
    assert flops_k2.layer_macs(cfg, 0) == 497_483_776
    assert flops_k2.layer_macs(cfg, 1) == 158_924_800
    assert flops_k2.token_macs(cfg) == 1_451_032_576
    assert flops_k2.head_macs(cfg) == 146_800_640
    assert flops_k2.expanded_pair_flops(cfg) == 40_960
    assert flops_k2.absorbed_pair_flops(cfg) == 139_264
    assert flops_k2.prompt_flops(cfg, 2048) == pytest.approx(
        2 * (2048 * 1_451_032_576 + 146_800_640)
        + 7 * 40_960 * 2048 * 2049 / 2)
    assert flops_k2.prompt_flops(cfg, 2048) == pytest.approx(6.545e12, rel=1e-3)
    assert flops_k2.decode_flops(cfg, 1900) == pytest.approx(5.049e9, rel=1e-3)
    assert flops_k2.serve_forward_flops(cfg, [2048], [1900, 1900]) == pytest.approx(
        flops_k2.prompt_flops(cfg, 2048) + 2 * flops_k2.decode_flops(cfg, 1900))


def test_parameters_held_agree_with_the_configuration_files_arithmetic(cfg):
    def size(shape):
        return int(np.prod(shape))
    dense = sum(size(s) for s in ref.layer_shapes(cfg, 0).values())
    moe = sum(size(s) for s in ref.layer_shapes(cfg, 1).values())
    total = sum(size(s) for s in ref.leaf_shapes(cfg).values())
    # matrices as counted by hand, plus the norms' gains and the router's bias
    assert dense == 497_483_776 + 2 * 7168 + 1536 + 512
    assert moe == (101_122_048 + 44_040_192 + 2_752_512 + 12 * 44_040_192
                   + 2 * 7168 + 1536 + 512 + 384)
    assert total == pytest.approx(4.85e9, rel=2e-3)
    assert ref.router_width(cfg) == 384 and cfg["num_experts_per_tok"] == 8


TOY = dict(
    hidden_size=48, intermediate_size=96, num_attention_heads=2,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rms_norm_eps=1e-5, rope_theta=50000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    first_k_dense_replace=1, num_hidden_layers=3, n_routed_experts=2,
    first_expert_held=2, published=dict(n_routed_experts=8),
    num_experts_per_tok=2, n_shared_experts=1, moe_intermediate_size=16,
    norm_topk_prob=True, routed_scaling_factor=2.827, vocab_size=61,
    torch_dtype="bfloat16", initializer_range=0.1)


@pytest.mark.parametrize("precision", ["f32", "bf16", "fp8"])
def test_reference_layer_by_layer_equals_itself_whole(precision):
    ids = [np.random.default_rng(i).integers(0, 61, 37) for i in range(2)]
    w = ref.make_weights(TOY, 2**31 + 5)
    hidden = ref.hidden_states(TOY, 2**31 + 5, ids, (precision,))[precision]
    head_w, head_b = ref.head_leaves(TOY, 2**31 + 5)
    for seq, h in zip(ids, hidden):
        got = np.asarray(ref.logits_of(h, head_w, head_b, precision))
        want = np.asarray(ref.forward(w, seq, TOY, precision))
        assert np.abs(got - want).max() < 1e-5


def test_leaves_hold_the_stored_dtypes_values():
    import jax.numpy as jnp

    w = ref.make_leaf(TOY, 3, "L1.wqa", (48, 24))
    assert w.dtype == jnp.float32
    assert (w == w.astype(jnp.bfloat16).astype(jnp.float32)).all()
    again = ref.make_leaf(TOY, 3, "L1.wqa", (48, 24), jnp.bfloat16)
    assert (again.astype(jnp.float32) == w).all()
    other = ref.make_leaf(TOY, 3, "L2.wqa", (48, 24))
    assert not (other == w).all()


def test_a_token_altered_under_the_timed_path_is_not_correct():
    """``test_rehearsal.py`` names its faulty cells by hand; this cell's
    fault is planted here: the decode program's ids shifted by one."""
    import argparse

    args = argparse.Namespace(workload="k2.serve-docqa", seed=2147483659,
                              seconds=1.0, trace=0, rehearsal=True,
                              describe=None)
    line = harness.run(args, fault="token_altered")
    assert line["would_be_correct"] is False, line["compared"]

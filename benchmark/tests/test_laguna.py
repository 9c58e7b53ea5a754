"""``laguna-s-2.1-ep8``'s yardstick: ``flops_laguna`` against the count by
hand in its docstring (the window's byte count among them), the configuration
file against the catalog row's widths and against what the count assumes, and
``reference_laguna`` in query blocks and layer by layer against itself whole
at a toy size.  (The rehearsal of both cells this configuration's PR added is
``test_rehearsal.py``'s, which takes its cells from the manifest.)

Run by hand with the rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import json
import math
import os

import numpy as np
import pytest

from benchmark import flops_laguna, reference_laguna as ref
from benchmark import run as harness

ROOT = harness.ROOT


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT,
                           "benchmark/configs/laguna-s-2.1-ep8.json")) as f:
        return json.load(f)


def test_flops_agree_with_the_count_by_hand(cfg):
    f = flops_laguna
    assert f.attention_macs(cfg, 0) == 44_187_648           # full, 48 heads
    assert f.attention_macs(cfg, 1) == 63_135_744           # sliding, 72
    assert f.expert_macs(cfg) == 9_437_184
    assert f.held_assignments_per_token(cfg) == 1.25
    assert f.layer_macs(cfg, 0) == 157_433_856              # dense
    assert f.layer_macs(cfg, 4) == 66_207_744               # full, experts
    assert f.layer_macs(cfg, 1) == 85_155_840               # sliding, experts
    assert f.token_macs(cfg) == 1_056_251_904
    assert f.head_macs(cfg) == 38_535_168
    assert f.pair_flops(cfg, 0) == 24_576 and f.pair_flops(cfg, 1) == 36_864
    # banded on sliding layers
    assert f.prompt_pairs(cfg, 0, 4096) == 4096 * 4097 / 2 == 8_390_656
    assert f.prompt_pairs(cfg, 1, 4096) == 131_328 + 512 * 3584 == 1_966_336
    assert f.prompt_pairs(cfg, 1, 100) == 100 * 101 / 2
    assert f.decode_pairs(cfg, 0, 3000) == 3001
    assert f.decode_pairs(cfg, 1, 3000) == 512 and f.decode_pairs(cfg, 1, 9) == 10
    assert f.prompt_flops(cfg, 4096) == pytest.approx(
        2 * (4096 * 1_056_251_904 + 38_535_168)
        + 3 * 24_576 * 8_390_656 + 9 * 36_864 * 1_966_336)
    assert f.prompt_flops(cfg, 4096) == pytest.approx(9.924e12, rel=1e-3)
    assert f.decode_flops(cfg, 3000) == pytest.approx(2.581e9, rel=1e-3)
    assert f.serve_forward_flops(cfg, [4096], [3000, 3000]) == pytest.approx(
        f.prompt_flops(cfg, 4096) + 2 * f.decode_flops(cfg, 3000))


def test_decode_reads_the_window_alone_on_sliding_layers(cfg):
    f = flops_laguna
    # p = 3000: 47 resident pages on 3 full layers, 8 on 9 sliding ones
    assert f.kv_bytes_read(cfg, [3000], 64) == \
        3 * 47 * 64 * 4096 + 9 * 8 * 64 * 4096 == 55_836_672
    # inside the first window both kinds read what is there
    assert f.kv_bytes_read(cfg, [99], 64) == 12 * 2 * 64 * 4096
    # however long the context, a sliding layer's share stays 8 pages
    far = f.kv_bytes_read(cfg, [8000], 64)
    assert far == 3 * 126 * 64 * 4096 + 9 * 8 * 64 * 4096
    # every layer keeping everything would read 3.36 times as much there
    assert 12 * 126 * 64 * 4096 / far == pytest.approx(3.36, rel=0.01)


def test_configuration_keeps_the_catalog_rows_widths(cfg):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert set(cfg["reduced_why"]) == changed
    for reading in ("router_scoring", "shared_expert", "gating", "qk_norm"):
        assert reading in cfg["assumed"]
    assert cfg["deployment"].startswith("8 chips share each layer")


def test_parameters_held_agree_with_the_configuration_files_arithmetic(cfg):
    def size(shape):
        return int(np.prod(shape))
    per_layer = [sum(size(s) for s in ref.layer_shapes(cfg, i).values())
                 for i in range(cfg["num_hidden_layers"])]
    norms = 2 * 3072
    assert per_layer[0] == 157_433_856 + norms
    assert per_layer[4] == (44_187_648 + 9_437_184 + 786_432
                            + 32 * 9_437_184 + norms)
    assert per_layer[1] == (63_135_744 + 9_437_184 + 786_432
                            + 32 * 9_437_184 + norms)
    total = sum(size(s) for s in ref.leaf_shapes(cfg).values())
    assert total == pytest.approx(4.33e9, rel=2e-3)         # 8.65 GB of bf16
    assert ref.router_width(cfg) == 256 and cfg["num_experts_per_tok"] == 10
    kinds = [ref.is_sliding(cfg, i) for i in range(12)]
    assert kinds == [False, True, True, True] * 3
    assert [ref.heads_of(cfg, i) for i in range(4)] == [48, 72, 72, 72]
    full = cfg["rope_parameters"]["full_attention"]
    assert full["attention_factor"] == pytest.approx(
        0.1 * math.log(full["factor"]) + 1.0, abs=1e-12)


TOY = dict(
    model_type="laguna", hidden_size=48, intermediate_size=96, head_dim=8,
    num_key_value_heads=2, num_hidden_layers=4,
    num_attention_heads_per_layer=[4, 6, 6, 6],
    layer_types=["full_attention"] + ["sliding_attention"] * 3,
    mlp_only_layers=[0], sliding_window=10,
    rope_parameters=dict(
        full_attention=dict(rope_theta=500000, rope_type="yarn", factor=128,
                            original_max_position_embeddings=16, beta_slow=1,
                            beta_fast=32, attention_factor=1.4852030263919618,
                            partial_rotary_factor=0.5),
        sliding_attention=dict(rope_type="default", rope_theta=10000,
                               partial_rotary_factor=1)),
    rms_norm_eps=1e-6, num_experts=2, first_expert_held=2,
    published=dict(num_experts=8), num_experts_per_tok=2,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    norm_topk_prob=True, moe_routed_scaling_factor=2.5, vocab_size=61,
    torch_dtype="bfloat16", initializer_range=0.1)


@pytest.mark.parametrize("precision", ["f32", "bf16", "fp8"])
def test_reference_in_query_blocks_layer_by_layer_equals_itself_whole(
        precision):
    """40 positions in query blocks of 8 (four windows long, 2.5 times the
    toy original length), one layer's leaves at a time, against every leaf
    at once and the scores whole."""
    ids = [np.random.default_rng(i).integers(0, 61, 40) for i in range(2)]
    w = ref.make_weights(TOY, 2**31 + 5)
    hidden = ref.hidden_states(TOY, 2**31 + 5, ids, (precision,),
                               q_block=8)[precision]
    head_w, head_b = ref.head_leaves(TOY, 2**31 + 5)
    for seq, h in zip(ids, hidden):
        got = np.asarray(ref.logits_of(h, head_w, head_b, precision))
        want = np.asarray(ref.forward(w, seq, TOY, precision, q_block=None))
        assert np.abs(got - want).max() < 1e-5


def test_the_window_is_a_mask_over_the_whole_sequence():
    """A sliding layer's output at position i does not move when a key more
    than the window behind it does; a full layer's does."""
    w = ref.make_leaves(TOY, 7, "L1.", ref.layer_shapes(TOY, 1))
    w0 = ref.make_leaves(TOY, 7, "L0.", ref.layer_shapes(TOY, 0))
    x = np.random.default_rng(0).normal(size=(30, 48)).astype(np.float32)
    y = x.copy()
    y[3] += 1.0
    for i, leaves, moved in ((1, w, False), (0, w0, True)):
        a = np.asarray(ref.attention(x, leaves, TOY, i, "f32"))
        b = np.asarray(ref.attention(y, leaves, TOY, i, "f32"))
        assert (np.abs(a[13:] - b[13:]).max() > 1e-6) == moved
        assert np.abs(a[12] - b[12]).max() > 1e-6     # 12 - 10 < 3 <= 12


def test_leaves_hold_the_stored_dtypes_values():
    import jax.numpy as jnp

    w = ref.make_leaf(TOY, 3, "L1.wq", (48, 48))
    assert w.dtype == jnp.float32
    assert (w == w.astype(jnp.bfloat16).astype(jnp.float32)).all()
    again = ref.make_leaf(TOY, 3, "L1.wq", (48, 48), jnp.bfloat16)
    assert (again.astype(jnp.float32) == w).all()
    other = ref.make_leaf(TOY, 3, "L2.wq", (48, 48))
    assert not (other == w).all()


def test_a_token_altered_under_the_timed_path_is_not_correct():
    """``test_rehearsal.py`` names its faulty cells by hand; this cell's
    fault is planted here: the decode program's ids shifted by one."""
    import argparse

    args = argparse.Namespace(workload="laguna.serve-mixed-8k",
                              seed=2147483659, seconds=1.0, trace=0,
                              rehearsal=True, describe=None)
    line = harness.run(args, fault="token_altered")
    assert line["would_be_correct"] is False, line["compared"]

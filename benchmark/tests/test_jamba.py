"""``jamba2-3b``'s yardstick: the manifest's new entries against their
files, the configuration file against the catalog row's keys,
``flops_jamba`` against the count by hand in its docstring (3,029 M
parameters), ``reference_jamba`` layer by layer against itself whole at a
toy size, the readers of the state-space layers' device time against a
hand-made table and trace, the rehearsal of the new cell and — with the
timed path broken underneath, once a fault — ``correct`` false.
(``test_rehearsal.py`` rehearses every cell of the manifest, the two new
ones among them.)

Run by hand with the rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import flops_jamba, reference_jamba as ref
from benchmark import run as harness
from benchmark.metrics import _state_space
from benchmark.trace_reduce import Event, Reduced

ROOT = harness.ROOT
CHAT, GENERATE = "jamba2.serve-chat", "sc2-7b.serve-generate"
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark/configs/jamba2-3b.json")) as f:
        return json.load(f)


def test_the_manifests_new_entries_resolve(cfg):
    m = harness.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[CHAT]["chips"] == cells[GENERATE]["chips"] == 1
    assert cells[CHAT]["config"] == cfg["name"] == "jamba2-3b"
    assert cells[GENERATE]["config"] == "starcoder2-7b"
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    entry = harness.find(m["configs"], cfg["name"], "configuration")
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    reported = {cell: {x["name"] for kind in ("end_to_end", "per_layer")
                       for x in harness.metrics_of_cell(m, kind, cell)}
                for cell in (CHAT, GENERATE)}
    assert {"serve_tokens_per_s", "setup_s", "serve_step_mfu.jamba",
            "state_step_roofline", "state_space_ms_per_decode_step",
            "state_space_prefill_share", "state_slots_in_use",
            "decode_step_ms.rest", "prefill_share.rest",
            "window_compiles.serve", "device_idle_share.serve",
            "device_time_unattributed_share.serve"} <= reported[CHAT]
    assert not {"paged_attention_roofline", "serve_step_mfu",
                "decode_step_ms.experts"} & reported[CHAT]
    assert {"serve_tokens_per_s", "setup_s", "serve_step_mfu",
            "paged_attention_roofline", "decode_step_ms.attention",
            "window_compiles.serve"} <= reported[GENERATE]
    for cell in (CHAT, GENERATE):
        for name in reported[cell] - {"serve_tokens_per_s", "setup_s"}:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", name + ".py")), name
        with open(os.path.join(ROOT, "benchmark", "limits",
                               cell + ".json")) as f:
            assert json.load(f)["cell"] == cell


def test_the_configuration_holds_the_published_keys_unchanged(cfg):
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    for key in ("source", "reduced_why", "assumed", "deployment"):
        assert cfg[key]
    assert [i for i in range(28) if ref.is_attention(cfg, i)] == [7, 21]


def test_the_traffic_is_the_cells_as_issued():
    from benchmark import traffic_gen

    chat = traffic_gen.load_traffic("serve-chat")
    assert (chat["job"], chat["clients"], chat["block"]) == (
        "serve_state_space", 128, 16)
    assert chat["prompt_len"] == {"dist": "log_uniform", "min": 64,
                                  "max": 1024}
    assert chat["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert chat["engine"] == {
        "slots": 128, "page_size": 64, "max_context": 1536,
        "prefill_buckets": [256, 512, 1024], "prefix_cache": False,
        "max_queue": 256, "deadline_s": 120}
    gen = traffic_gen.load_traffic("serve-generate")
    assert (gen["job"], gen["clients"], gen["block"]) == ("serve", 16, 16)
    assert gen["engine"]["prefill_buckets"] == [128]
    assert (gen["engine"]["slots"], gen["engine"]["max_context"]) == (16, 512)


def test_the_count_by_hand(cfg):
    f = flops_jamba
    assert f.n_layers(cfg) == (26, 2)
    assert f.mamba_macs(cfg) == 41_123_840
    assert f.attention_macs(cfg) == 13_762_560
    assert f.ffn_macs(cfg) == 62_914_560
    assert f.token_macs(cfg) == 2_858_352_640
    assert f.head_macs(cfg) == 167_772_160
    assert f.scan_flops(cfg) == 26 * 9 * 5120 * 16 == 19_169_280
    assert f.pair_flops(cfg) == 10_240
    assert f.parameter_count(cfg) == 3_029_337_472
    assert f.state_bytes_per_slot(cfg) == 9_318_400
    assert f.kv_bytes_per_token(cfg) == 1_024
    assert f.weight_bytes(cfg) == 2 * (3_029_337_472 + 167_772_160)
    assert f.prompt_flops(cfg, 512) == pytest.approx(2.940e12, rel=1e-3)
    assert f.decode_flops(cfg, 700) == pytest.approx(6.086e9, rel=1e-3)
    assert f.serve_forward_flops(cfg, [512], [700, 700]) == pytest.approx(
        f.prompt_flops(cfg, 512) + 2 * f.decode_flops(cfg, 700))
    assert f.state_step_bytes(cfg, 128) == 2 * 128 * 9_318_400


def test_the_reference_layer_by_layer_is_the_reference_whole(cfg):
    toy = {**cfg, **dict(
        hidden_size=48, intermediate_size=96, num_attention_heads=6,
        num_key_value_heads=1, head_dim=8, mamba_dt_rank=3, mamba_d_state=4,
        num_hidden_layers=3, attn_layer_period=3, attn_layer_offset=1,
        vocab_size=61, torch_dtype="float32", initializer_range=0.2)}
    ids = [np.random.default_rng(i).integers(0, 61, 37) for i in range(2)]
    w = ref.make_weights(toy, 5)
    hidden = ref.hidden_states(toy, 5, ids, ("f32", "bf16"))
    head_w, head_b = ref.head_leaves(toy, 5)
    for i, seq in enumerate(ids):
        for p in ("f32", "bf16"):
            whole = np.asarray(ref.forward(w, seq, toy, p))
            parts = np.asarray(ref.logits_in_blocks(hidden[p][i], head_w,
                                                    head_b, p))
            assert np.abs(whole - parts).max() < 1e-5
    # causal and recurrent forward in time: padding behind a sequence is
    # never seen
    padded = np.concatenate([ids[0], np.zeros(11, np.int64)])
    long = np.asarray(ref.forward(w, padded, toy))[:37]
    assert np.abs(long - np.asarray(ref.forward(w, ids[0], toy))).max() < 1e-5


# --------------------------------------------------------------- the readers
def _row(name, path, fused=None, consumer=""):
    from deeplearning4j_tpu.observability.recompile import ScopeRow

    return ScopeRow(name, "f32[4]{0}", "fusion", path, fused or {}, consumer)


def test_the_state_space_readers_on_a_hand_made_trace(monkeypatch):
    from deeplearning4j_tpu.observability import recompile

    rows = (_row("fusion.1", "layer_1/recurrent/ssm_scan",
                 {"layer_1/recurrent/ssm_scan": (5, 0)}),
            _row("fusion.2", "layer_1/recurrent/ssm_proj",
                 {"layer_1/recurrent/ssm_proj": (2, 1),
                  "layer_1/norm": (9, 0)}),
            _row("fusion.3", "layer_2/ffn", {"layer_2/ffn": (3, 2)}),
            _row("copy.4", "", {}, consumer="fusion.1"))
    scopes = recompile.ProgramScopes("generation.decode", "jit_decode_step",
                                     rows)
    monkeypatch.setattr(recompile, "registered_programs",
                        lambda: ["generation.decode"])
    monkeypatch.setattr(recompile, "program_scopes", lambda name: scopes)
    from benchmark.trace_reduce import short_name

    def ev(row, start, dur):
        return Event(short_name(f"%{row.name} = {row.shape} {row.opcode}("),
                     start, start + dur)

    ops = [ev(rows[0], 10, 40), ev(rows[1], 60, 20), ev(rows[2], 90, 30),
           ev(rows[3], 130, 10), ev(rows[0], 210, 40)]
    mods = [Event("jit_decode_step(1)", 0, 150),
            Event("jit_decode_step(1)", 200, 300)]
    trace = Reduced((0, 1000), {"/device:TPU:0": ops}, {"/device:TPU:0": mods},
                    [])
    ctx = argparse.Namespace(trace=trace, obs={})
    total, runs = _state_space.seconds(ctx, "decode")
    assert runs == 2 and total == pytest.approx((40 + 20 + 10 + 40) / 1e9)
    scan, _ = _state_space.seconds(ctx, "decode", ("ssm_scan", "ssm_conv"))
    assert scan == pytest.approx((40 + 10 + 40) / 1e9)
    assert _state_space.seconds(ctx, "prefill") is None
    reader = harness.load_reader("state_space_ms_per_decode_step")
    assert reader.read(ctx) == pytest.approx(1e3 * 110e-9 / 2)


# ---------------------------------------------------------------- the faults
def args_for(cell, seed=2147483659):
    return argparse.Namespace(workload=cell, seed=seed, seconds=1.0, trace=0,
                              rehearsal=True, describe=None)


@pytest.mark.parametrize("fault", ["token_altered", "state_not_reset",
                                   "padding_advances_state"])
def test_a_broken_timed_path_is_not_correct(fault):
    line = harness.run(args_for(CHAT), fault=fault)
    assert line["would_be_correct"] is False, line["compared"]


def test_a_sound_run_is_correct_and_its_control_is_not():
    _, _, job, ctx = harness.make_context(args_for(CHAT, seed=4000000007))
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

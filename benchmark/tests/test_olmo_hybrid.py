"""``olmo-hybrid-7b-pp4``'s yardstick: the
manifest's new entries against their files, the configuration file against
the catalog row's keys, ``flops_olmo_hybrid`` against the count by hand in
its docstring (2,436 M parameters), ``reference_olmo_hybrid`` layer by layer
against itself whole at a toy size, the readers of the delta-rule layers'
device time and of its paged kernel against a hand-made table and trace,
the rehearsal of the new cell and — with the timed path broken
underneath, once a fault — ``correct`` false.

Run by hand with the rest: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import flops_olmo_hybrid, reference_olmo_hybrid as ref
from benchmark import run as harness
from benchmark.metrics import _linear_attention
from benchmark.trace_reduce import Event, Reduced

ROOT = harness.ROOT
THINK = "olmoh.serve-think"
LINEAR, FULL = "linear_attention", "full_attention"
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT,
                           "benchmark/configs/olmo-hybrid-7b-pp4.json")) as f:
        return json.load(f)


def test_the_manifests_new_entries_resolve(cfg):
    m = harness.load_manifest()
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[THINK]["chips"] == 1
    assert cells[THINK]["config"] == cfg["name"] == "olmo-hybrid-7b-pp4"
    entry = harness.find(m["configs"], cfg["name"], "configuration")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "layer_types"]
    assert entry["source"] == cfg["source"]
    reported = {x["name"] for kind in ("end_to_end", "per_layer")
                for x in harness.metrics_of_cell(m, kind, THINK)}
    assert {"serve_tokens_per_s", "setup_s", "serve_step_mfu.olmoh",
            "delta_state_roofline", "delta_chunk_roofline",
            "linear_attention_ms_per_decode_step",
            "linear_attention_prefill_share", "state_slots_in_use",
            "paged_attention_roofline.olmoh", "window_compiles.serve",
            "device_idle_share.serve"} <= reported
    assert not {"serve_step_mfu", "state_step_roofline",
                "paged_attention_roofline"} & reported
    for name in reported - {"serve_tokens_per_s", "setup_s"}:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py")), name
    with open(os.path.join(ROOT, "benchmark", "limits",
                           THINK + ".json")) as f:
        assert json.load(f)["cell"] == THINK


def test_the_configuration_holds_the_published_keys_unchanged(cfg):
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == [LINEAR, LINEAR, LINEAR, FULL] * 2
    for key in ("source", "reduced_why", "assumed", "deployment"):
        assert cfg[key]


def test_the_traffic_is_the_cells_as_issued():
    from benchmark import traffic_gen

    think = traffic_gen.load_traffic("serve-think")
    assert (think["job"], think["clients"], think["block"]) == (
        "serve_linear_attention", 128, 16)
    assert think["prompt_len"] == {"dist": "log_uniform", "min": 64,
                                   "max": 512}
    assert think["output_len"] == {"dist": "uniform", "min": 256,
                                   "max": 1024}
    assert think["engine"] == {
        "slots": 128, "page_size": 64, "max_context": 1536,
        "prefill_buckets": [256, 512], "prefix_cache": False,
        "max_queue": 256, "deadline_s": 120}


def test_the_count_by_hand(cfg):
    f = flops_olmo_hybrid
    assert f.n_layers(cfg) == (6, 2)
    assert f.linear_macs(cfg) == 88_704_000
    assert f.full_macs(cfg) == 58_982_400
    assert f.ffn_macs(cfg) == 126_812_160
    assert f.token_macs(cfg) == 1_664_686_080
    assert f.head_macs(cfg) == 385_351_680
    assert f.delta_flops(cfg) == 6 * 7 * 30 * 96 * 192 == 23_224_320
    assert f.pair_flops(cfg) == 15_360
    assert f.parameter_count(cfg) == 2_435_748_072
    assert f.delta_state_bytes_per_slot(cfg) == 6 * 2_211_840
    assert f.state_bytes_per_slot(cfg) == 13_685_760
    assert f.kv_bytes_per_token(cfg) == 30_720
    assert f.weight_bytes(cfg) == 2 * 2_435_748_072
    assert f.prompt_flops(cfg, 512) == pytest.approx(1.7213e12, rel=1e-4)
    assert f.decode_flops(cfg, 700) == pytest.approx(4.1448e9, rel=1e-4)
    assert f.delta_step_bytes(cfg, 128) == 2 * 128 * 6 * 2_211_840
    # whole pages of 64, the two full layers alone: 1 + 1 + 2 + 11 pages
    assert f.kv_bytes_read(cfg, [0, 63, 64, 700], 64) == 15 * 64 * 30_720
    # the chunked form: a 512-token prompt is 8 chunks of 64
    assert f.chunk_flops(cfg, 512) == pytest.approx(
        2 * 6 * 30 * 8 * (2 * 64 * 64 * 96 + 64 * 64 * 288 / 2
                          + 64 * 64 * 192 / 2 + 3 * 64 * 96 * 192))
    assert f.chunk_flops(cfg, 100) == pytest.approx(
        f.chunk_flops(cfg, 64) + f.chunk_flops(cfg, 36, chunk=36))
    assert f.chunk_bytes(cfg, 512) == 6 * (
        512 * 4 * (2 * 2880 + 5760 + 60 + 5760) + 8 * 2 * 2_211_840)


def test_the_reference_layer_by_layer_is_the_reference_whole(cfg):
    toy = {**cfg, **dict(
        hidden_size=48, intermediate_size=96, num_attention_heads=6,
        num_key_value_heads=6, linear_num_key_heads=3,
        linear_num_value_heads=3, linear_key_head_dim=8,
        linear_value_head_dim=12, num_hidden_layers=3,
        layer_types=[LINEAR, FULL, LINEAR], vocab_size=61,
        torch_dtype="float32", initializer_range=0.2)}
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


def test_the_linear_attention_readers_on_a_hand_made_trace(monkeypatch):
    from deeplearning4j_tpu.observability import recompile

    rows = (_row("fusion.1", "layer_1/recurrent/gdn_state",
                 {"layer_1/recurrent/gdn_state": (5, 0)}),
            _row("fusion.2", "layer_1/recurrent/gdn_proj",
                 {"layer_1/recurrent/gdn_proj": (2, 1),
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
    total, runs = _linear_attention.seconds(ctx, "decode")
    assert runs == 2 and total == pytest.approx((40 + 20 + 10 + 40) / 1e9)
    state, _ = _linear_attention.seconds(ctx, "decode", ("gdn_state",))
    assert state == pytest.approx((40 + 10 + 40) / 1e9)
    assert _linear_attention.seconds(ctx, "prefill") is None
    reader = harness.load_reader("linear_attention_ms_per_decode_step")
    assert reader.read(ctx) == pytest.approx(1e3 * 110e-9 / 2)
    assert ctx.obs["notes"]["linear_attention_decode_ms"] == {
        "gdn_proj": pytest.approx(1e3 * 20e-9 / 2, abs=1e-4),
        "gdn_state": pytest.approx(1e3 * 90e-9 / 2, abs=1e-4)}


def test_the_readers_are_silent_on_a_state_space_program(monkeypatch):
    """A program whose recurrent kind is Jamba's (no delta-rule scope):
    nothing to read, nothing raised."""
    from deeplearning4j_tpu.observability import recompile

    rows = (_row("fusion.1", "layer_1/recurrent/ssm_scan",
                 {"layer_1/recurrent/ssm_scan": (5, 0)}),)
    scopes = recompile.ProgramScopes("generation.decode", "jit_decode_step",
                                     rows)
    monkeypatch.setattr(recompile, "registered_programs",
                        lambda: ["generation.decode"])
    monkeypatch.setattr(recompile, "program_scopes", lambda name: scopes)
    trace = Reduced((0, 1000), {"/device:TPU:0": [Event("fusion.1", 0, 5)]},
                    {"/device:TPU:0": [Event("jit_decode_step(1)", 0, 9)]},
                    [])
    ctx = argparse.Namespace(trace=trace, obs={"t0": 0.0, "t1": 1.0,
                                               "records": []})
    for name in ("linear_attention_ms_per_decode_step",
                 "linear_attention_prefill_share", "delta_state_roofline",
                 "delta_chunk_roofline"):
        assert harness.load_reader(name).read(ctx) is None, name


def test_the_paged_kernels_roofline_counts_the_full_layers_pages(cfg):
    """Only ``fused_paged_attention`` inside the decode program counts, and
    its bytes are the two full layers' whole pages."""
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ops = [Event("fused_paged_attention.3", 10, 5010),
           Event("fused_paged_attention.3", 20010, 23010),
           Event("fused_paged_attention.7", 40010, 49010),   # a prefill's
           Event("fusion.9", 5010, 9010)]
    mods = [Event("jit_decode_step(1)", 0, 10000),
            Event("jit_decode_step(1)", 20000, 30000),
            Event("jit_prefill_256(1)", 40000, 50000)]
    trace = Reduced((0, 10**6), {"/device:TPU:0": ops},
                    {"/device:TPU:0": mods}, [])
    record = argparse.Namespace(prompt_len=100, times=[0.5, 0.6, 0.7, 2.0])
    ctx = argparse.Namespace(
        trace=trace, config=cfg, peaks=peaks,
        traffic={"engine": {"page_size": 64}},
        obs={"t0": 0.0, "t1": 1.0, "records": [record]})
    reader = harness.load_reader("paged_attention_roofline.olmoh")
    nbytes = 2 * 2 * 64 * 30_720          # positions 100, 101: 2 pages each
    assert reader.read(ctx) == pytest.approx(
        100 * nbytes / 819e9 / (8000 / 1e9))
    ctx.trace = Reduced((0, 10**6), {"/device:TPU:0": ops[3:]},
                        {"/device:TPU:0": mods}, [])
    assert reader.read(ctx) is None


# ---------------------------------------------------------------- the faults
def args_for(cell, seed=2147483659):
    return argparse.Namespace(workload=cell, seed=seed, seconds=1.0, trace=0,
                              rehearsal=True, describe=None)


@pytest.mark.parametrize("fault", ["token_altered", "state_not_reset",
                                   "padding_advances_state"])
def test_a_broken_timed_path_is_not_correct(fault):
    line = harness.run(args_for(THINK), fault=fault)
    assert line["would_be_correct"] is False, line["compared"]


def test_a_sound_run_is_correct_and_its_control_is_not():
    _, _, job, ctx = harness.make_context(args_for(THINK, seed=4000000007))
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

"""``--rehearsal`` of each job at toy size on the CPU: the last line cannot
be taken for a result; the reference agrees with ``model.py``'s network in
float32; and, with the timed path broken underneath, ``correct`` comes out
false — once for each fault a cell can have.  The control (the reference at
the precision below bfloat16) comes out not correct too.

Needs no chip and describes no topology.  Run by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import argparse
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run as harness

CELLS = [w["name"] for w in harness.load_manifest()["workloads"]]
TRAIN = "sc2-3b.train-4k"
SERVE = "sc2-7b.serve-complete"


def args_for(cell, seed=2147483659, seconds=1.0, trace=0):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, rehearsal=True, describe=None)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line_is_no_result(cell):
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0", "--rehearsal"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert not {"correct", "metrics", "device"} & set(line)
    assert list(line)[-1] == "compared"
    assert "compared" in out.stderr.strip().splitlines()[-1]
    assert line["would_be_correct"] is True, line["compared"]


def test_without_a_tpu_there_is_no_result():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_reference_agrees_with_the_network_in_float32():
    import jax
    import jax.numpy as jnp

    from benchmark import model, reference

    cfg = dict(hidden_size=64, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=97, hidden_act="gelu_pytorch_tanh",
               norm_epsilon=1e-5, use_bias=True, rope_theta=10000.0,
               sliding_window=4096, torch_dtype="float32",
               initializer_range=0.2)
    seed = 2**31 + 7
    net = model.build_network(cfg, max_seq=32, lr=1e-3)
    model.install_weights(net, reference.make_weights(cfg, seed), 2, True)
    ids = np.random.default_rng(0).integers(0, 97, (2, 33))
    x, y = ids[:, :-1], ids[:, 1:]
    items = reference.cfg_items(cfg)
    w = reference.make_weights(cfg, seed)
    want = jax.nn.softmax(reference.logits_of(w, x, items), -1)
    assert np.abs(np.asarray(net.output(x)) - want).max() < 1e-5
    net.fit(jnp.asarray(x), jax.nn.one_hot(y, 97))
    zeros = lambda: {k: jnp.zeros_like(a) for k, a in w.items()}
    new, _, _, loss, gnorm = reference.train_step(
        w, zeros(), zeros(), jnp.asarray(0), x, y, items, 1e-3)
    assert abs(float(net.score_value) - float(loss)) < 1e-3 * float(loss)
    got = model.flat_leaves(net.params, 2)
    for k in got:
        assert np.abs(np.asarray(got[k]) - np.asarray(new[k])).max() < 1e-5, k
    m = model.flat_leaves(net.updater_state["m"], 2)
    for k in m:
        norm = float(jnp.linalg.norm(m[k])) / (1 - reference.BETA1)
        assert abs(norm - float(gnorm[k])) <= 1e-4 * float(gnorm[k]) + 1e-9, k


@pytest.mark.parametrize("cell,fault", [
    (TRAIN, "state_unchanged"), (TRAIN, "half_batch"),
    (SERVE, "token_altered")])
def test_a_broken_timed_path_is_not_correct(cell, fault):
    line = harness.run(args_for(cell), fault=fault)
    assert line["would_be_correct"] is False, line["compared"]


def test_sound_runs_are_correct_in_process():
    for cell in CELLS:
        line = harness.run(args_for(cell, seed=97))
        assert line["would_be_correct"] is True, line["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    """The reference at fp8, put in the program's place, fails one of the
    cell's numbers at its limit."""
    from benchmark import calibrate  # noqa: F401  (same code path as the chip's)

    _, c, job, ctx = harness.make_context(args_for(cell, seed=4000000007))
    try:
        job.setup(ctx)
        job.window(ctx, 1.0)
    finally:
        job.release(ctx)
    readings = job.calibrate(ctx, with_control=True)
    control = readings["control_fp8"]
    assert any(control[name] > limit for name, limit in ctx.limits.items()), \
        (control, ctx.limits)

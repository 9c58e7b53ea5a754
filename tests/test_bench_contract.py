"""The bench's driver contract: the LAST stdout line must be compact,
parseable JSON under the driver's ~2 KB tail-capture window (a payload
once outgrew it and could not be parsed, so the contract is pinned by
test).

Two tiers: a cheap unit test of ``emit_result`` (always runs, with a
deliberately bloated payload), and a full-bench subprocess integration
test gated behind ``DL4J_BENCH_TEST=1`` (minutes of CPU)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(REPO, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _fake_full(n_metrics=8):
    # every metric padded with the spread/variant bulk that once
    # overflowed the line
    metrics = []
    for i in range(n_metrics):
        metrics.append({
            "metric": f"Metric number {i} (d1024 L8 T2048, flash attention)",
            "value": 123456.7 + i,
            "unit": "tokens/sec",
            "vs_baseline": None,
            "spread": {"reps": 3, "rep_ms": [1.0, 2.0, 3.0] * 10},
            "variants": {f"v{j}": {"tokens_per_sec": j, "per_token_ms": j,
                                   "spread": {"rep_ms": [0.1] * 12}}
                         for j in range(4)},
        })
    return {
        "metric": metrics[0]["metric"], "value": metrics[0]["value"],
        "unit": metrics[0]["unit"], "vs_baseline": 1.23, "mfu": 0.68,
        "platform": "tpu", "device_kind": "TPU v5 lite",
        "peak_flops": 197e12, "baseline_source": "baseline_cpu.json",
        "all": metrics,
        "errors": ["x" * 400, "y" * 400, "z" * 400],
    }


def test_emit_line_is_compact_and_parseable(tmp_path):
    line = bench.emit_result(_fake_full(), out_dir=str(tmp_path))
    assert len(line) < 1500
    head = json.loads(line)
    for field in ("metric", "value", "unit", "vs_baseline", "mfu",
                  "platform", "device_kind"):
        assert field in head, f"missing driver field {field}"
    assert head["platform"] == "tpu"
    # the full payload round-trips from the file
    with open(tmp_path / "bench_full.json") as f:
        full = json.load(f)
    assert len(full["all"]) == 8


def test_emit_line_never_exceeds_window_even_when_huge(tmp_path):
    full = _fake_full(n_metrics=40)  # summary alone would blow the window
    for m in full["all"]:
        m["metric"] = "Very long metric name " * 8 + m["metric"]
    full["metric"] = full["all"][0]["metric"]
    line = bench.emit_result(full, out_dir=str(tmp_path))
    assert len(line) <= 1500
    json.loads(line)  # shrunk by dropping FIELDS — still valid JSON


def test_emit_survives_unwritable_out_dir(tmp_path):
    line = bench.emit_result(_fake_full(),
                             out_dir=str(tmp_path / "no" / "such" / "dir"))
    head = json.loads(line)
    assert "full_write_error" in head
    assert head["value"] == _fake_full()["value"]


@pytest.mark.skipif(os.environ.get("DL4J_BENCH_TEST") != "1",
                    reason="full CPU bench takes minutes; set DL4J_BENCH_TEST=1")
def test_full_bench_subprocess_contract():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=1800, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    last = lines[-1]
    assert len(last) < 2000, f"headline line is {len(last)} chars"
    head = json.loads(last)
    assert head["platform"] in ("cpu", "tpu")
    with open(os.path.join(REPO, "bench_full.json")) as f:
        full = json.load(f)
    assert not full.get("errors"), full.get("errors")
    by_name = {m["metric"]: m for m in full["all"]}
    lenet = next(m for n, m in by_name.items() if n.startswith("LeNet"))
    # the dispatch-floor fix is measured, not just built
    assert lenet["scanned_k"] >= 16 and lenet["scanned_step_ms"] > 0
    decode = next(m for n, m in by_name.items() if n.startswith("Decode"))
    # the KV cache is big enough to mean something
    assert decode["variants"]["mha"]["kv_cache_mb"] >= 10

"""BENCHMARK.json against the files it names and the contract's limits."""

import json
import os
import re

import pytest

from benchmark import run as harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    cells = len(manifest["workloads"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, cells // 4)
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in manifest[group]]
    for w in manifest["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for n in names:
        assert NAME.match(n), n
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def test_everything_named_resolves(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = set()
    for w in manifest["workloads"]:
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert cfg["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        assert body["source"] == cfg["source"]
        for key in cfg["reduced"]:
            assert key in body and key in body["reduced_why"], key
            assert not key.endswith(("_dim", "_rank", "_size")), key
        traffic = os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            job = json.load(f)["job"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "jobs", job + ".py"))
        assert os.path.exists(os.path.join(ROOT, "benchmark", "limits", w["name"] + ".json"))
    assert used == set(configs)
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".py")), m["name"]


def test_layer_metrics_move_what_their_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for cell in cells:
        reported = [n for n, ws in e2e.items() if cell in ws]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_of_cell(manifest, "per_layer", cell)
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    # every kernel roofline that moves a metric has an mfu beside it
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            for cell in m["workloads"]:
                assert any("mfu" in o["name"] and cell in o["workloads"]
                           and e2e_of(manifest, o, cell)
                           for o in manifest["per_layer"]), m["name"]


def e2e_of(manifest, metric, cell):
    return metric["moves"] in [m["name"] for m in
                               harness.metrics_of_cell(manifest, "end_to_end", cell)]


def test_files_under_paths_are_named_from_allowed_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), ROOT))


def test_flops_agree_with_the_count_by_hand():
    """flops.py against its docstring's count for starcoder2-3b at 3 layers,
    so that train_step_mfu cannot drift silently."""
    from benchmark import flops, model, reference

    cfg = model.load_config(os.path.join(ROOT, "benchmark/configs/starcoder2-3b.json"))
    assert flops.layer_matmul_params(cfg) == 95_944_704
    assert flops.matmul_params(cfg) == 438_829_056
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == pytest.approx(6 * 438_829_056 + 3 * 75_497_472 * (4097 / 4096))
    assert per_token == pytest.approx(2.860e9, rel=1e-3)
    layer = {k: v for k, v in reference.leaf_shapes(cfg).items() if k.startswith("L0.")}
    n = 0
    for shape in layer.values():
        size = 1
        for d in shape:
            size *= d
        n += size
    assert n == 95_979_008
    with pytest.raises(KeyError):
        flops.peaks_for("TPU v9 imaginary")

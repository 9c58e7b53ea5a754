"""Memory & collective-communication observability (`observability/
shardstats.py`): HLO collective census, sharding ledger, comm roofline.

Acceptance oracles from the PR issue:

- analytic oracle: for K-replica data parallel on the virtual CPU mesh,
  HLO-counted all-reduce bytes per step == parameter(+averaged updater)
  bytes within dtype/fusion tolerance, and the ledger's updater-state
  replication factor == K;
- pipeline master's per-stage ledger sums to the single-device total;
- on a 4-replica ParallelWrapper run: ≥1 all-reduce censused, zero
  extra recompiles in steady state, `GET /memory` serves the ledger;
- flight-recorder dumps carry a `sharding_ledger` record;
- the per-dispatch hook cost is bounded.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.observability import shardstats
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability.shardstats import (
    ShardStatsCollector, attribute_mesh_axes, collective_census,
    format_ledger, link_bandwidth_for, program_analysis, record_ledger,
    ring_wire_bytes, sharding_ledger,
)


def param_bytes(tree, itemsize=4):
    return sum(int(np.asarray(l).size) * itemsize
               for l in jax.tree_util.tree_leaves(tree))


def dense_net(n_in=12, hidden=32, n_out=4, updater="adam", seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater, learning_rate=0.01).list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def class_data(n, n_in=12, n_out=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rs.randint(0, n_out, n)]
    return DataSet(x, y)


# ---------------------------------------------------------------- census unit
def test_census_counts_and_sizes_ops():
    hlo = """
  %all-reduce = f32[16,8]{1,0} all-reduce(f32[16,8]{1,0} %dot), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add
  %all-reduce.1 = f32[] all-reduce(f32[] %b), channel_id=2, replica_groups=[1,4]<=[4], to_apply=%add
  %ag = f32[32,8]{1,0} all-gather(f32[8,8]{1,0} %x), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[8,8]{1,0} reduce-scatter(f32[32,8]{1,0} %y), channel_id=4, replica_groups=[1,4]<=[4], to_apply=%add
  %cp = f32[4,4]{1,0} collective-permute(f32[4,4]{1,0} %z), channel_id=5, source_target_pairs={{0,1},{1,0}}
  ROOT %fused = f32[16,8]{1,0} fusion(f32[16,8]{1,0} %all-reduce, f32[8,8]{1,0} %rs), kind=kLoop
"""
    census = collective_census(hlo)
    assert census["all-reduce"]["count"] == 2
    assert census["all-reduce"]["bytes"] == 16 * 8 * 4 + 4
    assert census["all-reduce"]["group_sizes"] == [4]
    # all-gather payload is the GATHERED tensor (result > operand)
    assert census["all-gather"]["bytes"] == 32 * 8 * 4
    assert census["all-gather"]["group_sizes"] == [4]   # explicit groups
    # reduce-scatter payload is the PRE-scatter tensor (operand > result)
    assert census["reduce-scatter"]["bytes"] == 32 * 8 * 4
    assert census["collective-permute"]["bytes"] == 4 * 4 * 4
    # the fusion line referencing %all-reduce must NOT count
    assert sum(e["count"] for e in census.values()) == 5


def test_census_async_start_counts_once_without_double_bytes():
    hlo = """
  %ar-start = (f32[256]{0}, f32[256]{0}) all-reduce-start(f32[256]{0} %g), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
  %ar-done = f32[256]{0} all-reduce-done((f32[256]{0}, f32[256]{0}) %ar-start)
"""
    census = collective_census(hlo)
    assert census["all-reduce"]["count"] == 1
    assert census["all-reduce"]["bytes"] == 256 * 4   # not 2x
    assert census["all-reduce"]["group_sizes"] == [8]


def test_census_tpu_tiled_layouts_and_variadic_tuples():
    """Post-layout TPU HLO carries tile annotations with parens inside
    the layout braces and fuses logical all-reduces into variadic ops
    with tuple results — both must still be counted."""
    hlo = """
  %fused-ar = (f32[1024]{0:T(1024)}, f32[512]{0:T(512)}) all-reduce(f32[1024]{0:T(1024)} %a, f32[512]{0:T(512)} %b), replica_groups=[1,4]<=[4], to_apply=%add
  %ar-start = (f32[256]{0:T(256)}, f32[256]{0:T(256)}) all-reduce-start(f32[256]{0:T(256)} %g), replica_groups=[1,8]<=[8], to_apply=%add
  %tiled = f32[8,128]{1,0:T(8,128)} all-gather(f32[2,128]{1,0:T(8,128)} %x), replica_groups=[1,4]<=[4], dimensions={0}
"""
    census = collective_census(hlo)
    assert census["all-reduce"]["count"] == 2
    # variadic: tuple result = sum of both payloads; -start: one payload
    assert census["all-reduce"]["bytes"] == (1024 + 512) * 4 + 256 * 4
    assert census["all-gather"]["bytes"] == 8 * 128 * 4
    assert sorted(census["all-reduce"]["group_sizes"]) == [4, 8]


def test_census_dtype_sizes_and_empty():
    hlo = "%ar = bf16[10]{0} all-reduce(bf16[10]{0} %g), replica_groups=[1,2]<=[2]"
    assert collective_census(hlo)["all-reduce"]["bytes"] == 20
    assert collective_census("ROOT %r = f32[8]{0} add(...)") == {}


def test_attribute_mesh_axes():
    census = {"all-reduce": {"count": 1, "bytes": 4, "group_sizes": [4]},
              "all-gather": {"count": 1, "bytes": 4, "group_sizes": [2]}}
    attr = attribute_mesh_axes(census, {"data": 4, "model": 2})
    assert attr == {"all-reduce": ["data"], "all-gather": ["model"]}
    # ambiguous sizes stay unattributed
    attr = attribute_mesh_axes(census, {"data": 4, "model": 4})
    assert attr["all-reduce"] == []


def test_ring_wire_bytes_recipe():
    assert ring_wire_bytes("all-reduce", 100.0, 4) == pytest.approx(150.0)
    assert ring_wire_bytes("all-gather", 100.0, 4) == pytest.approx(75.0)
    assert ring_wire_bytes("reduce-scatter", 100.0, 4) == pytest.approx(75.0)
    assert ring_wire_bytes("collective-permute", 100.0, 4) == 100.0
    assert ring_wire_bytes("all-reduce", 100.0, None) == 100.0  # lower bound


def test_link_bandwidth_sources():
    bw, src = link_bandwidth_for()
    assert src in ("table", "cpu-estimate")
    assert bw > 0

    class FakeTPU:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    bw, src = link_bandwidth_for(FakeTPU())
    assert (bw, src) == (shardstats.LINK_BANDWIDTH["TPU v5 lite"], "table")

    class UnknownTPU:
        device_kind = "TPU v5 something"   # a prefix match is not a match
        platform = "tpu"

    with pytest.raises(ValueError, match="no link bandwidth on record"):
        link_bandwidth_for(UnknownTPU())


# -------------------------------------------------------- program analysis
def test_program_analysis_counts_grad_allreduce_exactly():
    """The canonical DP shape: replicated params, sharded batch — the
    gradient all-reduce payload must equal the parameter bytes."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def loss(params, x):
        return jnp.mean((jnp.tanh(x @ params["w1"]) @ params["w2"]) ** 2)

    def step(params, x):
        g = jax.grad(loss)(params, x)
        return jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, params, g)

    f = jax.jit(step, in_shardings=({"w1": repl, "w2": repl}, data))
    params = jax.device_put({"w1": jnp.zeros((8, 16), jnp.float32),
                             "w2": jnp.zeros((16, 4), jnp.float32)},
                            {"w1": repl, "w2": repl})
    x = jax.device_put(jnp.zeros((16, 8), jnp.float32), data)
    analysis = program_analysis(f, (params, x), {})
    pb = (8 * 16 + 16 * 4) * 4
    assert analysis["collectives"]["all-reduce"]["bytes"] == pb
    assert analysis["collectives"]["all-reduce"]["group_sizes"] == [4]
    assert analysis["memory"]["argument"] > 0
    assert analysis["flops"] > 0


def test_program_analysis_preserves_argument_shardings():
    """A jit WITHOUT in_shardings gets its layout from the arguments —
    absifying must carry the NamedSharding or the partitioner compiles a
    collective-free single-device program."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    data = NamedSharding(mesh, P("data"))
    f = jax.jit(lambda a: jnp.sum(a, 0))        # cross-device reduction
    a = jax.device_put(jnp.ones((4, 64)), data)
    analysis = program_analysis(f, (a,), {})
    assert analysis.get("collective_bytes", 0) > 0


def test_program_analysis_never_executes_or_consumes(monkeypatch):
    """Donation safety: analysis lowers abstractly, so a donated-argnums
    jit can be analyzed and then still dispatched with the same arrays."""
    f = jax.jit(lambda a: a + 1.0, donate_argnums=(0,))
    a = jnp.ones((32,))
    analysis = program_analysis(f, (a,), {})
    assert analysis["memory"]["argument"] == 32 * 8 or \
        analysis["memory"]["argument"] == 32 * 4   # x64 on/off
    out = f(a)   # the buffer is still live — analysis did not consume it
    assert float(out[0]) == 2.0


# ------------------------------------------------------------------- ledger
def test_ledger_replicated_vs_sharded_vs_stacked():
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    replicated = jax.device_put(jnp.zeros((8, 8), jnp.float32), repl)
    sharded = jax.device_put(jnp.zeros((8, 8), jnp.float32), data)
    led = sharding_ledger({"r": {"w": replicated}, "s": {"w": sharded}},
                          data_axis_size=4)
    r, s = led["trees"]["r"], led["trees"]["s"]
    assert r["replication_factor"] == 4.0
    assert r["per_device_bytes"] == 256
    assert s["replication_factor"] == 1.0
    assert s["per_device_bytes"] == 64
    # ZeRO projection: replicated tree would drop to logical/K per device
    assert r["zero_projected_per_device_bytes"] == 64
    assert r["zero_savings_per_device_bytes"] == 256 - 64
    # stacked replica view measured against the logical single tree
    stacked = jax.device_put(jnp.zeros((4, 8, 8), jnp.float32), data)
    led = sharding_ledger({"u": stacked},
                          logical_trees={"u": jnp.zeros((8, 8),
                                                        jnp.float32)},
                          data_axis_size=4)
    assert led["trees"]["u"]["replication_factor"] == 4.0
    # subtree rows ride along for dict trees
    led = sharding_ledger({"params": {"l0": {"w": replicated},
                                      "l1": {"w": sharded}}})
    subs = led["trees"]["params"]["subtrees"]
    assert subs["l0"]["replication_factor"] == 4.0
    assert subs["l1"]["replication_factor"] == 1.0


def test_ledger_handles_host_arrays_and_non_arrays():
    led = sharding_ledger({"params": {"w": np.zeros((4, 4), np.float32),
                                      "flag": True, "name": "x"}})
    row = led["trees"]["params"]
    assert row["logical_bytes"] == 64
    assert row["replication_factor"] == 1.0


def test_format_ledger_is_readable():
    led = sharding_ledger({"params": {"w": np.zeros((64, 64), np.float32)}},
                          data_axis_size=4)
    txt = format_ledger(led, "unit")
    assert "sharding ledger — unit" in txt
    assert "params" in txt and "TOTAL" in txt


def test_record_ledger_sets_gauges_and_flight_event():
    from deeplearning4j_tpu.observability.flightrecorder import (
        get_flight_recorder,
    )

    reg = MetricsRegistry()
    record_ledger("unit_test", {"params": {"w": np.zeros((8,), np.float32)}},
                  registry=reg)
    snap = reg.to_json()
    vals = {(v["labels"]["component"], v["labels"]["tree"]): v["value"]
            for v in snap["dl4j_sharded_bytes"]["values"]}
    assert vals[("unit_test", "params")] == 32.0
    assert shardstats.latest_ledgers()["unit_test"]["trees"]["params"]
    kinds = [e.kind for e in get_flight_recorder().events()]
    assert "sharding_ledger" in kinds


# ----------------------------------------------------- analytic oracle tests
def test_sync_master_allreduce_bytes_match_param_bytes():
    """K-replica sync DP: the per-step gradient all-reduce must move
    exactly the parameter bytes (within scalar/fusion tolerance)."""
    from deeplearning4j_tpu.backend import device as backend
    from deeplearning4j_tpu.parallel.training_master import (
        DistributedNetwork, SyncTrainingMaster,
    )

    net = dense_net(updater="sgd")
    mesh = backend.default_mesh(data=8)
    with ShardStatsCollector() as coll:
        master = SyncTrainingMaster(mesh=mesh)
        DistributedNetwork(net, master).fit(
            ListDataSetIterator(class_data(64), 16))
        prog = coll.programs()["SyncTrainingMaster.step"]
    census = prog["collectives"]
    assert census["all-reduce"]["count"] >= 1
    pb = param_bytes(net.params)
    # per-leaf grad all-reduces + the scalar loss mean; fusion may merge,
    # padding/scalars may add — bytes must stay within 10% + 1KB slack
    assert pb <= census["all-reduce"]["bytes"] <= pb * 1.1 + 1024
    # replicated params on the 8-way mesh: ledger factor == mesh size
    led = shardstats.latest_ledgers()["sync_master"]
    assert led["trees"]["params"]["replication_factor"] == 8.0


def test_parallel_wrapper_acceptance_4_replicas():
    """The PR acceptance criterion, end to end: 4-replica ParallelWrapper
    — updater replication factor 4, ≥1 all-reduce with bytes matching the
    analytic count, zero extra recompiles in steady state, and the ledger
    served over GET /memory."""
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    from deeplearning4j_tpu.ui.server import UIServer

    net = dense_net(updater="adam")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1, 1),
                ("data", "model", "seq"))
    with ShardStatsCollector() as coll:
        pw = ParallelWrapper(net, workers=4, mesh=mesh,
                             averaging_frequency=1, average_updaters=True)
        pw.fit(ListDataSetIterator(class_data(96, seed=3), 8))
        prog = coll.programs()["ParallelWrapper.fit_window"]

        led = shardstats.latest_ledgers()["parallel_wrapper"]
        assert led["trees"]["updater_state"]["replication_factor"] == 4.0
        assert led["trees"]["params"]["replication_factor"] == 4.0
        assert led["data_axis_size"] == 4

        census = prog["collectives"]
        assert census["all-reduce"]["count"] >= 1
        # the averaging collective moves params + (averaged) Adam moments
        expected = param_bytes(net.params) + param_bytes(net.updater_state)
        assert expected <= census["all-reduce"]["bytes"] \
            <= expected * 1.1 + 1024
        assert census["all-reduce"]["group_sizes"] == [4]
        assert attribute_mesh_axes(
            census, {"data": 4, "model": 1, "seq": 1})["all-reduce"] \
            == ["data"]

        # zero extra recompiles in steady state: one signature for the
        # full windows (a ragged tail window would be a second PLANNED
        # shape, not a recompile-after-warn)
        det = pw._step_fn.detector
        assert det.recompile_count == 0
        assert det.compile_count == 1

        # GET /memory serves the ledger + the per-program census
        server = UIServer()
        port = server.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/memory", timeout=10) as r:
                doc = json.loads(r.read())
        finally:
            server.stop()
        pw_led = doc["ledgers"]["parallel_wrapper"]
        assert pw_led["trees"]["updater_state"]["replication_factor"] == 4.0
        assert doc["programs"]["ParallelWrapper.fit_window"][
            "collective_bytes"] > 0

    # comm roofline populated (CPU estimate, labeled by the gauge source)
    assert prog["comm_seconds_estimate"] > 0
    assert prog["comm_compute_ratio"] is not None


def test_pipeline_per_stage_ledger_sums_to_single_device_total():
    from deeplearning4j_tpu.parallel.pipeline import (
        PipelineParallelTrainingMaster,
    )

    net = dense_net(n_in=16, hidden=24, n_out=4, updater="sgd")
    single_total = param_bytes(net.params)
    master = PipelineParallelTrainingMaster(
        n_stages=2, n_microbatches=2, mode="orchestrated",
        devices=jax.devices()[:2])
    master.execute_training(net, ListDataSetIterator(
        class_data(16, n_in=16), 8))
    led = shardstats.latest_ledgers()["pipeline_master"]
    stage_rows = {k: v for k, v in led["trees"].items()
                  if k.startswith("params_stage")}
    assert len(stage_rows) == 2
    assert sum(r["logical_bytes"] for r in stage_rows.values()) \
        == single_total
    # each stage holds ONLY its share (true pipeline memory win)
    assert all(0 < r["per_device_bytes"] < single_total
               for r in stage_rows.values())


def test_facade_fit_records_ledger():
    shardstats.clear_ledgers()
    net = dense_net()
    ds = class_data(16)
    net.fit(ds.features, ds.labels, epochs=1)
    led = shardstats.latest_ledgers()["MultiLayerNetwork"]
    assert led["trees"]["params"]["logical_bytes"] \
        == param_bytes(net.params)


# ----------------------------------------------------------- flight recorder
def test_flight_dump_includes_sharding_ledger(tmp_path):
    from deeplearning4j_tpu.observability.flightrecorder import (
        dump_flight_report, read_flight_report,
    )

    record_ledger("dump_test",
                  {"params": {"w": np.zeros((16,), np.float32)}})
    path = dump_flight_report(str(tmp_path / "report.jsonl"), "unit")
    records = read_flight_report(path)
    ledgers = [r for r in records if r["record"] == "sharding_ledger"]
    assert len(ledgers) == 1
    assert "dump_test" in ledgers[0]["ledgers"]
    assert ledgers[0]["ledgers"]["dump_test"]["trees"]["params"][
        "logical_bytes"] == 64


# -------------------------------------------------------- generation warmup
@pytest.mark.generation
def test_generation_warmup_records_pools_ledger_and_census():
    from deeplearning4j_tpu.generation.programs import GenerationPrograms
    from deeplearning4j_tpu.models.zoo import transformer_char_lm
    from deeplearning4j_tpu.observability.recompile import RecompileDetector

    net = transformer_char_lm(vocab_size=29, d_model=32, n_heads=4,
                              layers=2, max_cache=64, seed=5)
    shardstats.clear_ledgers()
    with ShardStatsCollector() as coll:
        progs = GenerationPrograms(
            net, slots=2, pages_per_slot=4, page_size=4, num_pages=16,
            prefill_buckets=(8,),
            detector=RecompileDetector("generation.test",
                                       registry=MetricsRegistry()))
        progs.warm()
        collected = coll.programs()
    led = shardstats.latest_ledgers()["generation"]
    assert led["trees"]["kv_pools"]["logical_bytes"] > 0
    assert led["trees"]["params"]["logical_bytes"] > 0
    assert "generation.decode" in collected
    assert "generation.prefill_8" in collected
    # single-device decode: census empty but memory accounting present
    assert collected["generation.decode"]["memory"]["argument"] > 0


# ------------------------------------------------------------ hook overhead
def test_note_dispatch_hot_path_is_cheap():
    """The per-dispatch cost while a collector is installed is an
    identity check + a couple of cached counter increments — bound it
    hard so the hook's cost cannot rot silently."""
    coll = ShardStatsCollector(registry=MetricsRegistry())
    analysis = {"flops": 1e6, "memory": {"argument": 1},
                "collectives": {"all-reduce": {"count": 2, "bytes": 1024,
                                               "group_sizes": [4]}},
                "collective_bytes": 1024.0, "collective_count": 2}
    coll.note_dispatch("fn", analysis)   # slow path once
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        coll.note_dispatch("fn", analysis)
    per_call = (time.perf_counter() - t0) / n
    # generous CI bound: 50 µs/dispatch is still ~0.1% of a 50 ms step
    assert per_call < 50e-6


def test_no_analysis_when_no_collector_installed():
    """Without a collector the instrument seam must not lower/compile
    anything extra: cost_fn stays None and last_cost is None."""
    from deeplearning4j_tpu.observability.recompile import instrument

    assert shardstats.active_collector() is None
    f = instrument(jax.jit(lambda a: a * 2), "shardstats_off_test",
                   registry=MetricsRegistry())
    f(jnp.ones((4,)))
    assert f.detector.last_cost is None


# -------------------------------------------------------- regression rules
def test_doc_scope_rule_holds_a_memory_sentinel():
    """A ``scope="doc"`` rule over the ledger's replication factor (a
    count, not a timing): growth fails, the ZeRO-style drop improves."""
    from deeplearning4j_tpu.observability import regression

    rule = regression.Rule(
        "Memory: updater replication", scope="doc",
        field="observability.memory.sentinels.updater_replication_factor",
        direction=regression.LOWER, tolerance=0.0, required=False)
    base = {"all": [], "observability": {"memory": {"sentinels": {
        "updater_replication_factor": 4.0}}}}
    worse = {"all": [], "observability": {"memory": {"sentinels": {
        "updater_replication_factor": 5.0}}}}
    better = {"all": [], "observability": {"memory": {"sentinels": {
        "updater_replication_factor": 1.0}}}}
    assert regression.compare(base, worse, [rule]).exit_code == 1
    assert regression.compare(base, better,
                              [rule]).verdicts[0].status == "improved"
    # rules survive the JSON round-trip with their scope
    r2 = regression.Rule.from_dict(rule.to_dict())
    assert r2.scope == "doc" and r2.field == rule.field

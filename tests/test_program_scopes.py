"""Device time by the program's own layers, as far as a CPU can show it:
every layer class declares a kind; the compiled programs of a toy net of
each accepted block publish a table in which every matrix product lies
under a kind; the table keeps no array; and the readers built on it
(``benchmark/metrics/_layer_time.py``) split a hand-built trace so that the
parts add up.
"""

import gc
import re
import types
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.metrics import _layer_time
from benchmark.trace_reduce import Event, Reduced
from deeplearning4j_tpu.generation.programs import GenerationPrograms
from deeplearning4j_tpu.nn.layers import base
from deeplearning4j_tpu.nn.layers.composite import _SublayerChain
from deeplearning4j_tpu.observability import recompile

# the program's own scopes beside the layers' kinds: the closed vocabulary
# of docs/observability.md, "Device time by layer"
VOCABULARY = set(base.KINDS) | {
    "loss", "updater", "param_cast", "sample", "mhc_coeffs", "mhc_sinkhorn",
    "mla_attention", "attention_core", "attn_gate", "moe_router",
    "moe_experts", "moe_shared_expert", "ssm_proj", "ssm_conv", "ssm_scan"}
MATMULS = ("dot", "convolution", "custom-call")


# ------------------------------------------------------------ (a) the kinds
@pytest.mark.parametrize("name", sorted(base._LAYER_REGISTRY))
def test_every_registered_layer_class_declares_a_kind(name):
    cls = base._LAYER_REGISTRY[name]
    if issubclass(cls, _SublayerChain):
        # a composite has none: its sublayers carry theirs, its residual
        # adds and mixes fall to ``rest`` or ``mhc_*``
        assert cls.kind is None
    else:
        assert cls.kind in base.KINDS, (name, cls.kind)


def test_a_custom_layer_without_a_kind_enters_no_scope():
    class Plain(base.Layer):
        pass

    with Plain().kind_scope():           # nothing to enter, nothing raised
        pass
    assert Plain.kind is None


@pytest.mark.parametrize("op_name,path", [
    ("jit(step)/transpose(jvp(layer_3))/ffn/dot_general", "layer_3/ffn"),
    ("jit(step)/jvp(layer_3)/ffn/sub1/dot_general", "layer_3/ffn/sub1"),
    ("jit(decode_step)/layer_2/attention/jit(_take)/gather",
     "layer_2/attention"),
    ("jit(f)/jvp(jit(inner))/mul", ""),
    ("jit(f)/vmap(jvp())/mhc_mix/while/body/add", "mhc_mix/while/body"),
    ("jit(f)/layer_7/attention/reshape;jit(f)/layer_7/ffn/reshape",
     "layer_7/attention"),
    ("w", "")])
def test_scope_path_peels_the_transforms(op_name, path):
    assert recompile.scope_path(op_name) == path


# --------------------------------------------- (b) the toy blocks' programs
def _toy_net(family):
    from tests import test_laguna, test_latent_moe, test_xing

    if family == "starcoder2":
        return test_laguna._accepted_toy_net("starcoder2")
    return {"k2": test_latent_moe, "laguna": test_laguna,
            "xing": test_xing}[family].toy_net()[0]


FAMILIES = ("starcoder2", "k2", "laguna", "xing")
# the kinds a family's compute programs must show matrix products under
EXPECTED = {"starcoder2": {"attention", "ffn", "head"},
            "k2": {"attention", "ffn", "experts", "head"},
            "laguna": {"attention", "ffn", "experts", "head"},
            "xing": {"attention", "ffn", "experts", "head"}}


def _programs_of(net):
    progs = GenerationPrograms(net, slots=4, pages_per_slot=6, page_size=8,
                               num_pages=25, prefill_buckets=(16,))
    progs.warm()
    return {k: recompile.program_scopes(f"generation.{k}")
            for k in ("decode", "prefill_16")}


@pytest.fixture(scope="module", params=FAMILIES)
def family_programs(request):
    """(family, {program: ProgramScopes}) of one toy net: ``decode`` and one
    ``prefill_<bucket>`` as ``GenerationPrograms.warm`` registers them."""
    return request.param, _programs_of(_toy_net(request.param))


@pytest.fixture(scope="module")
def trained_programs():
    """The same of the block that trains, and its train step as
    ``instrument()``'s wrapper registers it on its first call.  (The three
    expert blocks are served only: ``RoutedMoELayer``'s sorted groups run
    under a ``fori_loop`` of dynamic length, which has no reverse mode.)"""
    net = _toy_net("starcoder2")
    scopes = _programs_of(net)
    ids = np.random.default_rng(0).integers(0, 97, (2, 13))
    vocab = net.layers[-1].n_out
    net.fit(ids[:, :-1] % vocab,
            np.eye(vocab, dtype=np.float32)[ids[:, 1:] % vocab])
    scopes["train"] = recompile.program_scopes("MultiLayerNetwork.train_step")
    return scopes


def _kind(path):
    return next((w for w in reversed(path.split("/")) if w in VOCABULARY),
                None)


GROUP = {"mla_attention": "attention", "attention_core": "attention",
         "moe_experts": "experts", "moe_router": "experts",
         "moe_shared_expert": "experts"}


def check_program(got, module, expected):
    assert got.module == module
    # a row for every instruction of the compiled module, names unique
    text = recompile._PROGRAMS[got.program].lower().compile().as_text()
    lines = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text, re.M)
    assert [r.name for r in got.rows] == lines
    assert len(set(lines)) == len(lines)
    # no wrapper survives on a path
    assert not any("(" in r.path for r in got.rows)
    # every matrix product whose metadata the CPU compiler kept (it rewrites
    # some dots and drops theirs) lies under a kind
    products = [r for r in got.rows if r.opcode in MATMULS and r.path]
    assert len(products) >= 10
    loose = [(r.name, r.path) for r in products if _kind(r.path) is None]
    assert not loose, loose
    kinds = {_kind(r.path) for r in products}
    assert expected <= {GROUP.get(k, k) for k in kinds}, kinds


@pytest.mark.parametrize("program", ["decode", "prefill_16"])
def test_a_toy_blocks_program_publishes_its_scopes(family_programs, program):
    family, scopes = family_programs
    check_program(scopes[program], {"decode": "jit_decode_step",
                                    "prefill_16": "jit_prefill_16"}[program],
                  EXPECTED[family])


def test_the_train_step_publishes_its_scopes(trained_programs):
    check_program(trained_programs["train"], "jit_step",
                  EXPECTED["starcoder2"])


def test_a_backward_matmul_reads_its_layers_kind(trained_programs):
    """``transpose(jvp(layer))/ffn/dot_general`` is still ``ffn``: the train
    step holds the forward's FFN products and two more for each."""
    def ffn(rows):
        return sum(r.opcode == "dot" and _kind(r.path) == "ffn"
                   for r in rows)
    fwd = ffn(trained_programs["prefill_16"].rows)
    both = ffn(trained_programs["train"].rows)
    assert fwd > 0 and both >= 2 * fwd, (fwd, both)
    paths = {r.path for r in trained_programs["train"].rows}
    assert any(p.endswith("loss") for p in paths)
    assert any("updater" in p.split("/") for p in paths)


# -------------------------------------------------- (c) what the table keeps
def test_the_table_keeps_no_array_and_no_network():
    """After ``del`` of the net and its programs the parameters are
    collected, and the entry still answers."""
    net = _toy_net("starcoder2")
    progs = GenerationPrograms(net, slots=4, pages_per_slot=6, page_size=8,
                               num_pages=25, prefill_buckets=(16,))
    progs.warm()
    leaves = jax.tree_util.tree_leaves(net.params)
    refs = [weakref.ref(x) for x in leaves] + [weakref.ref(net),
                                                 weakref.ref(progs)]
    del net, progs, leaves
    gc.collect()
    assert [r() for r in refs] == [None] * len(refs)
    assert recompile.program_scopes("generation.decode").rows


def test_a_new_signature_registers_the_program_again():
    fn = recompile.instrument(jax.jit(lambda a: jnp.tanh(a @ a.T).sum()),
                              "scopes.toy")
    fn(jnp.ones((4, 8)))
    small = recompile.program_scopes("scopes.toy")
    fn(jnp.ones((6, 8)))          # a detector miss: registered again
    fn(jnp.ones((6, 8)))          # a hit: nothing
    large = recompile.program_scopes("scopes.toy")
    assert small.module == large.module == "jit__lambda"
    assert any("[4,4]" in r.shape for r in small.rows)
    assert any("[6,6]" in r.shape for r in large.rows)
    assert not any("[4,4]" in r.shape for r in large.rows)


def test_an_unknown_program_is_a_readable_error():
    with pytest.raises(ValueError, match="no program registered as 'nope'"
                                         ".*registered: "):
        recompile.program_scopes("nope")


HLO = """HloModule jit_toy, is_scheduled=true

%fused_computation (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  %mul.1 = bf16[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(toy)/layer_1/norm/mul"}
  %mul.2 = bf16[8,8]{1,0} multiply(%mul.1, %p0), metadata={op_name="jit(toy)/layer_1/norm/mul"}
  ROOT %dot.1 = bf16[8,8]{1,0} convolution(%mul.2, %p1), metadata={op_name="jit(toy)/layer_2/ffn/dot_general"}
}

ENTRY %main (x: bf16[8,8], w: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %w = bf16[8,8]{1,0} parameter(1), metadata={op_name="params['w']"}
  %copy-start.1 = (bf16[8,8]{1,0:S(1)}, bf16[8,8]{1,0}, u32[]{:S(2)}) copy-start(%w)
  %copy-done.1 = bf16[8,8]{1,0:S(1)} copy-done(%copy-start.1)
  %copy.2 = bf16[8,8]{0,1} copy(%w), metadata={op_name="params['w']"}
  %bitcast.3 = bf16[8,8]{1,0} bitcast(%copy.2)
  %fusion.4 = bf16[8,8]{1,0} fusion(%x, %copy-done.1), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(toy)/layer_1/norm/mul"}
  ROOT %add.5 = bf16[8,8]{1,0} add(%fusion.4, %bitcast.3), metadata={op_name="jit(toy)/layer_2/add"}
}
"""


def test_the_parser_reads_fusions_and_what_xla_put_in_itself():
    module, rows = recompile.parse_module_text(HLO)
    by = {r.name: r for r in rows}
    assert module == "jit_toy" and len(rows) == 13
    # what is fused into the fusion, matrix products apart
    assert by["fusion.4"].fused == {"layer_1/norm": (2, 0),
                                    "layer_2/ffn": (1, 1)}
    assert by["fusion.4"].path == "layer_1/norm"
    # the prefetch of a weight and the copy under an argument's label carry
    # no place in the program: each points at what consumes its result
    assert by["copy-done.1"].consumer == "fusion.4"
    assert by["copy-start.1"].consumer == "fusion.4"
    assert by["copy.2"].path == "" and by["copy.2"].consumer == "add.5"
    assert by["add.5"].consumer == ""
    # the reader: the product decides the fusion, the consumer the copies
    table = _layer_time.table_of(recompile.ProgramScopes("t", module, rows))
    assert table["fusion.4 bf16[8,8]"] == "ffn"
    assert table["copy-done.1 bf16[8,8]"] == "ffn"
    assert table["add.5 bf16[8,8]"] is None       # the block's name alone


# ------------------------------------------------ (d) the readers' arithmetic
US = 1_000


def ev(name, start_us, dur_us):
    return Event(name, start_us * US, (start_us + dur_us) * US)


def row(name, shape, path, fused=None, opcode="fusion"):
    return recompile.ScopeRow(name, shape, opcode, path, fused or {})


def short(name, shape):
    return f"{name} {shape.split('{')[0]}"


@pytest.fixture()
def served():
    """A window of 1000 us: two decode executions of 100 us and one prefill
    of each of two buckets (200 and 300 us).  ``fusion.1 bf16[8,8]`` is an
    FFN product in ``prefill_512`` and an attention one in ``prefill_2048``:
    one short name under two kinds."""
    tables = {
        "jit_decode_step": _layer_time.table_of(recompile.ProgramScopes(
            "generation.decode", "jit_decode_step", (
                row("fusion.1", "bf16[8,8]{1,0}", "layer_1/attention"),
                row("fusion.2", "bf16[8,32]{1,0}", "layer_2/ffn/sub1"),
                row("fusion.3", "f32[8,97]{1,0}", "layer_3/head"),
                row("fusion.4", "bf16[8,8]{1,0}", "layer_2/norm"),
                row("while.5", "(s32[], f32[8,8]{1,0})", "layer_4",
                    {"layer_4/experts/moe_experts": (9, 2),
                     "layer_4/norm": (12, 0)}, "while"),
                row("fusion.6", "bf16[8,8]{1,0}", "layer_2")))),
        "jit_prefill_512": _layer_time.table_of(recompile.ProgramScopes(
            "generation.prefill_512", "jit_prefill_512", (
                row("fusion.1", "bf16[8,8]{1,0}", "layer_2/ffn"),
                row("fusion.9", "f32[1,97]{1,0}", "sample")))),
        "jit_prefill_2048": _layer_time.table_of(recompile.ProgramScopes(
            "generation.prefill_2048", "jit_prefill_2048", (
                row("fusion.1", "bf16[8,8]{1,0}",
                    "layer_1/attention/mla_attention"),))),
    }
    f1 = short("fusion.1", "bf16[8,8]")
    ops = []
    for t0 in (0, 500):                       # the two decode executions
        ops += [ev(f1, t0, 30), ev(short("fusion.2", "bf16[8,32]"), t0 + 30,
                                   20),
                ev(short("fusion.3", "f32[8,97]"), t0 + 50, 10),
                ev(short("fusion.4", "bf16[8,8]"), t0 + 60, 10),
                # a while and an operation of one of its trips, nested
                ev("while.5 s32[],", t0 + 70, 20), ev(f1, t0 + 72, 5),
                ev(short("fusion.6", "bf16[8,8]"), t0 + 90, 4),
                ev("fusion.77 f32[2]", t0 + 94, 2)]     # in no table
    ops += [ev(f1, 100, 150), ev(short("fusion.9", "f32[1,97]"), 250, 40)]
    ops += [ev(f1, 600, 280)]
    ops += [ev("copy.1 f32[4]", 950, 10)]               # a program unknown
    mods = [ev("jit_decode_step(11)", 0, 100), ev("jit_prefill_512(12)", 100,
                                                  200),
            ev("jit_decode_step(11)", 500, 100),
            ev("jit_prefill_2048(13)", 600, 300),
            ev("jit_read_page(14)", 950, 10)]
    trace = Reduced((0, 1000 * US), {"/device:TPU:0": ops},
                    {"/device:TPU:0": mods}, [])
    return types.SimpleNamespace(
        trace=trace, obs={"_layer_tables": tables, "notes": {}})


def read(metric, ctx):
    return harness.load_reader(metric).read(ctx)


def test_decode_parts_add_up_to_the_mean_module_duration(served):
    parts = {k: read(f"decode_step_ms.{k}", served)
             for k in ("attention", "experts", "ffn", "head", "rest")}
    assert parts["attention"] == pytest.approx(0.030)
    assert parts["ffn"] == pytest.approx(0.020)
    assert parts["head"] == pytest.approx(0.010)
    # the while goes to the kind that holds its matrix products, not to the
    # norm that holds more instructions; the nested event is not counted
    assert parts["experts"] == pytest.approx(0.020)
    # norm 10 + under the block's name alone 4 + in no table 2 + gaps 4
    assert parts["rest"] == pytest.approx(0.020)
    mean = 1e3 * np.mean(served.trace.module_durations("decode"))
    assert sum(parts.values()) == pytest.approx(mean)


def test_prefill_parts_add_up_and_buckets_keep_their_own_tables(served):
    parts = {k: read(f"prefill_share.{k}", served)
             for k in ("attention", "experts", "ffn", "head", "rest")}
    # one short name, two kinds: each bucket's events against its own table
    assert parts["ffn"] == pytest.approx(15.0)          # prefill_512
    assert parts["attention"] == pytest.approx(28.0)    # prefill_2048
    assert parts["head"] == pytest.approx(4.0)          # its ``sample``
    assert parts["experts"] == 0.0
    assert sum(parts.values()) == pytest.approx(
        read("prefill_share_of_window", served))
    assert served.obs["notes"]["prefill_ms_by_bucket"] == {
        "jit_prefill_2048": {"mean_ms": 0.3, "count": 1},
        "jit_prefill_512": {"mean_ms": 0.2, "count": 1}}


def test_the_guard_counts_what_no_kind_or_table_holds(served):
    share = read("device_time_unattributed_share.serve", served)
    # fusion.6 (the block's name alone) 2 x 4, fusion.77 (in no table)
    # 2 x 2, copy.1 (a program that published none) 10, of 690 us busy
    busy = 2 * 96 + 190 + 280 + 10
    assert share == pytest.approx(100.0 * 22 / busy)
    assert served.obs["notes"]["unattributed_by_program"] == {
        "jit_decode_step": pytest.approx(100.0 * 12 / busy, abs=1e-3),
        "jit_read_page": pytest.approx(100.0 * 10 / busy, abs=1e-3)}


def test_train_parts_add_up_to_busy_time_a_step():
    table = _layer_time.table_of(recompile.ProgramScopes(
        "MultiLayerNetwork.train_step", "jit_step", (
            row("fusion.1", "bf16[8,8]{1,0}", "layer_1/attention"),
            row("fusion.2", "bf16[8,32]{1,0}", "layer_2/ffn"),
            row("fusion.3", "f32[8,97]{1,0}", "layer_3/head"),
            row("fusion.4", "f32[]", "loss"),
            row("fusion.5", "f32[8,8]{1,0}", "updater"),
            row("fusion.6", "bf16[8,8]{1,0}", "param_cast"),
            row("all-reduce.7", "bf16[8,8]{1,0}", "", opcode="all-reduce"),
            row("fusion.8", "bf16[8,8]{1,0}", "layer_2/norm"))))
    ops, mods = [], []
    for t0 in (0, 400):
        mods.append(ev("jit_step(5)", t0, 300))
        for i, (name, dur) in enumerate((
                ("fusion.1 bf16[8,8]", 50), ("fusion.2 bf16[8,32]", 60),
                ("fusion.3 f32[8,97]", 40), ("fusion.4 f32[]", 30),
                ("fusion.5 f32[8,8]", 45), ("fusion.6 bf16[8,8]", 15),
                ("all-reduce.7 bf16[8,8]", 35), ("fusion.8 bf16[8,8]", 25))):
            ops.append(ev(name, t0 + sum(d for _, d in (
                ("fusion.1", 50), ("fusion.2", 60), ("fusion.3", 40),
                ("fusion.4", 30), ("fusion.5", 45), ("fusion.6", 15),
                ("all-reduce.7", 35), ("fusion.8", 25))[:i]), dur))
        mods.append(ev("jit_one_hot(6)", t0 + 300, 20))
        ops.append(ev("fusion f32[2,12,97]", t0 + 300, 20))
    trace = Reduced((0, 1000 * US), {"/device:TPU:0": ops},
                    {"/device:TPU:0": mods}, [])
    ctx = types.SimpleNamespace(
        trace=trace, obs={"_layer_tables": {"jit_step": table}, "notes": {}})
    parts = {k: read(f"train_step_ms.{k}", ctx)
             for k in ("attention", "ffn", "head_loss", "optimizer",
                       "param_cast", "rest")}
    assert parts == {"attention": pytest.approx(0.050),
                     "ffn": pytest.approx(0.060),
                     "head_loss": pytest.approx(0.070),
                     "optimizer": pytest.approx(0.045),
                     "param_cast": pytest.approx(0.015),
                     # all-reduce 35 + norm 25 + the feed's program 20
                     "rest": pytest.approx(0.080)}
    assert sum(parts.values()) == pytest.approx(1e3 * trace.busy_s / 2)
    # the collective is known by its instruction; the feed's program is not
    assert read("device_time_unattributed_share.train", ctx) == \
        pytest.approx(100.0 * 40 / 640)


def test_readers_are_silent_without_a_table_or_a_device_line(served):
    bare = types.SimpleNamespace(trace=served.trace,
                                 obs={"_layer_tables": {}, "notes": {}})
    assert read("decode_step_ms.attention", bare) is None
    assert read("device_time_unattributed_share.serve", bare) is None
    host_only = types.SimpleNamespace(
        trace=Reduced((0, 1000 * US), {}, {}, []),
        obs={"_layer_tables": served.obs["_layer_tables"], "notes": {}})
    assert read("prefill_share.rest", host_only) is None
    assert read("train_step_ms.rest", host_only) is None

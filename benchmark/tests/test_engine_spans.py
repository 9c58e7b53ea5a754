"""The readers of the decode loop's spans (``metrics/_engine_spans.py`` and
the eight metrics on it) on a hand-built trace: the parts add up to the
device's idle share, a gap is cut and not given whole, a child beats its
parent, another thread's span gets nothing.  And one traced rehearsal of the
serve cell on the CPU, in which the readers that need no device read numbers.
"""

import argparse
import types

import pytest

from benchmark import run as harness
from benchmark.metrics import _engine_spans
from benchmark.trace_reduce import Event, Reduced

US = 1_000
P = _engine_spans.PREFIX
SERVE = "sc2-7b.serve-complete"


def ev(name, start_us, dur_us):
    return Event(name, start_us * US, (start_us + dur_us) * US)


def ctx_of(ops, host, window=(0, 1000)):
    dev = {"/device:TPU:0": ops} if ops else {}
    trace = Reduced((window[0] * US, window[1] * US), dev, {}, host)
    return types.SimpleNamespace(trace=trace, obs={})


def read(metric, ctx):
    return harness.load_reader(metric).read(ctx)


FIVE = ("idle_share_admit", "idle_share_decode_dispatch",
        "idle_share_decode_harvest", "idle_share_decode_host",
        "idle_share_unattributed")


@pytest.fixture()
def loop():
    """One turn of the loop in a window of 1000 us.  Device busy 0-100,
    300-400, 700-720; idle 100-300, 400-700, 720-1000 = 780 us."""
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 300, 100),
           ev("fusion.3", 700, 20)]
    host = [
        ev(P + "loop.schedule", 90, 30),            # idle 100-120: 20
        ev(P + "admit.schedule", 120, 30),          # idle 120-150: 30
        ev(P + "admit.page_gather", 150, 100),      # idle 150-250: 100
        ev(P + "admit.base_key", 160, 40),          #   (child: still admit)
        ev(P + "admit.jitted_step", 250, 30),       # idle 250-280: 30
        # 280-300 idle under no span of the loop: 20
        ev(P + "admit.sample_harvest", 300, 110),   # idle 400-410: 10
        ev(P + "decode.jitted_step", 410, 90),      # idle 410-500: 90
        ev(P + "decode.sample_harvest", 500, 100),  # idle 500-600: 100
        ev(P + "decode.stream_write", 600, 80),     # idle 600-680: 80
        ev(P + "decode.deliver", 610, 30),          #   (child: same part)
        ev(P + "decode.gauges", 640, 40),
        # 680-700 idle under no span: 20
        ev(P + "loop.wait", 720, 200),              # idle 720-920: 200
        # 920-1000 idle under no span: 80
        # another thread's span and one of JAX's own over the whole window
        ev("client_submit", 0, 1000),
        ev("PjitFunction(decode_step)", 0, 1000),
    ]
    return ctx_of(ops, host)


def test_parts_add_up_to_the_idle_share(loop):
    shares = _engine_spans.idle_shares(loop)
    assert set(shares) == set(_engine_spans.PARTS)
    assert shares["admit"] == pytest.approx(0.170)
    assert shares["decode_dispatch"] == pytest.approx(0.090)
    assert shares["decode_harvest"] == pytest.approx(0.100)
    assert shares["decode_host"] == pytest.approx(0.100)      # 80 + 20
    assert shares["unattributed"] == pytest.approx(0.120)
    assert shares["wait"] == pytest.approx(0.200)
    assert sum(shares.values()) == pytest.approx(loop.trace.idle_share(),
                                                 abs=1e-9)
    five = [read(m, loop) for m in FIVE]
    assert five == pytest.approx([17.0, 9.0, 10.0, 10.0, 12.0])
    # idle inside loop.wait lands in none of the five
    assert sum(five) + 100 * shares["wait"] == pytest.approx(
        100 * loop.trace.idle_share(), abs=1e-9)


def test_a_gap_straddling_two_spans_is_cut():
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 300, 100)]
    host = [ev(P + "decode.jitted_step", 50, 100),          # 100-150
            ev(P + "decode.sample_harvest", 150, 250)]      # 150-300
    shares = _engine_spans.idle_shares(ctx_of(ops, host, (0, 400)))
    assert shares["decode_dispatch"] == pytest.approx(50 / 400)
    assert shares["decode_harvest"] == pytest.approx(150 / 400)
    assert shares["unattributed"] == pytest.approx(0.0)


def test_a_child_beats_its_parent_and_the_parent_resumes():
    pieces = _engine_spans.innermost([
        ev("outer", 0, 100), ev("child", 20, 30), ev("grandchild", 30, 10),
        ev("next", 100, 10)])
    assert pieces == [
        (0, 20 * US, "outer"), (20 * US, 30 * US, "child"),
        (30 * US, 40 * US, "grandchild"), (40 * US, 50 * US, "child"),
        (50 * US, 100 * US, "outer"), (100 * US, 110 * US, "next")]
    # admit.base_key is admission whatever encloses it; a child of another
    # stage than its parent would go to its own part
    ops = [ev("fusion.1", 0, 10), ev("fusion.2", 90, 10)]
    host = [ev(P + "decode.stream_write", 10, 80),
            ev(P + "admit.base_key", 30, 20)]
    shares = _engine_spans.idle_shares(ctx_of(ops, host, (0, 100)))
    assert shares["admit"] == pytest.approx(0.2)
    assert shares["decode_host"] == pytest.approx(0.6)


def test_other_threads_and_jax_events_get_nothing():
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 900, 100)]
    host = [ev("client_submit", 100, 800),
            ev("np.asarray(jax.Array)", 100, 800),
            ev(P + "loop.schedule", 100, 1)]
    shares = _engine_spans.idle_shares(ctx_of(ops, host))
    assert shares["unattributed"] == pytest.approx(0.799)
    assert shares["decode_host"] == pytest.approx(0.001)


def test_nothing_to_read():
    spans = [ev(P + "decode.jitted_step", 0, 10),
             ev(P + "admit.jitted_step", 10, 10)]
    no_device = ctx_of([], spans)
    parent = ctx_of([ev("fusion.1", 0, 100)], [ev("client_submit", 0, 900)])
    for m in FIVE:
        assert read(m, no_device) is None       # CPU rehearsal
        assert read(m, parent) is None          # a program without the spans
    assert read("prefills_per_decode_step", no_device) == 1.0
    assert read("prefills_per_decode_step", parent) is None
    assert read("admit_host_ms_per_prefill", parent) is None


def test_counts_and_admission_host_time(loop):
    assert read("prefills_per_decode_step", loop) == 1.0
    # schedule 30 + page_gather 100 (base_key is inside it) + no stream_write
    assert read("admit_host_ms_per_prefill", loop) == pytest.approx(0.130)


def test_traced_rehearsal_reads_the_host_side_metrics():
    """No device operation in a CPU trace, so the five shares read nothing;
    the three readers that need only host spans or the tracer read numbers
    from the engine's own spans in the profiler's file."""
    line = harness.run(argparse.Namespace(
        workload=SERVE, seed=2147483659, seconds=1.0, trace=1,
        rehearsal=True, describe=None))
    values = line["values"]
    for m in ("prefills_per_decode_step", "admit_host_ms_per_prefill",
              "admission_wait_p50_ms"):
        assert values[m]["value"] > 0.0, (m, values)
    assert not set(FIVE) & set(values)
    assert line["would_be_correct"] is True, line["compared"]

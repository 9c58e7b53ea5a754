"""trace_reduce.py on a hand-built trace: busy union, idle share, sums by
name, programs, and the attribution of idle gaps to host spans."""

import pytest

from benchmark.trace_reduce import (
    Event, Reduced, clip, gaps_of, host_span_at, merged, union_ns,
)

US = 1_000


def ev(name, start_us, dur_us):
    return Event(name, start_us * US, (start_us + dur_us) * US)


@pytest.fixture()
def reduced():
    dev = "/device:TPU:0"
    ops = {dev: [
        ev("fusion.1", 90, 20),          # straddles the window's start (100)
        ev("jvp_flash_attention_fwd_.2 bf16[48,4096,128]", 120, 30),
        ev("fusion.1", 140, 20),         # overlaps the kernel by 10
        ev("fused_paged_attention.3", 300, 40),     # inside decode
        ev("fused_paged_attention.3", 500, 60),     # inside prefill
        ev("copy.4", 1050, 100),         # straddles the end (1100)
    ]}
    mods = {dev: [ev("jit_decode_step(1)", 290, 60),
                  ev("jit_prefill(2)", 480, 100)]}
    host = [ev("client_submit", 170, 100), ev("feed_batch", 360, 100),
            ev("outer", 150, 400), ev("feed_batch", 700, 300)]
    return Reduced((100 * US, 1100 * US), ops, mods, host)


def test_union_merge_and_clip():
    a = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5)]
    assert merged(a) == [[0, 15 * US], [30 * US, 35 * US]]
    assert union_ns(a) == 20 * US
    c = clip(a, 12 * US, 32 * US)
    assert [(e.start, e.end) for e in c] == [(12 * US, 15 * US), (30 * US, 32 * US)]
    assert gaps_of(a, 0, 40 * US) == [(15 * US, 30 * US), (35 * US, 40 * US)]


def test_busy_idle_and_sums(reduced):
    # clipped: 100-110, 120-160 (two overlapping), 300-340, 500-560, 1050-1100
    busy_us = 10 + 40 + 40 + 60 + 50
    assert reduced.window_s == pytest.approx(1000e-6)
    assert reduced.busy_s == pytest.approx(busy_us * 1e-6)
    assert reduced.idle_share() == pytest.approx(1 - busy_us / 1000)
    assert reduced.op_seconds(contains="fusion") == pytest.approx(30e-6)
    assert reduced.op_seconds(contains="flash_attention") == pytest.approx(30e-6)
    assert reduced.op_seconds(contains="fused_paged_attention") == pytest.approx(100e-6)
    assert reduced.op_seconds(contains="fused_paged_attention",
                              inside="decode") == pytest.approx(40e-6)
    assert reduced.module_durations("decode") == [pytest.approx(60e-6)]
    assert reduced.module_durations("prefill") == [pytest.approx(100e-6)]


def test_gap_attribution(reduced):
    spans = reduced.host_spans
    # 160-300: client_submit covers 100 of 140, outer covers all of it
    assert host_span_at(spans, 160 * US, 300 * US) == "outer"
    # 340-500: feed_batch covers 100, outer 160 -> outer (more cover)
    assert host_span_at(spans, 340 * US, 500 * US) == "outer"
    # 700-1000 lies wholly inside the second feed_batch
    assert host_span_at(spans, 700 * US, 1000 * US) == "feed_batch"
    assert host_span_at(spans, 2000 * US, 2100 * US) == "(no host span)"
    b = reduced.breakdown()
    ops = dict(b["device_ops"])
    assert ops["fused_paged_attention.3"] == pytest.approx(100e-6)
    gaps = dict(b["idle_gaps"])
    # gaps: 160-300 and 340-500 -> outer; 560-1050 -> feed_batch (300 of 490)
    assert gaps["outer"] == pytest.approx(300e-6)
    assert gaps["feed_batch"] == pytest.approx(490e-6)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_ops_reads_nothing():
    r = Reduced((0, 1000), {}, {}, [])
    assert r.idle_share() is None and r.busy_s == 0.0
    assert r.op_seconds(contains="x") == 0.0 and r.module_durations("x") == []

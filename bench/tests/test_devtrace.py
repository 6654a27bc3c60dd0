"""The trace reduction on a small recorded device trace: busy union, idle
share, kernel lookup, the breakdown's top operations and labelled gaps."""
import pytest

import devtrace
from devtrace import Event

# One chip's ``XLA Ops`` line over a 1000 ns window: two overlapping ops,
# a fused split kernel event, and one op that starts before the window.
OPS = [
    Event("%copy.1 = f32[8] copy(x)", -50.0, 100.0),          # 0..50 in
    Event("%fusion.3 = s32[64] fusion(a, b)", 100.0, 200.0),  # 100..300
    Event("%fusion.3 = s32[64] fusion(a, b)", 250.0, 100.0),  # 250..350
    Event("%fused_split_pallas.1 = (f32[8,1]) custom-call(c)", 600.0, 300.0),
]
HOST = [Event("bench/train_job", 0.0, 1000.0),
        Event("grower_device/fetch", 360.0, 200.0)]


def test_union_merges_overlaps():
    assert devtrace.union(OPS) == [[-50.0, 50.0], [100.0, 350.0],
                                   [600.0, 900.0]]


def test_busy_and_idle_clip_to_window():
    busy = devtrace.busy_ns(OPS, 0.0, 1000.0)
    assert busy == pytest.approx(50 + 250 + 300)


def test_kernel_lookup_by_name():
    hits = devtrace.kernel_events(OPS, "fused_split")
    assert [e.start_ns for e in hits] == [600.0]
    assert devtrace.kernel_events(OPS, "forest_infer") == []


def test_top_ops_sum_by_short_name():
    top = dict(devtrace.top_ops(OPS, 0.0, 1000.0))
    assert top == {"fused_split_pallas.1": pytest.approx(300e-9),
                   "fusion.3": pytest.approx(300e-9),
                   "copy.1": pytest.approx(50e-9)}


def test_idle_gaps_labelled_by_innermost_host_span():
    gaps = devtrace.idle_gaps(OPS, HOST, 0.0, 1000.0)
    # gaps: 50..100, 350..600 (fetch covers its middle), 900..1000
    assert gaps[0] == ["grower_device/fetch", pytest.approx(250e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [50e-9, 100e-9, 250e-9])
    assert {g[0] for g in gaps} == {"grower_device/fetch",
                                     "bench/train_job"}


def test_reading_idle_and_kernel_time():
    r = devtrace.Reading(layer={}, config={}, peaks={}, t0=0.0, t1=1e-6,
                         bench_spans=[], obs_spans=[],
                         ops={"/device:TPU:0": devtrace.clip(OPS, 0, 1000)},
                         w0_ns=0.0, w1_ns=1000.0, offset_ns=0.0)
    assert r.window_s == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(600e-9)
    assert r.idle_pct() == pytest.approx(40.0)
    assert r.kernel_s("fused_split") == pytest.approx(300e-9)


def test_reading_without_device_ops_reads_nothing():
    r = devtrace.Reading(layer={}, config={}, peaks={}, t0=0.0, t1=1.0,
                         bench_spans=[], obs_spans=[], ops={}, w0_ns=0.0,
                         w1_ns=1e9, offset_ns=0.0)
    assert r.idle_pct() is None

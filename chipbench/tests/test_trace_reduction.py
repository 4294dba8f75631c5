"""The trace reduction, on a trace recorded on a TPU v5e and on
hand-made events."""

import time
from pathlib import Path

import pytest

from chipbench import trace as t

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def test_merge_clip_and_gaps():
    busy = t.merge([(5, 8), (0, 2), (1, 3), (8, 9), (12, 15)])
    assert busy == [(0, 3), (5, 9), (12, 15)]
    assert t.clip(busy, 2, 13) == [(2, 3), (5, 9), (12, 13)]
    assert t.idle_gaps(t.clip(busy, 2, 20), 2, 20) == [(3, 5), (9, 12),
                                                         (15, 20)]


def test_summary_on_hand_made_events():
    ops = {"/device:TPU:0": [t.Event("seg_agg.1 f32[8,128]", 10, 30),
                             t.Event("fusion.2 f32[8,128]", 25, 40),
                             t.Event("seg_agg.3 f32[8,128]", 60, 70)],
           "/device:TPU:1": [t.Event("seg_agg.1 f32[8,128]", 0, 100)]}
    spans = [t.Event("bench.window", 0, 100),
             t.Event("bench.admit", 40, 55),
             t.Event("bench.dispatch", 55, 70)]
    s = t.summarize(ops, spans)
    assert s.window_s == pytest.approx(100e-9)
    # device 0 busy 10-40 and 60-70 (40 ns); device 1 busy 100 ns
    assert s.busy_s == pytest.approx(70e-9)
    assert s.op_s["seg_agg.1 f32[8,128]"] == pytest.approx(120e-9)
    assert t.kernel_seconds(s, ("seg_agg",)) == pytest.approx(130e-9)
    assert t.kernel_seconds(s, ("fused_agg_combine",)) == 0
    # device 0 idles 0-10 (window), 40-60 (mid 50: admit), 70-100 (window)
    assert s.gaps == [("bench.window", pytest.approx(30e-9)),
                      ("bench.admit", pytest.approx(20e-9)),
                      ("bench.window", pytest.approx(10e-9))]
    assert s.idle_by_span == {"bench.window": pytest.approx(40e-9),
                              "bench.admit": pytest.approx(20e-9)}


def test_summary_of_a_long_window_is_quick():
    """A minute of 2 ms forwards: 30,000 spans and 90,000 ops.  Labelling
    the gaps once scanned every span for every gap."""
    n = 30_000
    ops = {"/device:TPU:0": [t.Event("seg_agg.1", 2000 * i + d, 2000 * i + d
                                     + 300) for i in range(n)
                             for d in (0, 400, 800)]}
    spans = [t.Event("bench.window", 0, 2000 * n)] + [
        t.Event("bench.forward", 2000 * i, 2000 * i + 1500)
        for i in range(n)]
    start = time.perf_counter()
    s = t.summarize(ops, spans)
    assert time.perf_counter() - start < 5.0
    assert s.busy_s == pytest.approx(n * 900e-9)
    assert s.idle_by_span["bench.forward"] == pytest.approx(n * 200e-9)
    assert s.idle_by_span["bench.window"] == pytest.approx(n * 900e-9)


def test_op_name_keeps_name_and_type():
    assert t.op_name("%fusion.1 = f32[12135144,41]{1,0:T(8,128)} "
                     "fusion(f32[232965,41]{1,0} %p)") == \
        "fusion.1 f32[12135144,41]"
    assert t.op_name("%copy-start = (f32[4]{0}, f32[4]{0}) copy-start()") \
        == "copy-start"
    assert t.op_name("jit_fwd") == "jit_fwd"


def test_recorded_tpu_trace():
    """Three traced calls of a 1024x1024 matmul on one v5e, each inside a
    ``bench.forward`` span, 2 ms of sleep between them.  The device clock
    reads about 1.2 ms early, so the first call's ops fall before the
    window and are left out."""
    devices, spans = t.read_xplane(str(SMALL))
    assert list(devices) == ["/device:TPU:0"]
    assert len(devices["/device:TPU:0"]) == 9
    assert [sp.name for sp in spans] == ["bench.window"] + \
        ["bench.forward"] * 3
    s = t.summarize(devices, spans)
    assert s.window_s == pytest.approx(10_245_230e-9)
    # per call: copy-start 13 ns, copy-done 2-3 ns, the fusion 11,827 ns
    assert s.busy_s == pytest.approx(23_685e-9)
    assert s.op_s["convolution_reduce_fusion f32[]"] == \
        pytest.approx(23_654e-9)
    assert s.gaps[:3] == [("bench.window", pytest.approx(4_562_216e-9)),
                          ("bench.forward", pytest.approx(3_169_241e-9)),
                          ("bench.window", pytest.approx(2_490_083e-9))]
    assert sum(s.idle_by_span.values()) == \
        pytest.approx(s.window_s - s.busy_s)

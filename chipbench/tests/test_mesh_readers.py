"""The four-chip cell's yardstick: the work counts of ``gin-reddit``,
worked by hand, and its four readers on hand-made trace summaries and
gauges -- the shares of a peak divide by the cell's chips."""

import json
import types
from pathlib import Path

import jax
import pytest

from chipbench import work
from chipbench.manifest import load_module
from chipbench.metrics_api import Readings
from chipbench.trace import Summary

PKG = Path(__file__).resolve().parents[1]
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return json.loads((PKG / "configs" / "gin-reddit.json").read_text())


def test_gin_reddit_counts():
    cfg = _cfg()
    # V = 232,965, E = 11,606,919; GIN sums at its input width (602, then
    # 128) and runs a two-map MLP: 602 -> 128 -> 128, then 128 -> 128 -> 41
    assert work.layers(cfg) == [{"mlp": [602, 128, 128], "agg": 602},
                                {"mlp": [128, 128, 41], "agg": 128}]
    # 2·V·(602·128 + 128·128 + 128·128 + 128·41) = 53,615,496,960;
    # E·(602 + 128) = 8,473,050,870
    assert work.forward_flops(cfg) == 62_088_547_830
    # values: V·602 + weights and biases (115,497) + V·41 floats · 4 B,
    # plus E·2 indices · 4 B
    assert work.forward_bytes(cfg) == (232_965 * 643 + 115_497) * 4 \
        + 2 * 11_606_919 * 4
    assert work.forward_bytes(cfg) == 692_503_320
    t, bound = work.least_time(work.forward_flops(cfg),
                               work.forward_bytes(cfg), V5E)
    assert bound == "memory"
    assert t == pytest.approx(692_503_320 / 819e9)


def _readings(summary, chips=4, count=10):
    ctx = types.SimpleNamespace(summary=summary, config=_cfg(), spans={},
                                cell={"chips": chips}, setup_end=None)
    outcome = types.SimpleNamespace(attempted=count, failed=0)
    # the peaks of the device this process runs on, as the chip's would be
    peaks = {jax.devices()[0].device_kind: V5E}
    return Readings(ctx=ctx, outcome=outcome, peaks=peaks)


def _read(name, r):
    return load_module(PKG / "metrics" / f"{name}.py", name).read(r)


SUMMARY = Summary(window_s=2.0, busy_s=1.5,
                  op_s={"fusion.7 f32[58242,602]": 3.0,
                        "collective-permute-done.1 f32[58242,602]": 0.036,
                        "collective-permute-start.1": 0.004},
                  gaps=[], idle_by_span={})


@pytest.mark.parametrize("chips", [1, 4])
def test_shares_of_the_peak_divide_by_the_chips(chips):
    r = _readings(SUMMARY, chips=chips)
    flops = work.forward_flops(_cfg())
    assert _read("mesh.mfu", r) == pytest.approx(
        100 * flops * 10 / 2.0 / (chips * 197e12))
    least = work.forward_bytes(_cfg()) / 819e9       # memory-bound
    assert _read("mesh.forward_roofline", r) == pytest.approx(
        100 * least / chips * 10 / 1.5)
    # on one chip they are the one-chip readers' numbers
    if chips == 1:
        assert _read("mesh.mfu", r) == pytest.approx(_read("forward.mfu", r))
        assert _read("mesh.forward_roofline", r) == \
            pytest.approx(_read("forward_roofline", r))


def test_permute_time_is_per_device_and_per_forward():
    r = _readings(SUMMARY, chips=4, count=10)
    # 40 ms of permute ops over four devices and ten forwards
    assert _read("halo.permute_ms", r) == pytest.approx(1.0)
    quiet = SUMMARY._replace(op_s={"fusion.7 f32[58242,602]": 3.0})
    assert _read("halo.permute_ms", _readings(quiet)) is None
    assert _read("halo.permute_ms", _readings(None)) is None


def test_wire_mb_sums_the_layers_gauges():
    from repro.profile import spans
    spans.reset()
    r = _readings(SUMMARY)
    assert _read("halo.wire_mb", r) is None          # a local plan sets none
    # the cell's pipelined ring: 3 slabs of 58,242 rows a layer, at widths
    # 602 and 128, 4 bytes each
    for i, width in enumerate((602, 128)):
        spans.gauge(f"halo.wire_bytes.l{i}", 3 * 58_242 * width * 4)
    assert _read("halo.wire_mb", r) == pytest.approx(510.19992)
    spans.reset()


def test_device_readers_read_nothing_without_a_trace():
    r = _readings(None)
    for name in ("mesh.mfu", "mesh.forward_roofline", "halo.permute_ms"):
        assert _read(name, r) is None

"""The work counts of both configurations, against numbers worked by hand."""

import json
from pathlib import Path

import pytest

from chipbench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_gcn_reddit_counts():
    cfg = _cfg("gcn-reddit")
    # V = 232,965, E = 11,606,919, 602 -> 128 -> 41; GCN aggregates at the
    # narrower side of each map: 128, then 41
    assert work.layers(cfg) == [{"mlp": [602, 128], "agg": 128},
                                {"mlp": [128, 41], "agg": 41}]
    # 2·V·602·128 = 35,902,702,080; 2·V·128·41 = 2,445,200,640;
    # E·(128 + 41) = 1,961,569,311
    assert work.forward_flops(cfg) == 40_309_472_031
    # values: V·602 + (602·128 + 128) + (128·41 + 41) + V·41
    # = 149,878,968 floats · 4 B, plus E·2 indices · 4 B
    assert work.forward_bytes(cfg) == 149_878_968 * 4 + 2 * 11_606_919 * 4
    assert work.forward_bytes(cfg) == 692_371_224
    assert work.agg_flops(cfg) == 1_961_569_311
    # per layer 2·V·w·4 + 2·E·4, w = 128 and 41
    assert work.agg_bytes(cfg) == 500_679_384
    t, bound = work.least_time(work.forward_flops(cfg),
                               work.forward_bytes(cfg), V5E)
    assert bound == "memory"
    assert t == pytest.approx(692_371_224 / 819e9)


def test_gin_pubmed_counts():
    cfg = _cfg("gin-pubmed")
    # V = 19,717, E = 44,338; GIN sums at its input width: 500, then 128
    assert work.layers(cfg) == [{"mlp": [500, 128, 128], "agg": 500},
                                {"mlp": [128, 128, 3], "agg": 128}]
    # 2·V·(500·128 + 128·128 + 128·128 + 128·3) + E·(500 + 128)
    assert work.forward_flops(cfg) == 3_858_936_232
    assert work.forward_bytes(cfg) == 40_415_464
    assert work.agg_flops(cfg) == 44_338 * 628
    assert work.agg_bytes(cfg) == 99_767_616
    t, bound = work.least_time(work.forward_flops(cfg),
                               work.forward_bytes(cfg), V5E)
    assert bound == "memory"
    assert t == pytest.approx(40_415_464 / 819e9)


def test_least_time_takes_the_larger_bound():
    assert work.least_time(197e12, 1.0, V5E) == (1.0, "compute")
    assert work.least_time(1.0, 819e9, V5E) == (1.0, "memory")

"""GIN on a 1-D mesh: the halo sum, then the MLP on each device's own rows.

Multi-device parts run in SUBPROCESSES with fake CPU devices, as in
tests/test_distributed.py; the owner buckets and the plan's refusals are
checked here, on the one real device."""

import numpy as np
import pytest

from repro.core.plan import build_plan
from repro.graph.partition import partition_1d
from repro.graph.structure import graph_from_coo
from repro.launch.mesh import make_mesh
from repro.models.gcn import PAPER_MODELS

from test_distributed import run_sub

#: 301 vertices over 4 blocks of 76: the last block ends in padding rows
SETUP = """
    from repro.core.plan import build_plan
    from repro.graph.structure import graph_from_coo
    from repro.models.gcn import PAPER_MODELS
    rng = np.random.default_rng(3)
    V, E, F, C = 301, 1800, 48, 5
    src = (rng.pareto(1.0, E) * 20).astype(np.int64) % V   # hot sources
    g = graph_from_coo(src, rng.integers(0, V, E), V)
    x = jnp.asarray(rng.standard_normal((V, F)) / np.sqrt(F), jnp.float32)
    cfg = PAPER_MODELS["gin"]
    mesh = make_mesh((4,), ("data",))
    local = build_plan(g, cfg, F, C)
    params = local.init(jax.random.PRNGKey(0))
    def dist(**kw):
        return build_plan(g, cfg, F, C, mesh=mesh, **kw)
"""


@pytest.mark.slow
@pytest.mark.parametrize("strategy,overlap", [("ring", "none"),
                                              ("ring", "pipelined"),
                                              ("allgather", "none")])
def test_gin_plan_on_a_mesh_matches_local_and_reference(strategy, overlap):
    out = run_sub(SETUP + f"""
    plan = dist(strategy={strategy!r}, overlap={overlap!r})
    assert plan.distributed and plan.partition_kind == "1d"
    assert [lp.order for lp in plan.layers] == ["aggregate_first"] * 2
    ref = local.run_model(params, x)
    with mesh:
        eager = plan.run_model(params, x)
        comp = plan.compile()(params, x)
    for got in (eager, comp):
        assert got.shape == (V, C)
        assert_allclose_dtype(got, ref, scale=10)
        assert_matches_reference(got, g, cfg, params, x, scale=10)
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_gin_halo_schedules_are_bitwise_equal():
    """``none`` and ``pipelined`` accumulate the same per-hop partials in
    the same order, for GIN's sum as for GCN's mean: the same bits, eager
    and compiled."""
    out = run_sub(SETUP + """
    outs = {}
    for ov in ("none", "pipelined"):
        plan = dist(strategy="ring", overlap=ov)
        with mesh:
            outs[ov] = (np.asarray(plan.run_model(params, x)),
                        np.asarray(plan.compile()(params, x)))
    for a, b in zip(outs["none"], outs["pipelined"]):
        assert np.array_equal(a, b)
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_distributed_gauges_equal_a_hand_count_and_describe():
    """The traced forward publishes what each ring hop gathers (summed
    over the mesh) and what one device sends, equal to ``describe()``
    and to a count from the partition's shapes."""
    out = run_sub(SETUP + """
    from repro.profile import spans
    for ov, slabs in (("none", 4), ("pipelined", 3)):
        plan = dist(strategy="ring", overlap=ov)
        pg = plan.partition
        eb = pg.bucket_src.shape[-1]
        spans.reset()
        with mesh:
            plan.compile()(params, x)
        counts = spans.counters()
        for i, (lp, d) in enumerate(zip(plan.layers, plan.describe())):
            hand = {"edges": E, "gather_rows": 4 * 4 * eb,
                    "kernel_slots": 4 * 4 * eb,
                    "gather_bytes": 4 * 4 * eb * lp.din * 4}
            for k, v in hand.items():
                assert d[f"agg_{k}"] == v == counts[f"agg.{k}.l{i}"], (k, ov)
            wire = slabs * pg.block_size * lp.din * 4
            assert d["halo_wire_bytes"] == wire
            assert counts[f"halo.wire_bytes.l{i}"] == wire
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_bf16_gin_plan_on_a_ring_stays_in_band():
    """The reduced-precision control: bf16 wire slabs and MLP operands,
    f32 accumulators, within the suite's bf16 band of the f32 oracle."""
    out = run_sub(SETUP + """
    plan = dist(strategy="ring", overlap="pipelined", dtype="bf16")
    with mesh:
        got = plan.compile()(params, x)
        rep = plan.instrument().run_model(params, x).validate()
    assert got.dtype == jnp.bfloat16
    assert_matches_reference(got, g, cfg, params, x, dtype="bf16", scale=2)
    # the halo and the MLP are phases of their own, priced once each
    assert [r.phase for r in rep.records] == ["distributed", "combine"] * 2
    assert not rep.mismatches(plan)
    print("OK")
    """)
    assert "OK" in out


def _graph(v, e, seed):
    rng = np.random.default_rng(seed)
    src = (rng.pareto(1.0, e) * 10).astype(np.int64) % v
    return graph_from_coo(src, rng.integers(0, v, e), v)


@pytest.mark.parametrize("v,e,shards", [(301, 1800, 4), (97, 400, 3),
                                        (64, 700, 8)])
def test_owner_buckets_hold_every_edge_once(v, e, shards):
    """Bucket ``[p, o]`` holds exactly shard p's edges whose source block
    o owns, in the shard's order (destinations ascending); its padding is
    masked, points past the block, and reads distinct rows."""
    g = _graph(v, e, seed=shards)
    pg = partition_1d(g, shards, edge_balanced=False)
    block = pg.block_size
    bs, bd, bm = (np.asarray(a) for a in (pg.bucket_src, pg.bucket_dst,
                                          pg.bucket_mask))
    assert bs.shape == bd.shape == bm.shape == (shards, shards,
                                                bs.shape[-1])
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    real = bm > 0
    assert int(real.sum()) == g.num_edges
    assert bs.shape[-1] == max(int(real[p, o].sum()) for p in range(shards)
                               for o in range(shards))
    for p in range(shards):
        mine = (dst >= p * block) & (dst < (p + 1) * block)
        for o in range(shards):
            n = int(real[p, o].sum())
            assert real[p, o, :n].all() and not real[p, o, n:].any()
            want = mine & (src // block == o)
            assert np.array_equal(bs[p, o, :n] + o * block, src[want])
            assert np.array_equal(bd[p, o, :n] + p * block, dst[want])
            assert (np.diff(bd[p, o, :n]) >= 0).all()
            pad = bs[p, o, n:]
            assert (bd[p, o, n:] == block).all()
            assert np.array_equal(pad, np.arange(len(pad)) % block)
    # the edge-balanced partition feeds the load model only: no buckets
    assert partition_1d(g, shards).bucket_src is None


def test_gin_on_a_2d_mesh_is_refused():
    g = _graph(64, 300, seed=1)
    mesh = make_mesh((1, 1), ("node", "feat"))
    with pytest.raises(ValueError, match="1-D mesh"):
        build_plan(g, PAPER_MODELS["gin"], 16, 3, mesh=mesh)

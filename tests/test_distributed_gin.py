"""GIN on a 1-D mesh: the halo sum, then the MLP on each device's own rows.

Multi-device parts run in SUBPROCESSES with fake CPU devices, as in
tests/test_distributed.py; the owner buckets and the plan's refusals are
checked here, on the one real device."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import hop_layouts
from repro.core.plan import build_plan
from repro.graph.partition import partition_1d
from repro.graph.structure import graph_from_coo
from repro.kernels import ops
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
@pytest.mark.parametrize("backend", ["xla", "pallas-tpu"])
def test_gin_halo_schedules_are_bitwise_equal(backend):
    """``none`` and ``pipelined`` accumulate the same per-hop partials in
    the same order, for GIN's sum as for GCN's mean: the same bits, eager
    and compiled, on either hop kernel (``segment_sum``, ``seg_agg``)."""
    out = run_sub(SETUP + f"""
    backend = {backend!r}
    """ + """
    outs = {}
    for ov in ("none", "pipelined"):
        plan = dist(strategy="ring", overlap=ov, backend=backend)
        with mesh:
            outs[ov] = (np.asarray(plan.run_model(params, x)),
                        np.asarray(plan.compile()(params, x)))
    for a, b in zip(outs["none"], outs["pipelined"]):
        assert np.array_equal(a, b)
    print("OK")
    """)
    assert "OK" in out


#: the same halo sum or layer with each ring hop through ``seg_agg``
#: (``hops``) and through ``segment_sum``, beside the float32 reference:
#: each case appends (blocked, flat, reference) triples to ``runs``
HOP_CASES = {
    "gin-halo": """
    ref = aggregate(g, x, op="sum", include_self=False) + x
    for ov in ("none", "pipelined"):
        def agg(hops):
            with mesh:
                return halo_aggregate(pg, xp, mesh, op="sum", overlap=ov,
                                      hops=hops)[:V]
        runs.append((agg(hop_layouts(pg, [F])[0]), agg(None), ref))
    """,
    "gcn-1d": """
    for order, width in (("combine_first", 8), ("aggregate_first", F)):
        ref = phase_ordered_layer(g, x, [(w, b)], order=order,
                                  agg_op="mean", activation="none")
        def layer(hops):
            with mesh:
                return distributed_gcn_layer(pg, xp, w, b, g.in_deg, mesh,
                    order=order, overlap="pipelined", hops=hops)[:V]
        runs.append((layer(hop_layouts(pg, [width])[0]), layer(None), ref))
    """,
    "gcn-2d": """
    p2 = partition_2d(g, 2, 2)
    mesh2 = make_mesh((2, 2), ("node", "feat"))
    x2 = pad_features_2d(x, p2)
    for order, width in (("combine_first", 8), ("aggregate_first", F)):
        ref = phase_ordered_layer(g, x, [(w, b)], order=order,
                                  agg_op="mean", activation="none")
        hops = hop_layouts(p2.nodes, [p2.feature_block(width)])[0]
        def layer(hops):
            with mesh2:
                return distributed_gcn_layer_2d(p2, x2, w, b, g.in_deg,
                    mesh2, order=order, hops=hops)[:V, :8]
        runs.append((layer(hops), layer(None), ref))
    """,
}


@pytest.mark.slow
@pytest.mark.parametrize("case", sorted(HOP_CASES))
def test_seg_agg_hops_match_segment_sum_hops_and_reference(case):
    """GIN's halo sum, the 1-D GCN layer in both phase orders and the 2-D
    layer, with every ring hop's reduce run by the ``pallas-tpu``
    ``seg_agg`` kernel (interpreted here) over the blocked owner buckets:
    each equals the ``segment_sum`` hop and the float32 reference within
    the f32 band."""
    out = run_sub(SETUP + """
    from repro.core.distributed import (distributed_gcn_layer,
        distributed_gcn_layer_2d, halo_aggregate, hop_layouts,
        pad_features, pad_features_2d)
    from repro.core.phases import aggregate, phase_ordered_layer
    from repro.graph.partition import partition_1d, partition_2d
    pg = partition_1d(g, 4, edge_balanced=False)
    xp = pad_features(x, pg.block_size, 4)
    w = jnp.asarray(rng.standard_normal((F, 8)) / np.sqrt(F), jnp.float32)
    b = jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32)
    runs = []
    """ + HOP_CASES[case] + """
    assert len(runs) == 2
    for blocked, flat, ref in runs:
        assert_allclose_dtype(blocked, flat, scale=10)
        assert_allclose_dtype(blocked, ref, scale=10)
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


@pytest.mark.parametrize("v,e,shards", [(301, 1800, 4), (97, 400, 3),
                                        (64, 700, 8), (1000, 4000, 4)])
def test_blocked_buckets_hold_every_edge_once(v, e, shards):
    """The ring hops' ``seg_agg`` layout (``hop_layouts``): every real
    edge sits exactly once in its (device, owner, destination block), at
    a seg id inside the block; pad slots are masked and read distinct
    rows; on the TPU tier a hop's gather count leaves a remainder in
    ``[1, 896]`` modulo 1024 (1000 vertices: 2 blocks of 512 slots, 1024
    ids, which the tail moves to 1040)."""
    g = _graph(v, e, seed=shards)
    pg = partition_1d(g, shards, edge_balanced=False)
    block = pg.block_size
    hops, = hop_layouts(pg, [32])
    assert hops.seg.shape == hops.mask.shape
    src, seg, mask = (np.asarray(a) for a in (hops.src, hops.seg[..., 0, :],
                                              hops.mask[..., 0, :]))
    nb, emax_p = mask.shape[2:]
    assert nb == -(-block // hops.tile_m) and emax_p % hops.tile_e == 0
    assert src.shape == (shards, shards, nb, src.shape[-1])
    assert 0 < nb * src.shape[-1] % 1024 <= 896
    real = mask > 0
    assert int(real.sum()) == g.num_edges
    assert ((0 <= seg) & (seg < hops.tile_m)).all()
    p, o, blk, slot = np.nonzero(real)
    got = np.stack([src[p, o, blk, slot] + o * block,
                    p * block + blk * hops.tile_m + seg[real]])
    want = np.stack([np.asarray(g.src), np.asarray(g.dst)])
    assert np.array_equal(got[:, np.lexsort(got[::-1])],
                          want[:, np.lexsort(want[::-1])])
    rows = jnp.arange(block, dtype=jnp.float32)[:, None]   # row i holds i
    for p in range(shards):
        for o in range(shards):
            ids = np.asarray(ops._gather_slots(rows, src[p, o],
                                               mask[p, o]))[..., 0]
            spread = np.arange(ids.size).reshape(ids.shape) % block
            pad = np.pad(~real[p, o], ((0, 0), (0, ids.shape[1] - emax_p)),
                         constant_values=True)
            assert np.array_equal(ids[pad], spread[pad])
            assert np.array_equal(ids[~pad], src[p, o][~pad])


@pytest.mark.parametrize("conv", ["gin", "gcn"])
@pytest.mark.parametrize("backend,kernel", [("auto", "segment_sum"),
                                            ("xla", "segment_sum"),
                                            ("pallas-tpu", "seg_agg")])
def test_hop_counts_and_describe_match_the_layout_read(conv, backend,
                                                       kernel):
    """``describe()`` names the hop kernel the tier gives (``"auto"`` is
    XLA's ``segment_sum`` on the CPU), and ``agg_counts()`` /
    ``halo_counts`` are the shapes of what the hops read: the flat owner
    buckets, or the blocked ``seg_agg`` layout (gathered f32 rows,
    ``gather_tail`` slots included, over the kernel's slots).  A 2-D
    partition counts its buckets once per feature shard."""
    from repro.core.distributed import halo_counts
    from repro.graph.partition import partition_2d
    g = _graph(301, 1800, seed=4)
    mesh = make_mesh((1,), ("data",))
    plan = build_plan(g, PAPER_MODELS[conv], 48, 5, mesh=mesh,
                      num_shards=4, backend=backend)
    assert [d["halo_kernel"] for d in plan.describe()] == [kernel] * 2
    assert plan.halo_kernel == kernel
    eb = plan.partition.bucket_src.shape[-1]
    for lp, hops, counts, d in zip(plan.layers, plan.hops,
                                   plan.agg_counts(), plan.describe()):
        width = lp.din if lp.order == "aggregate_first" else lp.dout
        if kernel == "seg_agg":
            assert hops.src.shape[:2] == hops.mask.shape[:2] == (4, 4)
            rows, slots = hops.src.size, hops.mask.size
            assert rows == slots + 16 * hops.mask.shape[2] * (
                hops.src.shape[-1] - hops.mask.shape[-1])
        else:
            assert hops is None
            rows = slots = 16 * eb
        want = {"edges": g.num_edges, "gather_rows": rows,
                "kernel_slots": slots, "gather_bytes": rows * width * 4}
        assert counts == want
        assert {k: d[f"agg_{k}"] for k in want} == want
    p2 = partition_2d(g, 2, 2)
    fb = p2.feature_block(48)
    for hops in (None, hop_layouts(p2.nodes, [fb])[0]):
        c = halo_counts(p2, 48, hops=hops)
        rows = 2 * (4 * p2.nodes.bucket_src.shape[-1] if hops is None
                    else hops.src.size)
        assert c["gather_rows"] == rows
        assert c["gather_bytes"] == rows * fb * 4


def test_gin_on_a_2d_mesh_is_refused():
    g = _graph(64, 300, seed=1)
    mesh = make_mesh((1, 1), ("node", "feat"))
    with pytest.raises(ValueError, match="1-D mesh"):
        build_plan(g, PAPER_MODELS["gin"], 16, 3, mesh=mesh)

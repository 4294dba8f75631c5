"""The TPU aggregation kernels compile for a described TPU v5e.

Interpret mode (every other kernel test) cannot tell whether Mosaic accepts
a block layout or whether a tile fits VMEM; the chip's own compiler can,
for a chip that is described and not attached.  Each case compiles one
kernel at a published feature width with the tiles the planner picks, under
a VMEM limit equal to ``kernels.ops.tpu_vmem_bytes`` of those tiles -- so a
pass also shows the working-set model the planner sizes against is an upper
bound on what Mosaic needs.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU compiler.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import build_plan
from repro.graph.structure import graph_from_coo
from repro.kernels import ops
from repro.kernels.fused_agg_combine import fused_agg_combine_blocked
from repro.kernels.seg_agg import seg_agg_blocked
from repro.models.gcn import PAPER_MODELS

#: Table 2 input widths: Pubmed, Reddit, Cora (Citeseer's 3703 below)
WIDTHS = (128, 500, 602, 1433)
DTYPES = (jnp.float32, jnp.bfloat16)
F_OUT = 128           # the paper models' hidden width
SEG_TILE_M = 128      # the planner's unfused aggregation tile
FUSED_TILE_M = 4096   # the widest fused tile suggest_tile_m emits


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _edges(nblocks, emax, sharding):
    """Seg ids and mask in the kernels' edge layout (ops.kernel_edges)."""
    return (_shape((nblocks, 1, emax), jnp.int32, sharding),
            _shape((nblocks, 1, emax), jnp.float32, sharding))


def _compile_seg(f, dtype, sharding):
    itemsize = jnp.dtype(dtype).itemsize
    budget = ops.tpu_vmem_budget()
    tile_e = ops.pick_tile_e(SEG_TILE_M, f, 0, itemsize, budget=budget,
                             cap=ops.SEG_TILE_E_MAX)
    limit = ops.tpu_vmem_bytes(SEG_TILE_M, tile_e, f, 0, itemsize)
    nblocks, emax = 2, 2 * tile_e

    def fn(rows, seg, mask):
        return seg_agg_blocked(rows, seg, mask, tile_m=SEG_TILE_M,
                               tile_e=tile_e, interpret=False,
                               vmem_limit_bytes=limit)

    return jax.jit(fn).lower(_shape((nblocks, emax, f), dtype, sharding),
                             *_edges(nblocks, emax, sharding)).compile()


def _compile_fused(f_in, f_out, dtype, sharding):
    itemsize = jnp.dtype(dtype).itemsize
    budget = ops.tpu_vmem_budget()
    tile_m = ops.fit_fused_tile_m(FUSED_TILE_M, f_in, f_out, 4,
                                  budget=budget)
    tile_e = ops.pick_tile_e(tile_m, f_in, f_out, itemsize, budget=budget)
    limit = ops.tpu_vmem_bytes(tile_m, tile_e, f_in, f_out, itemsize)
    nblocks, emax = 2, 2 * tile_e

    def fn(rows, seg, mask, w):
        return fused_agg_combine_blocked(rows, seg, mask, w, tile_m=tile_m,
                                         tile_e=tile_e, interpret=False,
                                         vmem_limit_bytes=limit)

    return jax.jit(fn).lower(_shape((nblocks, emax, f_in), dtype, sharding),
                             *_edges(nblocks, emax, sharding),
                             _shape((f_in, f_out), dtype, sharding)).compile()


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("f", WIDTHS)
def test_seg_agg_compiles_for_v5e(one_chip, f, dtype):
    assert "tpu_custom_call" in _compile_seg(f, dtype, one_chip).as_text()


@pytest.mark.parametrize("f", (41, 128, 500, 602))
def test_f32_seg_agg_keeps_its_tile_and_compiles_for_v5e(one_chip, f):
    """At the cells' widths the f32 kernel's working set, bf16 terms of
    the slab included, leaves the planner's edge chunk at 512 (so the
    slot layouts stay as they are), and the kernel compiles at that tile
    under the limit the planner passes, ``tpu_vmem_budget()``."""
    tile_e = ops.kernel_tile_e(SEG_TILE_M, f, 4)
    assert tile_e == 512
    nblocks, emax = 2, 2 * tile_e

    def fn(rows, seg, mask):
        return seg_agg_blocked(rows, seg, mask, tile_m=SEG_TILE_M,
                               tile_e=tile_e, interpret=False,
                               vmem_limit_bytes=ops.tpu_vmem_budget())

    compiled = jax.jit(fn).lower(
        _shape((nblocks, emax, f), jnp.float32, one_chip),
        *_edges(nblocks, emax, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("f", WIDTHS)
def test_fused_agg_combine_compiles_for_v5e(one_chip, f, dtype):
    compiled = _compile_fused(f, F_OUT, dtype, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_citeseer_fused_tile_compiles_for_v5e(one_chip):
    """Citeseer's 3703-wide input layer: the planner shrinks the fused tile
    until the kernel fits, and that tile compiles."""
    compiled = _compile_fused(3703, F_OUT, jnp.float32, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_gather_and_pad_scopes_survive_tpu_fusion(one_chip, monkeypatch):
    """The GIN forward at Pubmed's width, compiled for the chip: the
    pre-gather and the kernel stay ops of their own in the executable,
    each named for its layer's scope, so device op time in a trace can be
    put down to them (``CompiledPlan.op_scopes``).  The gather writes the
    kernel's padded slots itself; the edge-axis pad touches only the
    plan's constant ids and mask, so the ``pad`` scope holds constants
    alone, and no f32 pad of the gathered rows is left to run."""
    from repro.core.plan import hlo_op_scopes
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")  # lower for Mosaic
    rng = np.random.default_rng(0)
    v, e, f = 2000, 8000, 500
    g = graph_from_coo(rng.integers(0, v, e), rng.integers(0, v, e), v)
    plan = build_plan(g, PAPER_MODELS["gin"], f, 3, backend="pallas-tpu")
    params = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        jax.eval_shape(plan.init, jax.random.PRNGKey(0)))
    text = plan.compile().lower(
        params, _shape((v, f), jnp.float32, one_chip)).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    scopes = hlo_op_scopes(entry)
    shapes = dict(re.findall(r"%([\w.-]+) = (\w+\[[\d,]*\])", entry))
    for lp, d in zip(plan.layers, plan.describe()):
        layer, width = f"l{lp.index}", lp.din
        ops_in = {path: [op for op, p in scopes.items() if p == path]
                  for path in (f"{layer}.aggregate/gather",
                               f"{layer}.aggregate/pad",
                               f"{layer}.aggregate/seg_agg")}
        gathers = [shapes[op] for op in ops_in[f"{layer}.aggregate/gather"]
                   if op.startswith("fusion")]
        assert gathers == [f"f32[{d['agg_gather_rows']},{width}]"], ops_in
        assert all(op.startswith("constant")
                   for op in ops_in[f"{layer}.aggregate/pad"]), ops_in
        assert any(op.startswith("seg_agg")
                   for op in ops_in[f"{layer}.aggregate/seg_agg"]), ops_in
    assert not [s for op, s in shapes.items()
                if op.startswith("pad") and s.startswith("f32")], shapes


def test_gather_tail_keeps_the_tpu_gather_wide(one_chip, monkeypatch):
    """A Reddit-size source (232,965 × 128 f32) and 3 blocks padded to
    1024 slots: 3072 ids, 0 modulo 1024, where XLA's TPU gather keeps 128
    rows in flight and not 256 (2.7x slower at Reddit's size on a v5e).
    ``seg_agg_planned`` gathers 8 tail slots a block (3096 ids), the
    compiled gather keeps 256, and its rows reach the kernel with no pad
    or copy between."""
    from repro.core.dataflow import block_graph_arrays
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")  # lower for Mosaic
    rng = np.random.default_rng(0)
    v, dests, e = 232965, 192, 1800
    bg = block_graph_arrays(rng.integers(0, v, e),
                            np.sort(rng.integers(0, dests, e)), dests, 64)
    emax_p = ops.layout_counts(bg, 128, 4)["kernel_slots"] \
        // bg.nblocks
    assert (bg.nblocks, emax_p) == (3, 1024)
    assert ops.gather_tail(bg.nblocks, emax_p) == 8
    x = _shape((v, 128), jnp.float32, one_chip)

    def entry(fn):
        text = jax.jit(fn).lower(x).compile().as_text()
        return text[text.index("\nENTRY"):]

    def in_flight(text):
        return re.findall(r'"integer_config":\{"integer":"(\d+)"', text)

    planned = entry(lambda x: ops.seg_agg_planned(bg, x))
    assert in_flight(planned) == ["256"], planned
    assert re.search(r"= f32\[3096,128\]\S* fusion\(", planned), planned
    assert not re.search(r"= f32\[[\d,]+\]\S* (pad|copy)\(", planned), \
        planned
    assert "seg_agg" in planned
    src, mask = (jnp.pad(a, ((0, 0), (0, emax_p - bg.emax)))
                 for a in (bg.src, bg.mask))
    assert in_flight(entry(lambda x: ops._gather_slots(x, src, mask))) \
        == ["128"]


#: the cell's hop shapes: owner buckets of 956,740 slots (the hottest
#: owner's edges into one block of the cell's graph); blocked for seg_agg,
#: 456 destination blocks of 128 rows, 2,560 slots each (the fullest block
#: holds 2,298 edges) and a gather tail of 8
_RING_HOPS = {"segment_sum": ((956_740,), (956_740,)),
              "seg_agg": ((456, 2_568), (456, 1, 2_560))}


@pytest.mark.parametrize("backend,kernel", [("auto", "segment_sum"),
                                            ("pallas-tpu", "seg_agg")])
def test_gin_ring_forward_fits_four_v5e_chips(topo, monkeypatch, backend,
                                              kernel):
    """The ``gin-reddit.ring4`` forward compiled for four described v5e
    chips at the cell's shapes: Reddit's 232,965 rows at F=602, GIN's
    602-128-128 and 128-128-41 MLPs, 4 blocks of 58,242 rows, and each
    hop's edges in the layout its kernel reads (``_RING_HOPS``).  The hop
    arrays are arguments here, so no graph is built: every ring hop
    gathers one bucket's rows, not a shard's ~2.9M edges (on the TPU tier
    straight into the ``seg_agg`` kernel's slots, with an index count
    that keeps XLA's gather wide), each device's program fits its 16 GB,
    and the only features that cross chips are the ring's slabs.  On the
    CPU, ``"auto"`` plans XLA's ``segment_sum`` hop, as the cell's config
    does off the chip."""
    import copy
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")  # lower for Mosaic
    v, f, classes = 232_965, 602, 41
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    rng = np.random.default_rng(0)
    g = graph_from_coo(rng.integers(0, v, 64), rng.integers(0, v, 64), v)
    plan = build_plan(g, PAPER_MODELS["gin"], f, classes, mesh=mesh,
                      strategy="ring", overlap="pipelined", backend=backend)
    pg = plan.partition
    assert pg.block_size == 58_242
    assert {d["halo_kernel"] for d in plan.describe()} == {kernel}
    src_shape, seg_shape = _RING_HOPS[kernel]

    def forward(params, x, src, seg, mask):
        cell = copy.copy(plan)
        if kernel == "seg_agg":
            cell.hops = tuple(h._replace(src=src, seg=seg, mask=mask)
                              for h in plan.hops)
        else:
            cell.partition = pg._replace(bucket_src=src, bucket_dst=seg,
                                         bucket_mask=mask)
        return cell.run_model(params, x)

    def on(shape, dtype, *spec):
        return _shape(shape, dtype, NamedSharding(mesh, P(*spec)))

    params = jax.tree_util.tree_map(
        lambda a: on(a.shape, a.dtype),
        jax.eval_shape(plan.init, jax.random.PRNGKey(0)))
    hops = [on((4, 4) + shape, dt, "data")
            for shape, dt in ((src_shape, jnp.int32), (seg_shape, jnp.int32),
                              (seg_shape, jnp.float32))]
    compiled = jax.jit(forward).lower(
        params, on((4 * pg.block_size, f), jnp.float32, "data"),
        *hops).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + \
        mem.temp_size_in_bytes + mem.generated_code_size_in_bytes < 16e9
    text = compiled.as_text()
    rows = int(np.prod(src_shape))
    gathers = re.findall(r"= f32\[(\d+),(\d+)\]\S* gather\(", text)
    assert {(int(n), int(w)) for n, w in gathers} == {(rows, f),
                                                      (rows, 128)}
    assert ("tpu_custom_call" in text) == (kernel == "seg_agg")
    if kernel == "seg_agg":
        assert 0 < rows % 1024 <= 896
        # the gathered rows go to the kernel as they are: no fill-mode
        # select over them (the hop's ids are not constants)
        assert not re.search(rf"= f32\[{rows},\d+\]\S* select\(", text)
    assert "collective-permute-start" in text
    # the logits (41 wide) are gathered for the caller; no feature slab is
    gathered = re.findall(r"= f32\[([\d,]+)\]\S* all-gather\(", text)
    assert all(s.endswith(f",{classes}") for s in gathered), gathered


def test_planner_refuses_fused_tile_that_overflows_vmem():
    """At F=3703 a square layer's pinned W alone overflows VMEM: the plan
    refuses fused=True at build time instead of emitting a tile Mosaic
    would reject."""
    rng = np.random.default_rng(0)
    g = graph_from_coo(rng.integers(0, 64, 256), rng.integers(0, 64, 256),
                       64)
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(3703,),
                              name="gcn-3703")
    with pytest.raises(ValueError, match="fused=True refused"):
        build_plan(g, cfg, 3703, 3703, backend="pallas-tpu", fused=True)
    # the same layer unfused plans fine
    plan = build_plan(g, cfg, 3703, 3703, backend="pallas-tpu", fused=False)
    assert not plan.layers[0].fused

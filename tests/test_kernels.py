"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from tolerance import assert_allclose_dtype

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention as flash_pallas
from repro.kernels import fused_agg_combine as fused_mod
from repro.kernels import seg_agg as seg_mod
from repro.kernels.ref import fused_agg_combine_ref, mha_ref, seg_agg_ref

RNG = np.random.default_rng(42)


def _blocked_inputs(nblocks, emax, f, tile_m, dtype, density=0.8):
    rows = jnp.asarray(RNG.standard_normal((nblocks, emax, f)), dtype)
    seg = jnp.asarray(RNG.integers(0, tile_m, (nblocks, emax)), jnp.int32)
    mask = jnp.asarray(RNG.random((nblocks, emax)) < density, jnp.float32)
    return rows, seg, mask


def seg_agg_blocked(rows, seg, mask, *, tile_m, tile_e):
    """The kernel on (nblocks, emax) BlockedGraph arrays, laid out the way
    every production caller lays them out (``ops.kernel_edges``)."""
    seg3, mask3 = ops.kernel_edges(seg, mask, tile_e)
    return seg_mod.seg_agg_blocked(rows, seg3, mask3, tile_m=tile_m,
                                   tile_e=tile_e)


def fused_agg_combine_blocked(rows, seg, mask, w, *, tile_m, tile_e):
    seg3, mask3 = ops.kernel_edges(seg, mask, tile_e)
    return fused_mod.fused_agg_combine_blocked(rows, seg3, mask3, w,
                                               tile_m=tile_m, tile_e=tile_e)


# ---------------------------------------------------------------- seg_agg
@pytest.mark.parametrize("nblocks,emax,f,tile_m,tile_e", [
    (2, 256, 32, 16, 128),
    (4, 512, 128, 128, 256),
    (1, 1024, 64, 8, 512),
    (3, 256, 100, 64, 256),   # non-128-multiple feature dim
])
def test_seg_agg_shapes(nblocks, emax, f, tile_m, tile_e):
    rows, seg, mask = _blocked_inputs(nblocks, emax, f, tile_m, jnp.float32)
    out = seg_agg_blocked(rows, seg, mask, tile_m=tile_m, tile_e=tile_e)
    gseg = (seg + jnp.arange(nblocks)[:, None] * tile_m).reshape(-1)
    ref = seg_agg_ref(rows.reshape(-1, f), gseg, mask.reshape(-1),
                      nblocks * tile_m)
    assert_allclose_dtype(out, ref)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seg_agg_dtypes(dtype):
    rows, seg, mask = _blocked_inputs(2, 256, 64, 32, dtype)
    out = seg_agg_blocked(rows, seg, mask, tile_m=32, tile_e=128)
    gseg = (seg + jnp.arange(2)[:, None] * 32).reshape(-1)
    ref = seg_agg_ref(rows.astype(jnp.float32).reshape(-1, 64),
                      gseg, mask.reshape(-1), 64)
    assert_allclose_dtype(out, ref, dtype=dtype,
                          scale=2.0 if dtype == jnp.bfloat16 else 1.0)


def test_seg_agg_planned_sorted_ids_uneven_blocks():
    """Destination-sorted segment ids over a vertex count that is no
    ``tile_m`` multiple, two thirds of the edges in the first block,
    through the planned entry: the plain segment sum of the rows."""
    from repro.core.dataflow import block_graph_arrays
    e, f, v, tile_m = 999, 48, 117, 32
    seg = np.sort(np.concatenate([RNG.integers(0, 20, 666),
                                  RNG.integers(0, v, e - 666)]))
    src = RNG.integers(0, v, e)
    bg = block_graph_arrays(src, seg, v, tile_m)
    counts = np.asarray(bg.mask).sum(1)
    assert bg.nblocks == 4 and counts.max() > 4 * counts.min()
    x = jnp.asarray(RNG.standard_normal((v, f)), jnp.float32)
    out = ops.seg_agg_planned(bg, x)
    ref = seg_agg_ref(jnp.take(x, jnp.asarray(src), axis=0),
                      jnp.asarray(seg, jnp.int32), jnp.ones(e), v)
    assert_allclose_dtype(out, ref)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(16, 64))
@settings(max_examples=10, deadline=None)
def test_seg_agg_permutation_invariance(nblocks, echunks, f):
    """Segmented sum is invariant to edge order within a block."""
    emax, tile_m = 128 * echunks, 16
    rows, seg, mask = _blocked_inputs(nblocks, emax, f, tile_m, jnp.float32)
    out1 = seg_agg_blocked(rows, seg, mask, tile_m=tile_m, tile_e=128)
    perm = RNG.permutation(emax)
    out2 = seg_agg_blocked(rows[:, perm], seg[:, perm], mask[:, perm],
                           tile_m=tile_m, tile_e=128)
    assert_allclose_dtype(out1, out2, scale=10)


def test_seg_agg_mass_conservation():
    """sum over segments == sum over (masked) rows."""
    rows, seg, mask = _blocked_inputs(2, 256, 32, 64, jnp.float32)
    out = seg_agg_blocked(rows, seg, mask, tile_m=64, tile_e=128)
    lhs = np.asarray(out).sum(0)
    rhs = np.asarray(rows * mask[..., None]).sum((0, 1))
    assert_allclose_dtype(lhs, rhs, scale=10)


# ------------------------------------------------------- fused agg+combine
@pytest.mark.parametrize("fi,fo,tile_m", [(64, 32, 32), (100, 16, 16),
                                          (256, 128, 64)])
def test_fused_agg_combine(fi, fo, tile_m):
    nblocks, emax = 3, 512
    rows, seg, mask = _blocked_inputs(nblocks, emax, fi, tile_m, jnp.float32)
    w = jnp.asarray(RNG.standard_normal((fi, fo)) * 0.1, jnp.float32)
    out = fused_agg_combine_blocked(rows, seg, mask, w, tile_m=tile_m,
                                    tile_e=256)
    gseg = (seg + jnp.arange(nblocks)[:, None] * tile_m).reshape(-1)
    ref = fused_agg_combine_ref(rows.reshape(-1, fi), gseg, mask.reshape(-1),
                                w, nblocks * tile_m)
    assert_allclose_dtype(out, ref, scale=10)


def test_fused_equals_unfused_composition():
    """Fusion is a pure execution change: == seg_agg then matmul."""
    rows, seg, mask = _blocked_inputs(2, 256, 64, 32, jnp.float32)
    w = jnp.asarray(RNG.standard_normal((64, 48)) * 0.2, jnp.float32)
    fused = fused_agg_combine_blocked(rows, seg, mask, w, tile_m=32,
                                      tile_e=128)
    unfused = seg_agg_blocked(rows, seg, mask, tile_m=32, tile_e=128) @ w
    assert_allclose_dtype(fused, unfused, scale=10)


# ------------------------------------- planned seg_agg: the slot layout
def _sorted_edges(v, e, seed):
    rng = np.random.default_rng(seed)
    dst = np.sort(rng.integers(0, v, e))
    return rng.integers(0, v, e), dst


def _planned_case(source, f, seed=0):
    """A blocked layout whose ``emax`` is no ``tile_e`` multiple, and the
    matrix it gathers from: vertex features, or the dedup level-2 source
    ``[x ; partials]`` with ids that reach into the partial rows.  The
    ``tail`` graph pads 3 blocks to 1024 slots, so the TPU pre-gather adds
    a tail (``ops.gather_tail``)."""
    from repro.core.dataflow import block_graph_arrays
    from repro.graph.dedup import attach_blocked, build_dedup_layout
    v, e, tile_m = (192, 1800, 64) if source == "tail" else (300, 1500, 64)
    src, dst = _sorted_edges(v, e, seed)
    x = jnp.asarray(np.random.default_rng(seed + 1).standard_normal((v, f)),
                    jnp.float32)
    if source == "dedup":
        # every destination's two leading sources drawn from four hubs,
        # so many destinations share a pair
        hubs = np.random.default_rng(seed + 2).integers(0, 4, (v, 2))
        src = np.concatenate([hubs, src.reshape(v, -1)], 1).reshape(-1)
        dst = np.repeat(np.arange(v), src.size // v)
        lay = attach_blocked(build_dedup_layout(src, dst, v), tile_m)
        assert lay.num_pairs > 0
        partials = jnp.take(x, lay.pair_left, axis=0) + \
            jnp.take(x, lay.pair_right, axis=0)
        return lay.blocked, jnp.concatenate([x, partials], axis=0)
    return block_graph_arrays(src, dst, v, tile_m), x


def _old_order(bg, x, edge_weight):
    """The old order: gather to ``emax``, then pad the gathered rows to
    the kernel's edge layout."""
    rows = jnp.take(x, bg.src.reshape(-1), axis=0).reshape(
        bg.nblocks, bg.emax, x.shape[-1])
    if edge_weight is not None:
        w_blk = jnp.take(edge_weight, bg.eidx.reshape(-1), axis=0)
        rows = rows * w_blk.reshape(bg.nblocks, bg.emax, 1)
    tile_e = ops.kernel_tile_e(bg.tile_m, x.shape[-1], 4)
    seg3, mask3 = ops.kernel_edges(bg.dstl, bg.mask, tile_e)
    rows = jnp.pad(rows, ((0, 0), (0, seg3.shape[-1] - bg.emax), (0, 0)))
    out = seg_mod.seg_agg_blocked(rows, seg3, mask3, tile_m=bg.tile_m,
                                  tile_e=tile_e)
    return out[:bg.num_vertices]


def _slot_shape(bg, f):
    """``(emax_p, tail)`` of ``seg_agg_planned`` on ``bg`` at width f."""
    counts = ops.layout_counts(bg, f, 4)
    emax_p = counts["kernel_slots"] // bg.nblocks
    assert emax_p > bg.emax, "emax must not be a tile_e multiple here"
    return emax_p, counts["gather_rows"] // bg.nblocks - emax_p


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("source,weighted", [
    ("graph", False), ("graph", True), ("dedup", False), ("tail", True)])
@pytest.mark.parametrize("f", [41, 128, 500])
def test_seg_agg_planned_equals_the_old_order(source, weighted, f):
    """Gathering into the padded slots is bitwise the gather-then-pad
    order: pad slots carry mask 0, so they add an exact 0, and the tail
    slots are never read."""
    bg, x = _planned_case(source, f)
    _, tail = _slot_shape(bg, f)
    assert (tail > 0) == (source == "tail")
    w = jnp.asarray(np.random.default_rng(3).random(bg.num_edges),
                    jnp.float32) if weighted else None
    new = ops.seg_agg_planned(bg, x, w)
    np.testing.assert_array_equal(np.asarray(new),
                                  np.asarray(_old_order(bg, x, w)))


@pytest.mark.parametrize("source", ["graph", "tail"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("f", [41, 128, 500])
def test_seg_agg_planned_gathers_into_the_slot_layout(f, weighted, source):
    """One gather writes ``nblocks·(emax_p + tail)`` rows; no f32 row pad
    follows, only the 2-D pads of ids and mask."""
    bg, x = _planned_case(source, f)
    emax_p, tail = _slot_shape(bg, f)
    w = jnp.ones(bg.num_edges, jnp.float32) if weighted else None
    jaxpr = jax.make_jaxpr(lambda x, w: ops.seg_agg_planned(
        bg, x, w))(x, w).jaxpr
    eqns = list(_eqns(jaxpr))
    gathers = [q.outvars[0].aval.shape for q in eqns
               if q.primitive.name == "gather"
               and q.outvars[0].aval.shape[-1:] == (f,)]
    assert gathers == [(bg.nblocks * (emax_p + tail), f)]
    pads = [q.outvars[0].aval.shape for q in eqns
            if q.primitive.name == "pad"]
    assert pads and all(p in {(bg.nblocks, emax_p),
                              (bg.nblocks, emax_p + tail)}
                        for p in pads), pads


@pytest.mark.parametrize("nblocks,emax_p,tail", [
    (1821, 7168, 8),     # Reddit's layout: 0 modulo 1024 without a tail
    (155, 512, 0),       # Pubmed's: 512 modulo 1024 already
    (3, 1024, 8), (1, 2048, 8),
    (128, 1024, 0),      # every tail leaves 0 modulo 1024: none helps
])
def test_gather_tail_moves_the_index_count_off_the_slow_remainders(
        nblocks, emax_p, tail):
    assert ops.gather_tail(nblocks, emax_p) == tail
    if tail:
        assert 0 < nblocks * (emax_p + tail) % 1024 <= 896


@pytest.mark.parametrize("model", ["gcn", "gin"])
def test_planned_forward_pads_no_gathered_rows(model):
    """The lowered forward of a ``pallas-tpu`` plan pads ids and masks
    only: no f32 pad of rank 3 (the gathered rows) is left.  Its layout
    needs no gather tail: the interpreter pads a kernel operand whose
    slots are no block multiple (the compiled TPU forward with a tail is
    in tests/test_tpu_compile.py)."""
    import re
    from repro.core.plan import build_plan
    from repro.graph.structure import graph_from_coo
    from repro.models.gcn import PAPER_MODELS
    src, dst = _sorted_edges(300, 900, 4)
    g = graph_from_coo(src, dst, 300)
    plan = build_plan(g, PAPER_MODELS[model], 24, 5, backend="pallas-tpu",
                      fused=False)
    params = plan.init(jax.random.PRNGKey(0))
    x = jnp.ones((g.num_vertices, 24), jnp.float32)
    hlo = plan.compile().lower(params, x).as_text()
    pads = re.findall(r"stablehlo\.pad .*-> tensor<([0-9x]+)xf32>", hlo)
    assert pads, "the mask pad is gone: the test reads the wrong text"
    assert all(p.count("x") == 1 for p in pads), pads
    for lp, d in zip(plan.layers, plan.describe()):
        assert d["agg_gather_rows"] == d["agg_kernel_slots"] \
            > lp.agg_layout.nblocks * lp.agg_layout.emax


def test_no_module_outside_the_kernels_imports_private_ops_names():
    """The kernel layer's seam is its public names: no module of
    ``src/repro`` outside ``kernels/`` imports an underscore name of
    ``repro.kernels.ops`` or reads one off the module."""
    import ast
    from pathlib import Path
    root = Path(ops.__file__).resolve().parents[1]
    offenders = []
    for path in sorted(root.rglob("*.py")):
        if path.parent.name == "kernels":
            continue
        tree = ast.parse(path.read_text())
        aliases = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and \
                    node.module == "repro.kernels.ops":
                offenders += [f"{path.name}: {a.name}" for a in node.names
                              if a.name.startswith("_")]
            elif isinstance(node, ast.ImportFrom) and \
                    node.module == "repro.kernels":
                aliases |= {a.asname or a.name for a in node.names
                            if a.name == "ops"}
        offenders += [f"{path.name}: {node.value.id}.{node.attr}"
                      for node in ast.walk(tree)
                      if isinstance(node, ast.Attribute)
                      and isinstance(node.value, ast.Name)
                      and node.value.id in aliases
                      and node.attr.startswith("_")]
    assert not offenders, offenders


# --------------------------------------------------------- flash attention
CASES = [
    # b, hq, hkv, sq, sk, d, causal, window, cap
    (2, 4, 2, 128, 128, 64, True, 0, 0.0),
    (1, 8, 4, 100, 260, 32, True, 0, 50.0),
    (2, 2, 1, 64, 192, 64, True, 48, 0.0),
    (1, 4, 4, 1, 300, 64, True, 0, 0.0),          # decode shape
    (1, 2, 2, 96, 96, 128, False, 0, 0.0),        # non-causal (encoder)
]


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal,window,cap", CASES)
def test_flash_pallas_vs_ref(b, hq, hkv, sq, sk, d, causal, window, cap):
    q = jnp.asarray(RNG.standard_normal((b, hq, sq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, sk, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, sk, d)), jnp.float32)
    o1 = flash_pallas(q, k, v, causal=causal, window=window, softcap=cap,
                      tile_q=64, tile_k=64)
    o2 = mha_ref(q, k, v, causal=causal, sliding_window=window,
                 logit_softcap=cap)
    assert_allclose_dtype(o1, o2, scale=20)


def test_flash_pallas_kv_len():
    b, hq, hkv, sq, sk, d = 2, 4, 2, 8, 192, 32
    q = jnp.asarray(RNG.standard_normal((b, hq, sq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, sk, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, sk, d)), jnp.float32)
    kvl = jnp.asarray([50, 192], jnp.int32)
    o1 = flash_pallas(q, k, v, kvl, tile_q=64, tile_k=64)
    o2 = mha_ref(q, k, v, kv_len=kvl)
    assert_allclose_dtype(o1, o2, scale=20)


@pytest.mark.parametrize("dtype,scale", [(jnp.float32, 20), (jnp.bfloat16, 1)])
def test_flash_pallas_dtypes(dtype, scale):
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), dtype)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), dtype)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), dtype)
    o1 = flash_pallas(q, k, v, tile_q=32, tile_k=32)
    o2 = mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                 v.astype(jnp.float32))
    assert_allclose_dtype(o1, o2, dtype=dtype, scale=scale)

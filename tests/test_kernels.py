"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import functools
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


# ---------------------------------- seg_agg f32: three exact bf16 passes
def _f32_values(sign, lo_exp, hi_exp, n=4096, seed=0):
    """f32 values of one sign with full 24-bit significands, exponents
    drawn from [lo_exp, hi_exp]."""
    rng = np.random.default_rng(seed)
    sig = rng.integers(1 << 23, 1 << 24, n).astype(np.float64) / (1 << 23)
    exp = rng.integers(lo_exp, hi_exp + 1, n)
    return (sign * np.ldexp(sig, exp)).astype(np.float32)


@pytest.mark.parametrize("sign", [1.0, -1.0])
@pytest.mark.parametrize("lo_exp,hi_exp", [
    (0, 0),           # one binade
    (-60, 60),        # exponents far apart
    (-100, -80),      # small, residuals still normal
    (100, 127),       # up to the f32 maximum: the split never rounds up
])
def test_split_bf16x3_rebuilds_f32_bitwise(sign, lo_exp, hi_exp):
    """hi + mid + lo is the f32 value, bit for bit, and every term is a
    bf16.  Subnormal residuals are out of scope (the TPU flushes them)."""
    x = _f32_values(sign, lo_exp, hi_exp)
    x[:3] = [0.0, sign * np.finfo(np.float32).max,
             sign * np.finfo(np.float32).tiny * 2.0 ** 24]
    terms = jax.jit(seg_mod._split_bf16x3)(jnp.asarray(x))
    assert all(t.dtype == jnp.bfloat16 for t in terms)
    hi, mid, lo = (np.asarray(t, np.float64) for t in terms)
    rebuilt = ((hi + mid) + lo).astype(np.float32)
    assert np.isfinite(hi).all()
    np.testing.assert_array_equal(rebuilt.view(np.uint32), x.view(np.uint32))


def _segment_sum_f64(rows, seg, mask, tile_m):
    """Float64 block sums of (nblocks, emax, F) rows: the exact answer
    the f32 accumulation rounds."""
    nblocks, _, f = rows.shape
    out = np.zeros((nblocks, tile_m, f))
    r, s, m = (np.asarray(a, np.float64) for a in (rows, seg, mask))
    for b in range(nblocks):
        np.add.at(out[b], s[b].astype(int), r[b] * m[b][:, None])
    return out.reshape(nblocks * tile_m, f)


@pytest.mark.parametrize("f", [41, 128, 500, 602])
def test_seg_agg_f32_matches_a_float64_segment_sum(f):
    """At the cells' widths and the planner's tile, the f32 kernel is the
    float64 segment sum within the f32 accumulation band."""
    tile_m, tile_e = 128, ops.kernel_tile_e(128, f, 4)
    rows, seg, mask = _blocked_inputs(2, 2 * tile_e, f, tile_m, jnp.float32)
    out = seg_agg_blocked(rows, seg, mask, tile_m=tile_m, tile_e=tile_e)
    assert_allclose_dtype(out, _segment_sum_f64(rows, seg, mask, tile_m))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seg_agg_masked_slot_with_a_large_row_adds_exactly_zero(dtype):
    """A masked slot is excluded by its 0 in the one-hot, not by rounding:
    its row may hold a huge finite value and adds an exact 0."""
    rows, seg, mask = _blocked_inputs(2, 256, 64, 32, dtype)
    masked = np.asarray(mask) == 0
    assert masked.any()
    big = jnp.where(jnp.asarray(masked)[..., None],
                    jnp.asarray(3e38, dtype), rows)
    zero = jnp.where(jnp.asarray(masked)[..., None],
                     jnp.asarray(0, dtype), rows)
    got = seg_agg_blocked(big, seg, mask, tile_m=32, tile_e=128)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(seg_agg_blocked(zero, seg, mask, tile_m=32, tile_e=128),
                   np.float32))


def test_seg_agg_bf16_keeps_its_one_pass_result():
    """A bf16 slab takes one bf16 product a chunk, accumulated in f32 and
    rounded once: bitwise that sum computed outside the kernel."""
    nblocks, emax, f, tile_m, tile_e = 2, 256, 64, 32, 128
    rows, seg, mask = _blocked_inputs(nblocks, emax, f, tile_m,
                                      jnp.bfloat16)
    out = seg_agg_blocked(rows, seg, mask, tile_m=tile_m, tile_e=tile_e)
    onehot = jnp.where((jnp.arange(tile_m)[None, :, None] == seg[:, None])
                       & (mask[:, None] > 0), 1.0, 0.0).astype(jnp.bfloat16)
    acc = jnp.zeros((nblocks, tile_m, f), jnp.float32)
    for c in range(0, emax, tile_e):
        acc = acc + jnp.einsum("bme,bef->bmf", onehot[..., c:c + tile_e],
                               rows[:, c:c + tile_e],
                               preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(acc.astype(jnp.bfloat16).reshape(-1, f), np.float32))


def _kernel_dots(fn, *args):
    """The ``dot_general`` equations inside the ``pallas_call`` of
    ``fn(*args)``'s jaxpr."""
    eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    calls = [q for q in eqns if q.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return [q for q in _eqns(calls[0].params["jaxpr"])
            if q.primitive.name == "dot_general"]


def _asks_highest(eqn):
    precision = eqn.params.get("precision")
    return jax.lax.Precision.HIGHEST in (
        precision if isinstance(precision, tuple) else (precision,))


def _single_pass_bf16(eqn):
    return (all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)
            and eqn.params["preferred_element_type"] == jnp.float32
            and not _asks_highest(eqn))


def test_f32_seg_agg_multiplies_bf16_terms_in_single_passes():
    """Every MXU product of the f32 kernel takes bf16 operands into an f32
    accumulator, and none asks for ``Precision.HIGHEST``; the fused
    kernel's reduce does the same, and its weight product, whose W is not
    exact in bf16, keeps ``HIGHEST``."""
    rows, seg, mask = _blocked_inputs(2, 256, 64, 32, jnp.float32)
    seg3, mask3 = ops.kernel_edges(seg, mask, 128)
    dots = _kernel_dots(functools.partial(
        seg_mod.seg_agg_blocked, tile_m=32, tile_e=128), rows, seg3, mask3)
    assert len(dots) == 3 and all(map(_single_pass_bf16, dots)), \
        [q.params for q in dots]
    w = jnp.ones((64, 16), jnp.float32)
    dots = _kernel_dots(functools.partial(
        fused_mod.fused_agg_combine_blocked, tile_m=32, tile_e=128),
        rows, seg3, mask3, w)
    assert sum(map(_single_pass_bf16, dots)) == 3
    assert [q.invars[1].aval.shape for q in dots if _asks_highest(q)] \
        == [(64, 16)]


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

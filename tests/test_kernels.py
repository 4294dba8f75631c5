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


def test_seg_agg_wrapper_sorted_ids():
    e, f, v = 999, 48, 117
    seg = np.sort(RNG.integers(0, v, e)).astype(np.int32)
    rows = jnp.asarray(RNG.standard_normal((e, f)), jnp.float32)
    out = ops.seg_agg(rows, jnp.asarray(seg), v)
    ref = seg_agg_ref(rows, jnp.asarray(seg), jnp.ones(e), v)
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

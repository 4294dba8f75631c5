"""Pallas TPU kernel: collision-free segmented row aggregation (paper F3).

GPU baseline (paper): the ``scatter`` kernel -- one thread per feature element,
atomicAdd into the destination row; serialization whenever two warps hit the
same row.  The paper's guideline is "vectorize the atomic operation".

TPU adaptation (DESIGN.md §2): there are no atomics and no warps; we
restructure the reduction so collisions cannot exist:

  * edges are destination-sorted and regrouped into destination row blocks
    (``tile_m`` rows per grid step) host-side -- every grid step owns a
    disjoint output block, so grid steps never write the same row;
  * within a block, the segmented reduction is expressed as a ONE-HOT MATMUL
    on the MXU: ``out[m, f] = sum_e onehot[m, e] * rows[e, f]``.  The one-hot
    matrix is built in-register from ``broadcasted_iota == seg_ids`` --
    this is the "vectorized atomic": 128x128 row-updates per MXU pass,
    serialization-free by construction.

Precision: the 0/1 one-hot is exact in bf16, so a bf16 slab takes one
single-pass MXU product.  An f32 slab is split in VMEM into three bf16
terms that sum to it exactly, and takes three single-pass products into
the f32 accumulator; ``Precision.HIGHEST`` would emulate the f32 product
in six passes and split the one-hot as well.  Each product term is exact,
so the f32 accumulation is the only rounding either way.

Inputs are pre-gathered edge rows (the ``indexSelect`` product).  The gather
itself is XLA's native dynamic-gather (DMA-based on TPU); what the paper's
scatter kernel loses to atomics, this kernel recovers with dense MXU math.

The edge operands arrive in the layout ``kernels/ops.py`` owns (seg ids and
mask as ``(nblocks, 1, emax)``), and the VMEM limit the caller passes is the
budget ``ops.pick_tile_e`` sized ``tile_e`` against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret


def _dot(a, b, acc_dtype):
    """MXU product at full f32 precision (one bf16 pass would round f32
    operands), accumulated in ``acc_dtype``."""
    return jax.lax.dot(a.astype(acc_dtype), b.astype(acc_dtype),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=acc_dtype)


def _high_bits(x):
    """``x`` (f32) truncated to its sign, exponent and top 7 mantissa
    bits: a value bf16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split_bf16x3(x):
    """Three bf16 terms whose sum is exactly the f32 ``x``.

    Each term takes the top 8 significant bits of what the terms before
    it left; both residuals are exact in f32, and the last holds at most
    8 significant bits.  Exact for finite normal ``x`` whose residuals
    stay normal; a subnormal residual flushes to zero on the TPU.  The
    split truncates, so it never rounds a finite ``x`` up to inf."""
    hi = _high_bits(x)
    rest = x - hi
    mid = _high_bits(rest)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (rest - mid).astype(jnp.bfloat16))


def _reduce_chunk(onehot, rows, acc_dtype):
    """The segmented reduce of one edge chunk on the MXU, from the bf16
    ``onehot`` (0/1, exact in bf16).

    A bf16 slab takes one single-pass product.  An f32 slab is split into
    three bf16 terms that sum to it exactly (``_split_bf16x3``) and takes
    three single-pass products: every product term is exact, and the f32
    accumulation is the only rounding, as with ``Precision.HIGHEST``
    (whose f32 emulation takes six passes and splits the one-hot too).
    Other dtypes multiply at ``HIGHEST`` (``_dot``)."""
    if rows.dtype == jnp.bfloat16:
        return jax.lax.dot(onehot, rows, preferred_element_type=acc_dtype)
    if rows.dtype == jnp.float32:
        hi, mid, lo = (jax.lax.dot(onehot, term,
                                   preferred_element_type=acc_dtype)
                       for term in _split_bf16x3(rows))
        return hi + (mid + lo)
    return _dot(onehot, rows, acc_dtype)


def _onehot(seg_ref, mask_ref, tile_m: int, tile_e: int):
    """(tile_m, tile_e) bf16 one-hot of the chunk's local destination
    rows.  ``mask`` is validity alone: an edge with mask > 0 puts a 1 in
    its row, a padding edge (mask 0) a 0, so it adds an exact 0 for any
    finite row.  Per-edge weights scale the rows before the kernel
    (``ops.gather_seg_agg``'s ``weight``), never the mask."""
    seg = seg_ref[0]              # (1, tile_e) int32, block-local row ids
    mask = mask_ref[0]            # (1, tile_e) float32
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (tile_m, tile_e), 0)
    return jnp.where((row_ids == seg) & (mask > 0), 1.0,
                     0.0).astype(jnp.bfloat16)


def _seg_agg_kernel(seg_ref, mask_ref, rows_ref, out_ref, acc_ref, *,
                    tile_m: int, tile_e: int, acc_dtype=jnp.float32):
    """Grid: (dest_blocks, edge_chunks). Edge chunks accumulate into acc.

    ``acc_dtype`` is the VMEM accumulator precision -- f32 regardless of
    the input rows' dtype (the reduced-precision plan contract: bf16 rows
    on the wire/HBM, full-precision accumulate, one rounding at flush).
    """
    ei = pl.program_id(1)
    n_e = pl.num_programs(1)

    @pl.when(ei == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _reduce_chunk(_onehot(seg_ref, mask_ref, tile_m, tile_e),
                                  rows_ref[0], acc_dtype)

    @pl.when(ei == n_e - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_e", "interpret",
                                             "acc_dtype", "vmem_limit_bytes"))
def seg_agg_blocked(rows: jnp.ndarray, seg_local: jnp.ndarray,
                    mask: jnp.ndarray, *, tile_m: int, tile_e: int = 512,
                    interpret: Optional[bool] = None,
                    acc_dtype=jnp.float32,
                    vmem_limit_bytes: Optional[int] = None) -> jnp.ndarray:
    """Blocked segmented sum.

    Args:
      rows:      (nblocks, emax + tail, F) pre-gathered edge rows, grouped
                 by destination block (see core.dataflow.block_graph); the
                 grid stops at ``emax``, so no step reads the tail slots
                 (``kernels.ops.gather_tail``).
      seg_local: (nblocks, 1, emax) int32 destination row id LOCAL to the
                 block (the ``kernels.ops`` edge layout).
      mask:      (nblocks, 1, emax) edge validity: an edge with mask > 0
                 adds its row, one with mask 0 adds nothing.  Not a
                 weight: scale the rows before the kernel.
      tile_m:    output rows per block (static).
      tile_e:    edge chunk per grid step (static; a multiple of 128 that
                 divides emax).
      interpret: None = auto (compiled on TPU, interpreted elsewhere --
                 core.backend.default_interpret).
      acc_dtype: VMEM accumulator dtype (static).  Stays f32 even when
                 ``rows`` is bf16 (the plan's reduced-precision contract:
                 reduced storage, full-precision accumulate); the output is
                 rounded once at flush to ``rows.dtype``.
      vmem_limit_bytes: scoped VMEM the compiler may give one grid step
                 (None = the compiler's default).

    Returns (nblocks * tile_m, F) in ``rows.dtype``.
    """
    interpret = resolve_interpret(interpret)
    nblocks, _, f = rows.shape
    emax = seg_local.shape[-1]
    assert seg_local.shape == mask.shape == (nblocks, 1, emax), \
        (seg_local.shape, mask.shape, rows.shape)
    assert emax % tile_e == 0 and rows.shape[1] >= emax, \
        (emax, tile_e, rows.shape)

    out = pl.pallas_call(
        functools.partial(_seg_agg_kernel, tile_m=tile_m, tile_e=tile_e,
                          acc_dtype=acc_dtype),
        grid=(nblocks, emax // tile_e),
        in_specs=[
            pl.BlockSpec((1, 1, tile_e), lambda b, e: (b, 0, e)),  # seg ids
            pl.BlockSpec((1, 1, tile_e), lambda b, e: (b, 0, e)),  # mask
            pl.BlockSpec((1, tile_e, f), lambda b, e: (b, e, 0)),  # rows
        ],
        out_specs=pl.BlockSpec((1, tile_m, f), lambda b, e: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, tile_m, f), rows.dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, f), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="seg_agg",
    )(seg_local, mask, rows)
    return out.reshape(nblocks * tile_m, f)

"""Pallas TPU kernel: fused Aggregation -> Combination (paper F5, §5.1-3).

The paper: "a vertex is able to start the execution in Combination phase after
this vertex completes its aggregation", but GPU frameworks insert a phase
barrier and an HBM round-trip for the aggregated matrix.  Guideline: adaptive
execution granularity.

This kernel IS that guideline on TPU: the execution granularity is a
``tile_m``-row destination block.  Per grid step:

  1. segmented-reduce the block's gathered neighbor rows into a VMEM
     accumulator (one-hot MXU matmul -- see seg_agg.py);
  2. immediately hit the accumulator with the combination weight tile
     (second MXU matmul) while it is still VMEM-resident.

The (tile_m, F_in) aggregate never exists in HBM, and W stays pinned in VMEM
across all destination blocks -- the software realization of the paper's
"degree- & length-aware replacement policy" (the hottest data, W, is made
cache-permanent; DESIGN.md §2).

The working set per grid step (edge slab, W, accumulator, one-hot) is what
``kernels.ops.tpu_vmem_bytes`` models; the plan sizes ``tile_m``/``tile_e``
against the same VMEM limit the caller passes here, and refuses
``fused=True`` where no tile fits.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret
from repro.kernels.seg_agg import _dot, _onehot, _reduce_chunk


def _fused_kernel(seg_ref, mask_ref, rows_ref, w_ref, out_ref, acc_ref, *,
                  tile_m: int, tile_e: int, acc_dtype=jnp.float32):
    """``acc_dtype`` is the VMEM accumulator precision for BOTH MXU passes
    (segmented reduce and the fused GEMM) -- f32 even for bf16 rows/W (the
    reduced-precision plan contract); one rounding at the output flush."""
    ei = pl.program_id(1)
    n_e = pl.num_programs(1)

    @pl.when(ei == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _reduce_chunk(_onehot(seg_ref, mask_ref, tile_m, tile_e),
                                  rows_ref[0], acc_dtype)

    @pl.when(ei == n_e - 1)
    def _combine():
        # Phase fusion point: aggregate tile -> GEMM without leaving VMEM.
        out_ref[0] = _dot(acc_ref[...], w_ref[...],
                          acc_dtype).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "tile_e", "interpret",
                                    "acc_dtype", "vmem_limit_bytes"))
def fused_agg_combine_blocked(rows: jnp.ndarray, seg_local: jnp.ndarray,
                              mask: jnp.ndarray, w: jnp.ndarray, *,
                              tile_m: int, tile_e: int = 512,
                              interpret: Optional[bool] = None,
                              acc_dtype=jnp.float32,
                              vmem_limit_bytes: Optional[int] = None
                              ) -> jnp.ndarray:
    """out[block b] = (sum_seg rows[b]) @ w, fused in VMEM.

    rows: (nblocks, emax + tail, F_in) destination-block-grouped gathered
    rows; the grid stops at ``emax`` (``kernels.ops.gather_tail``).
    seg_local/mask: (nblocks, 1, emax) (the ``kernels.ops`` edge layout).
    w: (F_in, F_out).
    interpret: None = auto-detect (core.backend.default_interpret).
    acc_dtype: static VMEM accumulator dtype; stays f32 for reduced (bf16)
    rows/W -- storage is reduced, the accumulate is not.
    vmem_limit_bytes: scoped VMEM for one grid step (None = compiler
    default).
    Returns (nblocks * tile_m, F_out) in w.dtype.
    """
    interpret = resolve_interpret(interpret)
    nblocks, _, f_in = rows.shape
    emax = seg_local.shape[-1]
    f_out = w.shape[1]
    assert w.shape[0] == f_in, (w.shape, f_in)
    assert seg_local.shape == mask.shape == (nblocks, 1, emax), \
        (seg_local.shape, mask.shape, rows.shape)
    assert emax % tile_e == 0 and rows.shape[1] >= emax, \
        (emax, tile_e, rows.shape)

    out = pl.pallas_call(
        functools.partial(_fused_kernel, tile_m=tile_m, tile_e=tile_e,
                          acc_dtype=acc_dtype),
        grid=(nblocks, emax // tile_e),
        in_specs=[
            pl.BlockSpec((1, 1, tile_e), lambda b, e: (b, 0, e)),
            pl.BlockSpec((1, 1, tile_e), lambda b, e: (b, 0, e)),
            pl.BlockSpec((1, tile_e, f_in), lambda b, e: (b, e, 0)),
            pl.BlockSpec((f_in, f_out), lambda b, e: (0, 0)),  # W: VMEM-pinned
        ],
        out_specs=pl.BlockSpec((1, tile_m, f_out), lambda b, e: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, tile_m, f_out), w.dtype),
        scratch_shapes=[pltpu.VMEM((tile_m, f_in), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="fused_agg_combine",
    )(seg_local, mask, rows, w)
    return out.reshape(nblocks * tile_m, f_out)

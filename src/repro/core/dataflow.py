"""Inter-phase dataflow execution at adaptive granularity (paper F5, §5.1-3).

The paper: "a vertex is able to start the execution in Combination phase after
this vertex completes its aggregation ... the implementation of GCNs on GPU
misses this inter-phase dataflow", causing the aggregated intermediate to make
a full HBM round-trip and phase-level barriers to serialize memory-bound and
compute-bound work.

This module provides the *tiled* executor: destination vertices are processed
in blocks of ``tile_m`` rows; each block is aggregated and immediately
combined while the next block's edges stream in.  Two backends:

  * ``xla``        -- lax.scan over vertex blocks; XLA keeps the per-block
    aggregate in registers/cache rather than a (V, F) HBM intermediate.
  * ``pallas-tpu`` -- the fused gather->reduce->GEMM kernel
    (kernels/fused_agg_combine.py) where the block accumulator lives in VMEM
    and the weight tile is VMEM-resident across all blocks.

Granularity (``tile_m``) is the paper's "adaptive execution granularity":
large tiles amortize the weight-tile reuse (compute efficiency), small tiles
shrink the working set and expose pipeline overlap.  ``suggest_tile_m`` picks
the largest tile whose working set fits VMEM.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import PALLAS_TPU
from repro.graph.structure import Graph
from repro.profile.machine import Machine, machine_for_backend


class BlockedGraph(NamedTuple):
    """Edges regrouped by destination block with per-block static capacity.

    src:   (nblocks, emax) int32 global source ids (padded).
    dstl:  (nblocks, emax) int32 destination row LOCAL to the block.
    mask:  (nblocks, emax) f32.
    tile_m: rows per block; num_vertices: real vertex count.
    eidx:  (nblocks, emax) int32 ORIGINAL edge index of each slot (pad
           slots point at edge 0 and are masked) -- lets traced per-edge
           data (edge weights) be regrouped into this layout with one
           gather, no host round-trip (kernels/ops.seg_agg_planned).
    num_edges: real (unmasked) slots.  The kernels pad ``emax`` further
           to a ``tile_e`` multiple ``emax_p`` (pad slots carry mask 0)
           and the pre-gather writes every slot, so the
           padding waste a forward pays is ``nblocks·emax_p / num_edges``
           (``kernels.ops.layout_counts``).
    """

    src: jnp.ndarray
    dstl: jnp.ndarray
    mask: jnp.ndarray
    tile_m: int
    num_vertices: int
    eidx: Optional[jnp.ndarray] = None
    num_edges: int = 0

    @property
    def nblocks(self) -> int:
        return int(self.src.shape[0])

    @property
    def emax(self) -> int:
        return int(self.src.shape[1])


def block_offsets(block_ids: np.ndarray, nblocks: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Per-edge offset within its (sorted) block, fully vectorized.

    ``block_ids`` must be non-decreasing (edges are dst-sorted).  Returns
    (counts, offsets): edge e lands at [block_ids[e], offsets[e]] in any
    (nblocks, emax) padded layout.  O(E) numpy, no Python loop.
    """
    counts = np.bincount(block_ids, minlength=nblocks)
    starts = np.zeros(nblocks + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    offsets = np.arange(len(block_ids), dtype=np.int64) - starts[block_ids]
    return counts, offsets


def block_graph(g: Graph, tile_m: int) -> BlockedGraph:
    """Host-side regroup of a destination-sorted graph into row blocks."""
    return block_graph_arrays(np.asarray(g.src), np.asarray(g.dst),
                              g.num_vertices, tile_m)


def block_graph_arrays(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                       tile_m: int) -> BlockedGraph:
    """``block_graph`` over raw dst-sorted arrays (no ``Graph`` container).

    Exists for edge lists whose SOURCE ids live outside the destination
    row space — the dedup two-level layout (graph/dedup.py) gathers from
    the (V + P)-row ``[x ; partials]`` concatenation while its output rows
    stay the original V destinations, so a ``Graph`` (which ties both
    endpoints to one vertex count) cannot carry it.  ``num_vertices`` is
    the DESTINATION row count only; ``src`` values are unconstrained.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    v = int(num_vertices)
    nblocks = -(-v // tile_m)
    blk = dst // tile_m
    counts, offs = block_offsets(blk, nblocks)
    emax = max(8, int(-(-(counts.max() if len(src) else 1) // 8) * 8))
    bs = np.zeros((nblocks, emax), np.int32)
    bd = np.zeros((nblocks, emax), np.int32)
    bm = np.zeros((nblocks, emax), np.float32)
    be = np.zeros((nblocks, emax), np.int32)
    bs[blk, offs] = src
    bd[blk, offs] = dst - blk * tile_m
    bm[blk, offs] = 1.0
    be[blk, offs] = np.arange(len(src), dtype=np.int32)
    return BlockedGraph(jnp.asarray(bs), jnp.asarray(bd), jnp.asarray(bm),
                        tile_m, v, jnp.asarray(be), len(src))


def suggest_tile_m(in_len: int, out_len: int, avg_deg: float,
                   dtype_bytes: int = 4, vmem_budget: Optional[int] = None,
                   backend: str = "pallas-tpu",
                   machine: Optional[Machine] = None) -> int:
    """Largest aligned tile whose fused working set fits the on-chip budget.

    Working set per block: W (in*out) + accumulator (m*in) + output (m*out)
    + gathered rows stream (avg_deg*m*in, double-buffered factor 2).

    The budget and alignment come from one coherent ``machine``
    (``repro.profile.Machine``; default: the platform's preset via
    ``machine_for_backend``), the paper's F3 point that the winning kernel
    shape follows the memory hierarchy: fit one giant tile into half of
    VMEM (``machine.tile_budget()``) -- a single sequential grid walks the
    blocks, so bigger tiles only amortize the VMEM-pinned W further --
    aligned to ``machine.row_align`` rows.

    ``vmem_budget`` remains as a deprecated override; prefer passing a
    ``machine``.
    """
    if machine is None:
        machine = machine_for_backend(backend)
    per_row = (in_len + out_len + 2 * avg_deg * in_len) * dtype_bytes
    align = machine.row_align
    budget = machine.tile_budget() if vmem_budget is None else vmem_budget
    w = in_len * out_len * dtype_bytes
    m = max(align, int((budget - w) / max(per_row, 1)))
    return int(max(align, min(4096, (m // align) * align)))


def fused_gcn_layer(bg: BlockedGraph, x: jnp.ndarray, w: jnp.ndarray,
                    bias: Optional[jnp.ndarray] = None, *, agg_op: str = "mean",
                    in_deg: Optional[jnp.ndarray] = None,
                    backend: str = "xla") -> jnp.ndarray:
    """Aggregate-then-combine per vertex block; intermediate never spans V.

    Semantics: combine(aggregate(x))  == aggregate_first with single matmul;
    by linearity identical to combine_first, so this is a pure execution-
    granularity change (the paper's point).

    x: (V, F_in) padded to block multiple internally.  w: (F_in, F_out).
    """
    from repro.core.phases import _mm
    if backend == PALLAS_TPU:
        from repro.kernels import ops as kops
        out = kops.fused_agg_combine(bg.src, bg.dstl, bg.mask, x, w,
                                     tile_m=bg.tile_m)
    else:
        def body(carry, blk):
            src, dstl, mask = blk
            rows = jnp.take(x, src, axis=0) * mask[:, None]      # gather
            agg = jax.ops.segment_sum(rows, dstl, num_segments=bg.tile_m)
            out_blk = _mm(agg, w)                                 # fuse: GEMM now
            return carry, out_blk
        _, blocks = jax.lax.scan(body, 0, (bg.src, bg.dstl, bg.mask))
        out = blocks.reshape(bg.nblocks * bg.tile_m, w.shape[1])

    out = out[: bg.num_vertices]
    # self contribution + mean normalization (linear, applied post-GEMM;
    # reciprocal-multiply keeps eager == compiled bitwise -- see
    # phases.aggregate).  The self matmul goes through phases._mm so bf16
    # plan operands accumulate f32.
    if agg_op == "mean":
        assert in_deg is not None
        self_term = _mm(x[: bg.num_vertices], w)
        norm_dtype = jnp.promote_types(out.dtype, self_term.dtype)
        out = (out.astype(norm_dtype) + self_term) * (
            1.0 / (in_deg.astype(norm_dtype) + 1.0))[:, None]
    elif agg_op == "sum_self":
        out = out + _mm(x[: bg.num_vertices], w)
    if bias is not None:
        out = out + bias
    return out

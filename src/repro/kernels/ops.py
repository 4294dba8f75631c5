"""The Pallas TPU tier: public jit'd entry points for the aggregation
kernels.

These own tile selection (VMEM-budget-aware, MXU-aligned), static-shape
padding, and the glue between a plan's blocked layout and the kernels'
edge layout, so the planner and the ring hops call plain functions:

  * ``kernel_tile_e`` -- the edge chunk a kernel streams per grid step;
  * ``kernel_edges`` / ``gather_tail`` / ``layout_counts`` -- the edge
    layout the kernels read, and what one aggregation over it moves;
  * ``gather_seg_agg`` -- gather rows into that layout, then ``seg_agg``:
    the one composition both ``seg_agg_planned`` (one chip) and the ring
    hop (``core.distributed``) run;
  * ``seg_agg_planned`` / ``fused_agg_combine`` -- aggregation over a
    plan-owned ``BlockedGraph``.

The planner has already chosen this tier when it calls them, so none takes
a backend.  The kernels run compiled on a TPU and interpreted elsewhere
(``core.backend.default_interpret``; ``REPRO_PALLAS_INTERPRET=0/1``
overrides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import PALLAS_TPU
from repro.kernels.fused_agg_combine import fused_agg_combine_blocked
from repro.kernels.seg_agg import seg_agg_blocked
from repro.profile.machine import machine_for_backend


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# The kernels' edge layout and VMEM budget (owned here, nowhere else)
# ---------------------------------------------------------------------------

#: widest edge chunk each TPU kernel streams per grid step.  Candidates are
#: lane multiples, so a ``(1, 1, tile_e)`` seg/mask block is (8, 128)-legal.
SEG_TILE_E_MAX = 512
FUSED_TILE_E_MAX = 2048
_LANE = 128


def tpu_vmem_bytes(tile_m: int, tile_e: int, f_in: int, f_out: int = 0,
                   itemsize: int = 4) -> int:
    """VMEM working set of one grid step of the TPU aggregation kernels.

    ``f_out=0`` is ``seg_agg`` (output as wide as the input); otherwise the
    fused kernel with a ``(f_in, f_out)`` weight.  Counts what Mosaic
    allocates: two buffers per blocked operand (edge slab, seg/mask padded
    to 8 sublanes, W, output), the f32 accumulator scratch, and the
    chunk's in-kernel temporaries: three 4-byte ``(tile_m, tile_e)``
    arrays (iota, compare, select) and the bf16 one-hot; the chunk's f32
    products, three for an f32 slab, which is also split into three bf16
    terms through one f32 residual (``seg_agg._split_bf16x3``); the f32
    image of a reduced edge slab.  Feature widths are padded to the
    128-lane tile.  Checked against the smallest ``vmem_limit_bytes`` a
    v5e compile accepts (tests/test_tpu_compile.py): the model errs high.
    """
    fi = _round_up(f_in, _LANE)
    fo = _round_up(f_out, _LANE) if f_out else fi
    slab = tile_e * fi
    total = 2 * slab * itemsize                # edge slab, double-buffered
    total += 2 * 2 * 8 * tile_e * 4            # seg ids + mask blocks
    total += 2 * tile_m * fo * itemsize        # output block
    total += tile_m * fi * 4                   # accumulator scratch
    total += 3 * tile_m * tile_e * 4           # iota, compare, select
    total += tile_m * tile_e * 2               # bf16 one-hot
    total += tile_m * fi * 4                   # chunk product
    if itemsize == 4:
        total += 3 * slab * 2 + slab * 4       # bf16 terms, f32 residual
        total += 2 * tile_m * fi * 4           # two more chunk products
    else:
        total += slab * 4                      # f32 image of the slab
    if f_out:
        total += 2 * fi * fo * itemsize        # W, pinned
        total += tile_m * fo * 4               # combine product
    return total


def tpu_vmem_budget() -> int:
    """Scoped VMEM the TPU kernels request and their tiles are sized
    against: the device's ``Machine.tile_budget()``."""
    return machine_for_backend(PALLAS_TPU).tile_budget()


def pick_tile_e(tile_m: int, f_in: int, f_out: int = 0, itemsize: int = 4,
                *, budget: int, cap: int = FUSED_TILE_E_MAX):
    """Widest lane-multiple edge chunk (<= ``cap``) whose working set
    (``tpu_vmem_bytes``) fits ``budget``; None when even 128 does not."""
    tile_e = cap
    while tile_e >= _LANE:
        if tpu_vmem_bytes(tile_m, tile_e, f_in, f_out, itemsize) <= budget:
            return tile_e
        tile_e //= 2
    return None


def fit_fused_tile_m(tile_m: int, f_in: int, f_out: int, itemsize: int,
                     *, budget: int, align: int = 8) -> int:
    """Largest ``tile_m`` (the request, halved while needed, kept a
    multiple of ``align``) at which some edge chunk of the fused kernel
    fits ``budget``.  Raises ValueError where none does: the pinned W and
    one 128-edge slab alone overflow VMEM, so ``fused=True`` cannot run."""
    m = tile_m
    while pick_tile_e(m, f_in, f_out, itemsize, budget=budget) is None:
        if m <= align:
            raise ValueError(
                f"fused=True refused: no fused tile fits VMEM at F_in={f_in},"
                f" F_out={f_out} with {itemsize}-byte operands (W alone "
                f"takes {2 * f_in * f_out * itemsize} of the {budget}-byte "
                "budget); plan this layer with fused=False")
        m = max(align, (m // 2) // align * align)
    return m


def kernel_tile_e(tile_m: int, f_in: int, itemsize: int,
                  f_out: int = 0) -> int:
    """The edge chunk a kernel streams per grid step: ``seg_agg``'s
    (``f_out=0``, at most ``SEG_TILE_E_MAX``) or the fused kernel's with a
    ``(f_in, f_out)`` weight (at most ``FUSED_TILE_E_MAX``), the widest
    whose working set fits ``tpu_vmem_budget()``.  Raises ValueError where
    even 128 edges do not fit."""
    budget = tpu_vmem_budget()
    tile_e = pick_tile_e(tile_m, f_in, f_out, itemsize, budget=budget,
                         cap=FUSED_TILE_E_MAX if f_out else SEG_TILE_E_MAX)
    if tile_e is None:
        raise ValueError(
            f"no TPU aggregation tile fits VMEM: tile_m={tile_m}, "
            f"F_in={f_in}, F_out={f_out or f_in}, {itemsize}-byte operands "
            f"exceed the {budget}-byte budget even at tile_e={_LANE}")
    return tile_e


#: XLA's TPU gather keeps half as many rows in flight (its emitter's
#: setting drops from 256 to 128) when the index count leaves a remainder
#: of 0 or more than 896 modulo 1024: Reddit's pre-gather of 1821 × 7168
#: slots ran 2.7x slower so (TPU v5e, jax 0.9).
_GATHER_WINDOW, _GATHER_SLOW_ABOVE = 1024, 896


def gather_tail(nblocks: int, emax_p: int) -> int:
    """Slots per block the TPU pre-gather writes past ``emax_p`` so that
    its index count ``nblocks·(emax_p + tail)`` leaves a remainder in
    ``[1, 896]`` modulo 1024.  The kernels' grid stops at ``emax_p``, so
    no step reads them.  A multiple of 8, which keeps the rows' block
    view a bitcast; 0 where no such tail exists."""
    for tail in range(0, _GATHER_WINDOW, 8):
        if 0 < nblocks * (emax_p + tail) % _GATHER_WINDOW \
                <= _GATHER_SLOW_ABOVE:
            return tail
    return 0


def kernel_edges(seg_local, mask, tile_e: int, *same_padding):
    """The edge layout every aggregation kernel reads.

    Pads the per-block edge axis of the ``(nblocks, emax)`` BlockedGraph
    arrays to a ``tile_e`` multiple (pad slots carry mask 0) and lifts seg
    ids and mask to ``(nblocks, 1, emax_p)``.  A ``(1, 1, tile_e)`` block
    then ends in (full dim, lane multiple), which Mosaic accepts; a
    ``(1, tile_e)`` block over ``(nblocks, emax)`` is refused.
    ``same_padding`` arrays get the same edge-axis padding and keep their
    rank, with ``gather_tail`` more slots per block: the source ids (and
    edge indices) that the rows are then gathered by (``gather_seg_agg``).
    Leading axes before ``nblocks`` are kept: the ring hops' ``(P, P, nb,
    emax)`` owner buckets (``graph.partition.block_buckets``) are padded
    once, one ``(nb, emax_p + tail)`` gather a hop.
    """
    *lead, nblocks, emax = seg_local.shape
    emax_p = _round_up(emax, tile_e)
    tail = gather_tail(nblocks, emax_p)

    def pad(a, to):
        if to == emax:
            return a
        width = [(0, 0)] * a.ndim
        width[len(lead) + 1] = (0, to - emax)
        return jnp.pad(a, width)

    with jax.named_scope("pad"):
        return (pad(seg_local, emax_p).reshape(*lead, nblocks, 1, emax_p),
                pad(mask, emax_p).reshape(*lead, nblocks, 1, emax_p),
                *(pad(a, emax_p + tail) for a in same_padding))


def layout_counts(bg, f_in: int, itemsize: int, f_out: int = 0) -> dict:
    """What one aggregation over the blocked layout ``bg`` moves, by the
    same tiling ``seg_agg_planned`` (``f_out=0``) and ``fused_agg_combine``
    (a ``(f_in, f_out)`` weight) apply:

    * ``edges``: real edges (``bg.num_edges``);
    * ``gather_rows``: rows the pre-gather writes, straight into the
      kernel's slot layout: ``nblocks·(emax_p + gather_tail)``;
    * ``kernel_slots``: edge slots the kernel reads, ``nblocks·emax_p``;
    * ``gather_bytes``: ``gather_rows · f_in · itemsize``.
    """
    tile_e = kernel_tile_e(bg.tile_m, f_in, itemsize, f_out)
    emax_p = _round_up(bg.emax, tile_e)
    slots = bg.nblocks * emax_p
    rows = bg.nblocks * (emax_p + gather_tail(bg.nblocks, emax_p))
    return {"edges": int(bg.num_edges), "gather_rows": rows,
            "kernel_slots": slots, "gather_bytes": rows * f_in * itemsize}


def _gather_slots(x, src, mask):
    """Gather ``x`` rows by padded ``(nblocks, emax_p + tail)`` source ids
    into the kernel's slot layout ``(nblocks, emax_p + tail, F)``: one
    write of each row, and no pad of the rows afterwards.  Slots that hold
    no edge (``mask`` 0, and the tail) read rows 0, 1, 2, ... in turn, not
    all row 0: reads of one row queue behind each other (12.7 of Reddit's
    65.4 ms a layer on a v5e), and mask 0 adds a finite row as an exact 0.
    """
    nblocks, slots = src.shape
    with jax.named_scope("gather"):
        edge = jnp.pad(mask.reshape(nblocks, -1) > 0,
                       ((0, 0), (0, slots - mask.shape[-1])))
        spread = np.arange(src.size, dtype=np.int32).reshape(
            src.shape) % x.shape[0]
        ids = jnp.where(edge, src, spread)
        # the ids are in range; "clip" costs an index clamp, where the
        # default fill mode selects over every gathered row (a second
        # pass over the slab) unless the ids fold as constants
        rows = jnp.take(x, ids.reshape(-1), axis=0, mode="clip")
        return rows.reshape(nblocks, slots, x.shape[-1])


# ---------------------------------------------------------------------------
# Segmented aggregation over a destination-blocked edge layout
# ---------------------------------------------------------------------------


def gather_seg_agg(x, src, seg3, mask3, *, tile_m: int, tile_e: int,
                   weight=None) -> jnp.ndarray:
    """Gather ``x`` rows into the kernel's slots, then run ``seg_agg``.

    ``src`` ``(nblocks, emax_p + tail)``, ``seg3`` and ``mask3``
    ``(nblocks, 1, emax_p)``: one ``kernel_edges`` layout at ``tile_e``.
    ``weight``, optional ``(nblocks, emax_p + tail)``, scales each slot's
    row before the sum.  Returns the ``(nblocks * tile_m, F)`` block sums.
    """
    rows = _gather_slots(x, src, mask3)
    if weight is not None:
        rows = rows * weight[..., None].astype(rows.dtype)
    return seg_agg_blocked(rows, seg3, mask3, tile_m=tile_m, tile_e=tile_e,
                           vmem_limit_bytes=tpu_vmem_budget())


def seg_agg_planned(bg, x: jnp.ndarray, edge_weight=None) -> jnp.ndarray:
    """Trace-pure segmented aggregation over a plan-owned blocked layout.

    ``bg`` is a ``core.dataflow.BlockedGraph`` (with ``eidx``) built ONCE at
    plan time; everything here is jnp gathers and the Pallas kernel, so the
    whole call traces under ``jax.jit``/``grad`` with zero host transfers.

    x: (V, F) vertex features; ``edge_weight``: optional (E,) per-edge
    scalar, regrouped into the blocked layout via ``bg.eidx`` (one gather).
    Returns (V, F) -- ``sum_{(u,v) in E} w_uv * x_u`` per destination v.

    The gather source may carry MORE rows than the destination space: a
    ``dedup="pairs"`` plan (``graph.dedup.DedupLayout``) passes a
    ``(V+P, F)`` matrix -- the V inputs plus P pair partial sums -- and a
    blocked layout whose ``src`` ids reach into the partial rows, so the
    kernel folds the SHORTENED level-2 edge list unchanged; only the
    first-dim bound differs, never the kernel body.

    The ids are padded to the kernel's edge layout first (``kernel_edges``:
    pad slots take mask 0 and edge 0), and the rows are gathered straight
    into it (``gather_seg_agg``), as ``fused_agg_combine`` does: each row
    is written once, at ``(nblocks, emax_p + gather_tail, F)``.
    """
    if edge_weight is not None and bg.eidx is None:
        raise ValueError("BlockedGraph built without eidx cannot "
                         "regroup edge weights; rebuild via block_graph")
    tile_e = kernel_tile_e(bg.tile_m, x.shape[-1],
                           jnp.dtype(x.dtype).itemsize)
    ids = (bg.src,) if edge_weight is None else (bg.src, bg.eidx)
    seg3, mask3, src, *eidx = kernel_edges(bg.dstl, bg.mask, tile_e, *ids)
    weight = None if edge_weight is None else jnp.take(
        edge_weight, eidx[0].reshape(-1), axis=0).reshape(src.shape)
    out = gather_seg_agg(x, src, seg3, mask3, tile_m=bg.tile_m,
                         tile_e=tile_e, weight=weight)
    return out[:bg.num_vertices]


# ---------------------------------------------------------------------------
# Fused aggregation + combination (paper F5)
# ---------------------------------------------------------------------------


def fused_agg_combine(src, dst_local, mask, x, w, *,
                      tile_m: int) -> jnp.ndarray:
    """Gather x rows by ``src`` (XLA DMA gather), then fused reduce+GEMM.

    src/dst_local/mask: (nblocks, emax) BlockedGraph layout.
    x: (V, F_in); w: (F_in, F_out).  Returns (nblocks*tile_m, F_out).
    The kernel walks a sequential edge-chunk grid with a VMEM scratch
    accumulator; ``tile_e`` is sized by ``pick_tile_e`` against the same
    budget passed to the compiler.
    """
    f_in, f_out = w.shape
    tile_e = kernel_tile_e(tile_m, f_in, jnp.dtype(x.dtype).itemsize, f_out)
    seg3, mask3, src = kernel_edges(dst_local, mask, tile_e, src)
    rows = _gather_slots(x, src, mask3)
    return fused_agg_combine_blocked(
        rows, seg3, mask3, w, tile_m=tile_m, tile_e=tile_e,
        vmem_limit_bytes=tpu_vmem_budget())

"""Public jit'd wrappers for the Pallas kernels.

These own tile selection (VMEM-budget-aware, MXU-aligned), static-shape
padding, and the host<->kernel layout glue so the rest of the framework calls
plain functions.  Interpret mode is auto-detected per platform
(``core.backend.default_interpret``: interpreted off-TPU, compiled on TPU;
override with ``REPRO_PALLAS_INTERPRET=0/1``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import PALLAS_GPU, PALLAS_TPU
from repro.core.backend import default_interpret as _interpret
from repro.core.backend import interpret_for, resolve_backend
from repro.kernels import ref as kref
from repro.profile.machine import machine_for_backend
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_agg_combine import fused_agg_combine_blocked
from repro.kernels.gpu_agg import (fused_agg_combine_gpu_blocked,
                                   seg_agg_gpu_blocked)
from repro.kernels.seg_agg import seg_agg_blocked


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


#: ONE remediation text shared by the ``seg_agg`` tracing ValueError and
#: the ``host-in-trace`` AST lint rule (repro.analysis.ast_lint), so the
#: error a user hits and the finding a reviewer reads agree verbatim on
#: the fix: route through the trace-pure planned entry points.
SEG_AGG_REMEDIATION = (
    "seg_agg regroups edges on the host and cannot run inside jit/grad; "
    "dispatch the trace-pure seg_agg_planned instead -- via a plan from "
    "build_plan, plan_for_conv, or plan_for_phases (each owns a blocked "
    "layout), or call seg_agg_planned directly with a "
    "core.dataflow.block_graph layout")


# ---------------------------------------------------------------------------
# The kernels' edge layout and VMEM budget (owned here, nowhere else)
# ---------------------------------------------------------------------------

#: widest edge chunk each TPU kernel streams per grid step.  Candidates are
#: lane multiples, so a ``(1, 1, tile_e)`` seg/mask block is (8, 128)-legal.
SEG_TILE_E_MAX = 512
FUSED_TILE_E_MAX = 2048
_LANE = 128

#: GPU tier: the edge chunk shares the SM with ``A100.target_ctas`` peers,
#: so it stays a small warp-aligned slab, not a VMEM-budgeted one.
_GPU_TILE_E = 128


def tpu_vmem_bytes(tile_m: int, tile_e: int, f_in: int, f_out: int = 0,
                   itemsize: int = 4) -> int:
    """VMEM working set of one grid step of the TPU aggregation kernels.

    ``f_out=0`` is ``seg_agg`` (output as wide as the input); otherwise the
    fused kernel with a ``(f_in, f_out)`` weight.  Counts what Mosaic
    allocates: two buffers per blocked operand (edge slab, seg/mask padded
    to 8 sublanes, W, output), the f32 accumulator scratch, and the
    chunk's in-kernel temporaries (four ``(tile_m, tile_e)`` arrays for
    iota, compare, one-hot and its cast; the f32 product; the f32 image
    of a reduced edge slab).  Feature widths are padded to the 128-lane
    tile.  Checked against the smallest ``vmem_limit_bytes`` a v5e compile
    accepts (tests/test_tpu_compile.py): the model errs high.
    """
    fi = _round_up(f_in, _LANE)
    fo = _round_up(f_out, _LANE) if f_out else fi
    slab = tile_e * fi
    total = 2 * slab * itemsize                # edge slab, double-buffered
    total += 2 * 2 * 8 * tile_e * 4            # seg ids + mask blocks
    total += 2 * tile_m * fo * itemsize        # output block
    total += tile_m * fi * 4                   # accumulator scratch
    total += 4 * tile_m * tile_e * 4           # one-hot temporaries
    total += tile_m * fi * 4                   # chunk product
    if itemsize != 4:
        total += slab * 4                      # f32 image of the slab
    if f_out:
        total += 2 * fi * fo * itemsize        # W, pinned
        total += tile_m * fo * 4               # combine product
    return total


def tpu_vmem_budget(backend: str = PALLAS_TPU) -> int:
    """Scoped VMEM the TPU kernels request and their tiles are sized
    against: the device's ``Machine.tile_budget()``."""
    return machine_for_backend(backend).tile_budget()


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


def _tile_e(backend: str, tile_m: int, f_in: int, f_out: int,
            itemsize: int, cap: int) -> int:
    if backend == PALLAS_GPU:
        return _GPU_TILE_E
    tile_e = pick_tile_e(tile_m, f_in, f_out, itemsize,
                         budget=tpu_vmem_budget(backend), cap=cap)
    if tile_e is None:
        raise ValueError(
            f"no TPU aggregation tile fits VMEM: tile_m={tile_m}, "
            f"F_in={f_in}, F_out={f_out or f_in}, {itemsize}-byte operands "
            f"exceed the {tpu_vmem_budget(backend)}-byte budget even at "
            f"tile_e={_LANE}")
    return tile_e


#: XLA's TPU gather keeps half as many rows in flight (its emitter's
#: setting drops from 256 to 128) when the index count leaves a remainder
#: of 0 or more than 896 modulo 1024: Reddit's pre-gather of 1821 × 7168
#: slots ran 2.7x slower so (TPU v5e, jax 0.9).
_GATHER_WINDOW, _GATHER_SLOW_ABOVE = 1024, 896


def gather_tail(backend: str, nblocks: int, emax_p: int) -> int:
    """Slots per block the TPU pre-gather writes past ``emax_p`` so that
    its index count ``nblocks·(emax_p + tail)`` leaves a remainder in
    ``[1, 896]`` modulo 1024.  The kernels' grid stops at ``emax_p``, so
    no step reads them.  A multiple of 8, which keeps the rows' block
    view a bitcast; 0 off the TPU tier or where no such tail exists."""
    if backend != PALLAS_TPU:
        return 0
    for tail in range(0, _GATHER_WINDOW, 8):
        if 0 < nblocks * (emax_p + tail) % _GATHER_WINDOW \
                <= _GATHER_SLOW_ABOVE:
            return tail
    return 0


def kernel_edges(seg_local, mask, tile_e: int, *same_padding,
                 backend=None):
    """The edge layout every aggregation kernel reads.

    Pads the per-block edge axis of the ``(nblocks, emax)`` BlockedGraph
    arrays to a ``tile_e`` multiple (pad slots carry mask 0) and lifts seg
    ids and mask to ``(nblocks, 1, emax_p)``.  A ``(1, 1, tile_e)`` block
    then ends in (full dim, lane multiple), which Mosaic accepts; a
    ``(1, tile_e)`` block over ``(nblocks, emax)`` is refused.
    ``same_padding`` arrays get the same edge-axis padding and keep their
    rank: on the planned paths the source ids (and edge indices) that the
    rows are then gathered by (``_gather_slots``), with
    ``gather_tail(backend, ...)`` more slots per block; rows the caller
    grouped itself (``seg_agg_pregrouped``, no backend).
    """
    nblocks, emax = seg_local.shape
    emax_p = _round_up(emax, tile_e)
    tail = gather_tail(backend, nblocks, emax_p)

    def pad(a, to):
        if to == emax:
            return a
        return jnp.pad(a, ((0, 0), (0, to - emax))
                       + ((0, 0),) * (a.ndim - 2))

    with jax.named_scope("pad"):
        return (pad(seg_local, emax_p).reshape(nblocks, 1, emax_p),
                pad(mask, emax_p).reshape(nblocks, 1, emax_p),
                *(pad(a, emax_p + tail) for a in same_padding))


def layout_counts(bg, f_in: int, itemsize: int, backend: str,
                  f_out: int = 0) -> dict:
    """What one aggregation over the blocked layout ``bg`` moves, by the
    same tiling ``seg_agg_planned`` (``f_out=0``) and ``fused_agg_combine``
    (a ``(f_in, f_out)`` weight) apply:

    * ``edges``: real edges (``bg.num_edges``);
    * ``gather_rows``: rows the pre-gather writes, straight into the
      kernel's slot layout: ``nblocks·(emax_p + gather_tail)``;
    * ``kernel_slots``: edge slots the kernel reads, ``nblocks·emax_p``;
    * ``gather_bytes``: ``gather_rows · f_in · itemsize``.
    """
    backend = resolve_backend(backend)
    tile_e = _tile_e(backend, bg.tile_m, f_in, f_out, itemsize,
                     FUSED_TILE_E_MAX if f_out else SEG_TILE_E_MAX)
    emax_p = _round_up(bg.emax, tile_e)
    slots = bg.nblocks * emax_p
    rows = bg.nblocks * (emax_p + gather_tail(backend, bg.nblocks, emax_p))
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
        return jnp.take(x, ids.reshape(-1), axis=0).reshape(
            nblocks, slots, x.shape[-1])


# ---------------------------------------------------------------------------
# Segmented aggregation over a destination-sorted edge list
# ---------------------------------------------------------------------------


def _seg_agg_call(backend: str, rows, seg_local, mask, tile_m: int):
    """Pad pre-grouped ``(nblocks, emax, F)`` rows and their edge arrays to
    the kernel's edge layout, then run it (``_seg_agg_kernel``)."""
    tile_e = _tile_e(backend, tile_m, rows.shape[-1], 0,
                     jnp.dtype(rows.dtype).itemsize, SEG_TILE_E_MAX)
    seg3, mask3, rows = kernel_edges(seg_local, mask, tile_e, rows)
    return _seg_agg_kernel(backend, rows, seg3, mask3, tile_m, tile_e)


def _seg_agg_kernel(backend: str, rows, seg3, mask3, tile_m: int,
                    tile_e: int):
    """Run the tier's blocked kernel (TPU sequential-grid vs GPU
    row-owned) on operands already in the ``kernel_edges`` layout.
    ``backend`` must already be resolved, so entry and interpret mode can
    never disagree."""
    if backend == PALLAS_GPU:
        return seg_agg_gpu_blocked(rows, seg3, mask3, tile_m=tile_m,
                                   tile_e=tile_e,
                                   interpret=interpret_for(backend))
    return seg_agg_blocked(rows, seg3, mask3, tile_m=tile_m, tile_e=tile_e,
                           interpret=interpret_for(backend),
                           vmem_limit_bytes=tpu_vmem_budget(backend))


def seg_agg(rows: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int,
            tile_m: int = 128, backend: str = PALLAS_TPU) -> jnp.ndarray:
    """Drop-in segment_sum(rows, seg_ids) -- the SLOW ad-hoc fallback.

    Requires ``seg_ids`` sorted (destination-sorted edges -- the framework
    invariant).  This entry performs the O(E) block regrouping on the HOST
    on *every call* (``device_get`` + numpy), so it cannot be traced
    (``jax.jit`` / ``grad`` raise) and it re-pays the regrouping cost per
    invocation.  It exists only for one-off calls on un-planned graphs.

    Repeated-graph callers must go through the plan-owned blocked layout
    instead: ``GraphExecutionPlan`` builds it once per (graph, tile_m)
    (``core.plan._blocked_for``) and dispatches ``seg_agg_planned`` --
    trace-pure, zero host transfers.  ``phases.aggregate(..., layout=...)``
    is the phase-level door.  ``backend`` selects the kernel tier
    ("pallas-tpu" | "pallas-gpu"; "pallas"/"auto" resolve per platform --
    see core/backend.py).
    """
    backend = resolve_backend(backend)
    e, f = rows.shape
    if isinstance(seg_ids, jax.core.Tracer):
        raise ValueError(SEG_AGG_REMEDIATION)
    # documented host fallback -- the Tracer guard above is the contract
    seg_np = np.asarray(jax.device_get(seg_ids))  # analysis: allow(host-in-trace)
    nblocks = _round_up(num_segments, tile_m) // tile_m
    blk = seg_np // tile_m
    counts = np.bincount(blk, minlength=nblocks)
    emax = max(int(counts.max()) if len(counts) else 1, 1)
    seg_l = np.zeros((nblocks, emax), np.int32)
    mask = np.zeros((nblocks, emax), np.float32)
    from repro.core.dataflow import block_offsets
    _, offs = block_offsets(blk, nblocks)
    seg_l[blk, offs] = seg_np - blk * tile_m
    mask[blk, offs] = 1.0
    bs_rows = jnp.zeros((nblocks, emax, f), rows.dtype).at[
        jnp.asarray(blk), jnp.asarray(offs)].set(rows)
    out = _seg_agg_call(backend, bs_rows, jnp.asarray(seg_l),
                        jnp.asarray(mask), tile_m)
    return out[:num_segments]


def seg_agg_pregrouped(rows_blocked, seg_local, mask, tile_m: int,
                       backend: str = PALLAS_TPU) -> jnp.ndarray:
    """Kernel entry for already block-grouped inputs (BlockedGraph layout:
    ``(nblocks, emax[, F])``)."""
    return _seg_agg_call(resolve_backend(backend), rows_blocked, seg_local,
                         mask, tile_m)


def seg_agg_planned(bg, x: jnp.ndarray, edge_weight=None, *,
                    backend: str = PALLAS_TPU) -> jnp.ndarray:
    """Trace-pure segmented aggregation over a plan-owned blocked layout.

    ``bg`` is a ``core.dataflow.BlockedGraph`` (with ``eidx``) built ONCE at
    plan time; everything here is jnp gathers and the Pallas kernel, so the
    whole call traces under ``jax.jit``/``grad`` with zero host transfers --
    the production replacement for the ad-hoc ``seg_agg`` regrouping.

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
    into it (``_gather_slots``), as ``fused_agg_combine`` does: each row is
    written once, at ``(nblocks, emax_p + gather_tail, F)``.
    """
    backend = resolve_backend(backend)
    if edge_weight is not None and bg.eidx is None:
        raise ValueError("BlockedGraph built without eidx cannot "
                         "regroup edge weights; rebuild via block_graph")
    tile_e = _tile_e(backend, bg.tile_m, x.shape[-1], 0,
                     jnp.dtype(x.dtype).itemsize, SEG_TILE_E_MAX)
    ids = (bg.src,) if edge_weight is None else (bg.src, bg.eidx)
    seg3, mask3, src, *eidx = kernel_edges(bg.dstl, bg.mask, tile_e, *ids,
                                           backend=backend)
    rows = _gather_slots(x, src, mask3)
    if edge_weight is not None:
        w_blk = jnp.take(edge_weight, eidx[0].reshape(-1),
                         axis=0).reshape(src.shape)
        rows = rows * w_blk[..., None].astype(rows.dtype)
    out = _seg_agg_kernel(backend, rows, seg3, mask3, bg.tile_m, tile_e)
    return out[:bg.num_vertices]


# ---------------------------------------------------------------------------
# Fused aggregation + combination (paper F5)
# ---------------------------------------------------------------------------


def fused_agg_combine(src, dst_local, mask, x, w, *, tile_m: int,
                      backend: str = PALLAS_TPU) -> jnp.ndarray:
    """Gather x rows by ``src`` (XLA DMA gather), then fused reduce+GEMM.

    src/dst_local/mask: (nblocks, emax) BlockedGraph layout.
    x: (V, F_in); w: (F_in, F_out).  Returns (nblocks*tile_m, F_out).
    ``backend`` selects the kernel tier: "pallas-tpu" (sequential edge-chunk
    grid + VMEM scratch, ``tile_e`` sized by ``pick_tile_e`` against the
    same budget passed to the compiler) or "pallas-gpu" (one CTA per block,
    register accumulator -- kernels/gpu_agg.py); "pallas"/"auto" resolve
    per platform.
    """
    backend = resolve_backend(backend)
    f_in, f_out = w.shape
    tile_e = _tile_e(backend, tile_m, f_in, f_out,
                     jnp.dtype(x.dtype).itemsize, FUSED_TILE_E_MAX)
    seg3, mask3, src = kernel_edges(dst_local, mask, tile_e, src,
                                    backend=backend)
    rows = _gather_slots(x, src, mask3)
    if backend == PALLAS_GPU:
        return fused_agg_combine_gpu_blocked(
            rows, seg3, mask3, w, tile_m=tile_m, tile_e=tile_e,
            interpret=interpret_for(backend))
    return fused_agg_combine_blocked(
        rows, seg3, mask3, w, tile_m=tile_m, tile_e=tile_e,
        interpret=interpret_for(backend),
        vmem_limit_bytes=tpu_vmem_budget(backend))


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, kv_len=None, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    tile_q: int = 128, tile_k: int = 128) -> jnp.ndarray:
    return _flash(q, k, v, kv_len, causal=causal, window=window,
                  softcap=softcap, tile_q=tile_q, tile_k=tile_k,
                  interpret=_interpret())


# Re-export oracles for convenience in tests/benchmarks.
ref = kref

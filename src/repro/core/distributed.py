"""Distributed GCN-family execution: vertex-partitioned aggregation via
shard_map.

The paper profiles a single GPU; this module is the cluster-scale story its
Table 4 implies (DESIGN.md §8.5): with a 1-D destination partition the
Aggregation phase's remote traffic is one feature row per cut edge, so
running Combination first shrinks the COLLECTIVE term by in_len/out_len --
the multi-chip restatement of the paper's 4.7x.

Two interchangeable aggregation strategies (both exact):

  * ``allgather``  -- one all-gather of the full feature matrix per layer,
    then purely local gather+segment-reduce.  Simple; wire bytes V*F.
  * ``ring``       -- collective_permute steps around the data-axis ring; at
    each step every device reduces the contributions of the block it
    currently holds, reading only the bucket of its edges whose sources
    that block owns (``PartitionedGraph.bucket_*``).  Same total wire
    bytes as all-gather but only O(V/P * F) resident.

A distributed layer is two steps.  ``halo_aggregate`` exchanges the halo
and returns each vertex's neighbour sum plus its own row (over in-degree
+ 1 for the mean) in the partition layout; the layer's combine then runs
on the rows each device owns: one matmul for GCN/SAGE
(``distributed_gcn_layer``, either phase order), GIN's MLP with its
interior ReLU (``phases.combine``, called by ``core.plan``) after the sum.

The ring strategy additionally has two SCHEDULES, selected by the
``overlap=`` plan decision (``build_plan(overlap=...)``, priced by
:func:`choose_overlap`):

  * ``overlap="none"``       -- ``_ring_local``: single-buffered; each hop
    reduces the resident slab and only then passes it onward (P sends, the
    send serialized behind the hop's partial combine).
  * ``overlap="pipelined"``  -- ``_ring_local_pipelined``: double-buffered;
    each hop issues the ``ppermute`` FIRST, so hop k+1's slab is in flight
    while hop k's resident slab is matmul-reduced into the accumulator --
    the collective rides under the per-hop partial combine instead of in
    front of it.  P-1 sends (the last resident slab is reduced without a
    send).  The per-hop partials are accumulated in exactly the same order
    as the single-buffered schedule, so both schedules are bit-for-bit
    equal -- eager and under ``plan.compile()``.

Both strategies run under shard_map on the ``data`` axis; per-shard edge
lists and the ring's owner buckets come from graph.partition (uniform
blocks, padded static shapes).
:func:`overlap_model` / :func:`choose_overlap` price the schedules against
a ``Machine`` (per-hop link bytes vs. per-hop partial-combine work), and
``plan.instrument()`` reports the resulting exposed vs. overlapped
collective time per distributed record.

**2-D (node x feature) partitioning** (``distributed_gcn_layer_2d``)
generalizes the same halo patterns to a multi-host mesh: device (p, q) owns
node block p restricted to feature columns q, the ring/all-gather halo runs
along the *node* axis on rows that are only F/Q wide (per-device halo bytes
/ Q), and the Combination GEMM is a feature-parallel partial matmul closed
with one reduce-scatter (``psum_scatter``) over the *feature* axis.  The
intended placement is node
axis across hosts (the expensive, DCN-crossing halo shrinks by Q) and
feature axis across the fast intra-host links (the reduce-scatter stays
local).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.backend import PALLAS_TPU
from repro.graph.partition import (Partition2D, PartitionedGraph,
                                   block_buckets)
from repro.kernels.ops import (SEG_TILE_E_MAX, _gather_slots,
                               _seg_agg_kernel, _tile_e, kernel_edges)


def pad_features(x: jnp.ndarray, block: int, num_shards: int) -> jnp.ndarray:
    """Pad vertex features to num_shards*block rows (partition layout)."""
    total = block * num_shards
    v = x.shape[0]
    return jnp.pad(x, ((0, total - v), (0, 0)))


def _require_uniform(pg: PartitionedGraph) -> None:
    """The shard_map strategies lay out rows as p*block + local; that needs
    the UNIFORM partition (partition_1d(..., edge_balanced=False)).  The
    edge-balanced variant feeds the analytic load model instead."""
    starts = np.asarray(pg.vtx_start)
    expect = np.arange(pg.num_shards) * pg.block_size
    expect = np.minimum(expect, pg.num_vertices)
    if not np.array_equal(starts, expect):
        raise ValueError(
            "distributed aggregation requires a uniform partition; build "
            "with partition_1d(g, P, edge_balanced=False)")


def _local_agg(x_full, src, dst_local, mask, block):
    rows = jnp.take(x_full, src, axis=0) * mask[:, None]
    return jax.ops.segment_sum(rows, dst_local, num_segments=block)


def _allgather_local(x_loc, srcl, dstl, mskl, block, nsh, axis,
                     kernel=None):
    """Per-device all-gather halo body (inside shard_map, over ``axis``)."""
    del nsh, kernel
    x_full = jax.lax.all_gather(x_loc, axis, tiled=True)
    return _local_agg(x_full, srcl, dstl, mskl, block)


class HopLayout(NamedTuple):
    """The ring hops' owner buckets in the ``seg_agg`` kernel's layout
    (``kernels.ops.kernel_edges``), built once at plan build by
    :func:`hop_layouts`: bucket ``[shard, owner]`` blocked by destination
    block of ``tile_m`` rows (``graph.partition.block_buckets``).

    src:  (P, P, nb, emax_p + tail) int32 source ids local to the owner.
    seg:  (P, P, nb, 1, emax_p) int32 destination row local to its block.
    mask: (P, P, nb, 1, emax_p) f32 1.0 for real edges, 0.0 padding.
    """

    src: jnp.ndarray
    seg: jnp.ndarray
    mask: jnp.ndarray
    backend: str
    tile_m: int
    tile_e: int

    @property
    def kernel(self):
        """The static half a hop dispatches on."""
        return self.backend, self.tile_m, self.tile_e


def hop_layouts(pg: PartitionedGraph, widths, backend: str = PALLAS_TPU):
    """One :class:`HopLayout` per exchanged row width in ``widths`` (the
    hop gathers f32 rows: a reduced wire slab is promoted first).  The
    host blocking is done once, and layers whose widths give the same
    ``tile_e`` share one padded layout."""
    tile_m = max(8, min(128, -(-pg.block_size // 8) * 8))
    src, seg, mask = block_buckets(pg, tile_m)
    padded = {}
    out = []
    for width in widths:
        tile_e = _tile_e(backend, tile_m, int(width), 0, 4, SEG_TILE_E_MAX)
        if tile_e not in padded:
            seg3, mask3, src_p = kernel_edges(seg, mask, tile_e, src,
                                              backend=backend)
            padded[tile_e] = HopLayout(src_p, seg3, mask3, backend, tile_m,
                                       tile_e)
        out.append(padded[tile_e])
    return out


def _hop_partial(buf, k, p, bsrc, bseg, bmsk, block, nsh, kernel=None):
    """Partial combine of hop k's resident slab: the contributions of the
    block currently held (``(p - k) mod P`` -- ring sends i -> i+1), read
    from that owner's bucket of the device's edges alone.  Shared by BOTH
    ring schedules so their per-hop math -- and therefore their
    accumulation order -- is structurally identical (bitwise-equal outputs
    are part of the overlap contract).

    ``kernel`` None: the flat bucket (``PartitionedGraph.bucket_*``:
    destinations ascending, padding masked and out of range), gathered
    and summed by XLA's sorted ``segment_sum``.  Otherwise the bucket in
    the ``seg_agg`` layout (:class:`HopLayout`, ``kernel`` its static
    half): the rows are gathered into the kernel's slots
    (``ops._gather_slots``) and reduced by the tier's kernel, then the
    ``nb * tile_m`` output rows are cut to the block.  Both promote a
    reduced (bf16) slab to the mask's f32 before the sum."""
    owner = jnp.mod(p - k, nsh)                   # whose block we hold
    src, seg, msk = (jax.lax.dynamic_index_in_dim(a, owner, keepdims=False)
                     for a in (bsrc, bseg, bmsk))
    if kernel is None:
        rows = jnp.take(buf, src, axis=0) * msk[:, None]
        part = jax.ops.segment_sum(rows, seg, num_segments=block,
                                   indices_are_sorted=True)
    else:
        backend, tile_m, tile_e = kernel
        rows = _gather_slots(
            buf.astype(jnp.promote_types(buf.dtype, msk.dtype)), src, msk)
        part = _seg_agg_kernel(backend, rows, seg, msk, tile_m,
                               tile_e)[:block]
    # the barrier pins each partial's rounding: the pipelined schedule's
    # last partial sits outside the scan, where XLA would otherwise fuse
    # it with the accumulate differently (a 1-ulp drift between schedules)
    return jax.lax.optimization_barrier(part)


def _ring_local(x_loc, bsrc, bseg, bmsk, block, nsh, axis, kernel=None):
    """Per-device ring halo body, single-buffered (``overlap="none"``):
    nsh hops of collective_permute over ``axis``, each hop reducing the
    currently-held block's bucket and THEN passing the block onward -- the
    send waits behind the hop's partial combine, so the wire time is fully
    exposed.  Shared by the 1-D path (axis = the single data axis) and the
    2-D path (axis = the node axis of the mesh; feature columns ride
    along).
    """
    p = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % nsh) for i in range(nsh)]  # ring

    def hop(carry, k):
        buf, acc = carry
        acc = acc + _hop_partial(buf, k, p, bsrc, bseg, bmsk, block, nsh,
                                 kernel)
        buf = jax.lax.ppermute(buf, axis, perm)   # pass block onward
        return (buf, acc), None

    # acc dtype: _hop_partial's f32 mask multiply promotes reduced (bf16)
    # slabs to f32 partials, so the accumulator must be the promoted type
    # while the ppermute wire keeps carrying the reduced x_loc slab.
    # f32 slabs: promote_types(f32, f32) == f32 -- unchanged.
    acc0 = jnp.zeros((block, x_loc.shape[-1]),
                     jnp.promote_types(x_loc.dtype, bmsk.dtype))
    (_, acc), _ = jax.lax.scan(hop, (x_loc, acc0), jnp.arange(nsh))
    return acc


def _ring_local_pipelined(x_loc, bsrc, bseg, bmsk, block, nsh, axis,
                          kernel=None):
    """Per-device ring halo body, double-buffered (``overlap="pipelined"``).

    Each hop issues the ``ppermute`` FIRST -- hop k+1's slab is in flight
    while hop k's resident slab is reduced into the accumulator -- and the
    final resident slab is reduced without a send, so the ring costs P-1
    sends (vs. P single-buffered) and every send rides under a partial
    combine.  This is the collective restatement of the accelerator
    double-buffering discipline (start the next transfer, process the
    current slot).

    Bitwise contract: the per-hop partials (``_hop_partial``) accumulate in
    the SAME order as ``_ring_local`` -- hop 0..P-1 added left to right
    onto a zero accumulator -- so both schedules return bit-identical
    results; only the issue order of communication vs. compute differs.
    """
    p = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % nsh) for i in range(nsh)]  # ring

    def hop(carry, k):
        buf, acc = carry
        nxt = jax.lax.ppermute(buf, axis, perm)   # in flight during reduce
        acc = acc + _hop_partial(buf, k, p, bsrc, bseg, bmsk, block, nsh,
                                 kernel)
        return (nxt, acc), None

    # same promoted accumulator as _ring_local (f32 partials over a reduced
    # bf16 wire slab); identical type for f32 slabs
    acc0 = jnp.zeros((block, x_loc.shape[-1]),
                     jnp.promote_types(x_loc.dtype, bmsk.dtype))
    (buf, acc), _ = jax.lax.scan(hop, (x_loc, acc0), jnp.arange(nsh - 1))
    # last hop: the slab is already resident -- reduce it, send nothing
    return acc + _hop_partial(buf, nsh - 1, p, bsrc, bseg, bmsk, block, nsh,
                              kernel)


_STRATEGIES = {"ring": _ring_local, "allgather": _allgather_local}

#: resolved overlap schedules a distributed layer accepts ("auto" is a
#: plan-level request resolved by ``choose_overlap`` before dispatch)
OVERLAP_MODES = ("none", "pipelined")


def _halo_body(strategy: str, overlap: str,
               hops: Optional[HopLayout] = None):
    """Resolve (strategy, overlap) to the per-device halo body, validating
    the combination: pipelining and hop layouts need the ring's per-hop
    structure."""
    if strategy not in _STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; "
                         f"expected one of {sorted(_STRATEGIES)}")
    if overlap not in OVERLAP_MODES:
        raise ValueError(
            f"unknown overlap {overlap!r}; expected 'none' | 'pipelined' "
            "('auto' is resolved at plan build -- see choose_overlap)")
    if hops is not None and strategy != "ring":
        raise ValueError("hop layouts serve the ring's per-hop reduce; "
                         "the all-gather halo has none")
    if overlap == "pipelined":
        if strategy != "ring":
            raise ValueError(
                "overlap='pipelined' requires strategy='ring'; the "
                "all-gather halo is one collective with no per-hop "
                "structure to pipeline")
        return _ring_local_pipelined
    return _STRATEGIES[strategy]


def aggregate_allgather(pg: PartitionedGraph, x: jnp.ndarray, mesh: Mesh,
                        axis: str = "data") -> jnp.ndarray:
    """x: (P*block, F) sharded over `axis` -> aggregated (P*block, F)."""
    _require_uniform(pg)
    block = pg.block_size

    def fn(x_local, src, dst_local, mask, starts):
        out = _allgather_local(x_local[0], src[0], dst_local[0], mask[0],
                               block, pg.num_shards, axis)
        return out[None]

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis, None),
                  P(axis)),
        out_specs=P(axis, None), check_vma=False,
    )(x.reshape(pg.num_shards, -1, x.shape[-1]), pg.src, pg.dst_local,
      pg.mask, pg.vtx_start).reshape(x.shape[0], x.shape[-1])


def aggregate_ring(pg: PartitionedGraph, x: jnp.ndarray, mesh: Mesh,
                   axis: str = "data", *, overlap: str = "none",
                   hops: Optional[HopLayout] = None) -> jnp.ndarray:
    """Ring halo exchange: collective_permutes with a partial reduce per
    hop over the held block's owner bucket.  ``overlap`` picks the
    schedule: ``"none"`` = single-buffered (``_ring_local``),
    ``"pipelined"`` = double-buffered with each send in flight under the
    resident slab's reduce (``_ring_local_pipelined``); both are
    bit-for-bit equal.  ``hops`` (:func:`hop_layouts`) runs each hop's
    reduce through the ``seg_agg`` kernel; None keeps XLA's
    ``segment_sum``."""
    _require_uniform(pg)
    block = pg.block_size
    nsh = pg.num_shards
    local = _halo_body("ring", overlap)
    kernel = None if hops is None else hops.kernel

    def fn(x_local, src, seg, mask):
        out = local(x_local[0], src[0], seg[0], mask[0], block, nsh, axis,
                    kernel)
        return out[None]

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(axis, None),) + (P(axis),) * 3,
        out_specs=P(axis, None), check_vma=False,
    )(x.reshape(nsh, -1, x.shape[-1]),
      *_halo_edges(pg, "ring", hops)).reshape(x.shape[0], x.shape[-1])


def _halo_edges(pg: PartitionedGraph, strategy: str,
                hops: Optional[HopLayout] = None):
    """The ``(P, ...)`` edge arrays a strategy's halo body reads: the
    owner buckets for the ring (blocked for ``seg_agg`` when ``hops`` is
    given), the flat per-shard lists for the all-gather."""
    if hops is not None:
        return hops.src, hops.seg, hops.mask
    if strategy == "ring":
        return pg.bucket_src, pg.bucket_dst, pg.bucket_mask
    return pg.src, pg.dst_local, pg.mask


def halo_aggregate(pg: PartitionedGraph, x: jnp.ndarray, mesh: Mesh, *,
                   op: str = "sum", in_deg=None, strategy: str = "ring",
                   axis: str = "data", overlap: str = "none",
                   hops: Optional[HopLayout] = None) -> jnp.ndarray:
    """The aggregation half of a distributed layer, in the partition
    layout: each vertex's in-neighbour sum plus its own row (``op="sum"``),
    or that over ``in_deg + 1`` (``op="mean"``, a multiply by the
    reciprocal).  ``x`` is ``(P*block, F)`` sharded over ``axis``; the
    result is too, so a layer's combine runs on the rows each device
    owns.  ``hops``: as in :func:`aggregate_ring` (ring only)."""
    _halo_body(strategy, overlap, hops)   # validate the combination
    if strategy == "ring":
        out = aggregate_ring(pg, x, mesh, axis, overlap=overlap, hops=hops)
    else:
        out = aggregate_allgather(pg, x, mesh, axis)
    out = out + x
    if op == "sum":
        return out
    if op != "mean":
        raise ValueError(f"distributed aggregation is sum or mean, not "
                         f"{op!r}")
    deg = jnp.maximum(
        in_deg.astype(jnp.promote_types(x.dtype, jnp.float32)) + 1.0,
        1.0)[:, None]
    deg = pad_features(deg, pg.block_size, pg.num_shards)
    # reciprocal-multiply normalization (not broadcast division) so the
    # jitted plan.compile() path stays bit-for-bit equal to eager dispatch
    return out * (1.0 / jnp.where(deg == 0, 1.0, deg))


def halo_bytes(pg: PartitionedGraph, feature_len: int,
               dtype_bytes: int = 4) -> dict:
    """Analytic collective cost of one distributed Aggregation (both strats).

    Reported by bench_ordering to show the combine-first collective saving.
    """
    v_padded = pg.block_size * pg.num_shards
    per_device = v_padded * feature_len * dtype_bytes * \
        (pg.num_shards - 1) / pg.num_shards
    # cut edges: sources not owned by the destination shard
    src = np.asarray(pg.src)
    starts = np.asarray(pg.vtx_start)
    owners = np.clip(np.searchsorted(starts, src, side="right") - 1, 0,
                     pg.num_shards - 1)
    mine = owners == np.arange(pg.num_shards)[:, None]
    cut_edges = int((np.asarray(pg.mask) * ~mine).sum())
    return {
        "allgather_bytes_per_device": per_device,
        "ring_bytes_per_device": per_device,  # same total, spread over hops
        "bytes_per_hop_per_device":           # one slab per ring hop
            pg.block_size * feature_len * dtype_bytes,
        "ring_hops": max(pg.num_shards - 1, 0),
        "cut_edges": cut_edges,
        "min_halo_bytes": cut_edges * feature_len * dtype_bytes,
    }


# ---------------------------------------------------------------------------
# Overlap pricing (the plan's ``overlap="auto"`` decision model)
# ---------------------------------------------------------------------------

#: minimum modeled saving (fraction of the exchange's single-buffered time)
#: at which ``choose_overlap`` commits to the pipelined schedule -- below
#: this the double-buffer's extra resident slab and scheduling constraints
#: buy nothing material, so auto keeps the simpler single-buffered ring.
OVERLAP_SAVING_THRESHOLD = 0.02


def overlap_model(pg: PartitionedGraph, feature_len: int, machine, *,
                  strategy: str = "ring", dtype_bytes: int = 4) -> dict:
    """Price both ring schedules for ONE halo exchange on ``machine``.

    The model the plan's ``overlap="auto"`` decision (and the exposed /
    overlapped split in ``plan.instrument()`` reports) is built on:

      * per hop, every device sends one (block, feature_len) slab over a
        single interconnect link -- ``t_wire_hop = Machine.hop_time(bytes)``
        (per-hop link bandwidth + link latency, NOT the aggregate
        ``interconnect_total``: a ring saturates one link per direction);
      * per hop, the resident slab's partial combine is priced as the
        full aggregation roofline divided by the shard count: a device's
        whole edge list, where a hop now reads one owner bucket of it
        (about a P-th), so the model errs toward hiding the wire.

    Single-buffered (``overlap="none"``) exposes every hop's wire time;
    the pipelined schedule hides ``min(t_wire, t_comp)`` per hop under the
    partial combine.  ``feature_len`` is the row width the exchange
    actually moves: dout under combine-first, din under aggregate-first,
    divided by the feature-shard count on a 2-D partition (callers pass
    ``p2.feature_block(...)``).

    Returns a dict with per-hop terms (``t_wire_hop_s`` / ``t_comp_hop_s``
    / ``bytes_per_hop``), both schedules' exposed collective seconds
    (``exposed_none_s`` / ``exposed_pipelined_s``), the pipelined hidden
    time (``overlapped_pipelined_s``), the single-buffered exchange time
    (``t_none_s``) and the relative saving (``saving_frac``).
    """
    from repro.core.phases import aggregate_cost
    from repro.profile.machine import get_machine
    m = get_machine(machine)
    nsh = pg.num_shards
    hops = max(nsh - 1, 0)
    bytes_hop = pg.block_size * feature_len * dtype_bytes
    agg = aggregate_cost(_local_graph_view(pg), feature_len, dtype_bytes)
    # resident-slab partial combine, per device per hop (see docstring)
    t_comp_hop = max(agg["flops"] / nsh / m.peak_flops,
                     agg["bytes"] / nsh / m.hbm_bw)
    if strategy == "ring" and hops > 0:
        t_wire_hop = m.hop_time(bytes_hop)
        exposed_none = hops * t_wire_hop
        overlapped = hops * min(t_wire_hop, t_comp_hop)
        exposed_pipelined = hops * max(t_wire_hop - t_comp_hop, 0.0)
    else:
        # all-gather (one collective, nothing to hide) or a single shard
        v_padded = pg.block_size * nsh
        total = v_padded * feature_len * dtype_bytes * hops / max(nsh, 1)
        t_wire_hop = m.hop_time(total) if total else 0.0
        exposed_none = exposed_pipelined = t_wire_hop
        overlapped = 0.0
    t_none = hops * t_comp_hop + exposed_none
    return {
        "strategy": strategy, "hops": hops, "bytes_per_hop": bytes_hop,
        "t_wire_hop_s": t_wire_hop, "t_comp_hop_s": t_comp_hop,
        "exposed_none_s": exposed_none,
        "exposed_pipelined_s": exposed_pipelined,
        "overlapped_pipelined_s": overlapped,
        "t_none_s": t_none,
        "saving_frac": overlapped / t_none if t_none > 0 else 0.0,
    }


def choose_overlap(pg: PartitionedGraph, feature_lens, machine, *,
                   strategy: str = "ring", dtype_bytes: int = 4) -> str:
    """Resolve ``overlap="auto"`` -> ``"none" | "pipelined"`` for a plan.

    ``feature_lens`` is the exchanged row width -- one int, or a sequence
    (one per layer; a model's layers share one schedule, so the decision
    sums modeled savings across them).  Commits to the pipelined schedule
    iff the hidden collective time is at least ``OVERLAP_SAVING_THRESHOLD``
    of the single-buffered exchange time -- so the decision flips with the
    ``Machine``'s interconnect: a near-infinite link leaves nothing worth
    hiding (``"none"``), a link comparable to the per-hop combine hides
    half the wire time (``"pipelined"``), and the all-gather strategy
    (no per-hop structure) is always ``"none"``.

    Worked example::

        >>> choose_overlap(pg, [128, 7], TPU_V5E)
        'pipelined'
        >>> fast = replace(TPU_V5E, interconnect_bw=1e18, link_latency_s=0)
        >>> choose_overlap(pg, [128, 7], fast)
        'none'
    """
    if strategy != "ring":
        return "none"
    if isinstance(feature_lens, (int, np.integer)):
        feature_lens = [feature_lens]
    models = [overlap_model(pg, int(fl), machine, strategy=strategy,
                            dtype_bytes=dtype_bytes)
              for fl in feature_lens]
    saving = sum(m["overlapped_pipelined_s"] for m in models)
    t_none = sum(m["t_none_s"] for m in models)
    if t_none <= 0.0:
        return "none"
    return "pipelined" if saving >= OVERLAP_SAVING_THRESHOLD * t_none \
        else "none"


def _local_graph_view(pg: PartitionedGraph):
    """Minimal |V|/|E| stats view for the scheduler's analytic cost model."""
    import types
    return types.SimpleNamespace(
        num_vertices=pg.num_vertices,
        num_edges=int(np.asarray(pg.mask).sum()))


def _reduce_wire(h: jnp.ndarray, dtype: str) -> jnp.ndarray:
    """Reduce the halo-exchange operand to the plan dtype's wire width:
    bf16 cast (half the ppermute bytes), int8 per-row fake-quant (the
    values an int8 wire + f32 accumulate would move; the 1-byte width is
    priced analytically), identity for f32."""
    if dtype == "bf16":
        return h.astype(jnp.bfloat16)
    if dtype == "int8-agg":
        from repro.core.phases import quantize_int8
        return quantize_int8(h)
    return h


def distributed_gcn_layer(pg: PartitionedGraph, x, w, bias, in_deg,
                          mesh: Mesh, *, order: Optional[str] = None,
                          strategy: str = "ring", axis: str = "data",
                          overlap: str = "none", dtype: str = "f32",
                          hops: Optional[HopLayout] = None):
    """One distributed GCN layer with explicit phase ordering (Table 4).

    combine_first: project locally (embarrassingly parallel GEMM), then
    aggregate projected rows -- halo moves out_len-wide rows.
    aggregate_first: aggregate raw features (halo moves in_len-wide rows),
    then project.  ``order=None`` asks the scheduler's cost model (which at
    cluster scale also prices the collective term -- same in/out ratio).

    ``overlap`` picks the ring halo SCHEDULE (``"none"`` single-buffered |
    ``"pipelined"`` double-buffered, each send in flight under the resident
    slab's partial combine); both return bit-identical results, and
    pipelining requires ``strategy="ring"``.  ``"auto"`` is resolved at
    plan build by :func:`choose_overlap`, never passed here.

    ``dtype`` is the plan's resolved execution precision: ``"f32"`` is the
    unchanged (bitwise-golden) path; ``"bf16"`` casts operands to bf16 so
    the halo's ppermute wire moves HALF the bytes while every partial
    combine still accumulates f32; ``"int8-agg"`` fake-quantizes only the
    exchanged aggregation operand (per-row scales, f32 accumulate) and
    keeps the GEMM in f32.

    ``hops`` (:func:`hop_layouts`, at the exchanged width) runs the ring
    hops through the ``seg_agg`` kernel; None keeps ``segment_sum``.

    This is the shard_map primitive; model-level code reaches it through a
    ``GraphExecutionPlan`` built with ``mesh=``/``num_shards=`` (core/plan.py)
    rather than calling it with hand-threaded flags.
    """
    from repro.core.phases import _mm
    if order is None:
        from repro.core.scheduler import choose_ordering
        order = choose_ordering(
            _local_graph_view(pg), int(w.shape[0]), int(w.shape[1]),
            agg_op="mean", n_mlp_layers=1)
    if dtype == "bf16":
        x = x.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
        bias = bias.astype(jnp.bfloat16)
    mean = functools.partial(halo_aggregate, pg, mesh=mesh, op="mean",
                             in_deg=in_deg, strategy=strategy, axis=axis,
                             overlap=overlap, hops=hops)
    if order == "combine_first":
        out = mean(_reduce_wire(_mm(x, w), dtype))  # the wire carries h
    else:
        out = _mm(mean(_reduce_wire(x, dtype)), w)  # the wire carries x
    out = out + bias
    return out.astype(jnp.bfloat16) if dtype == "bf16" else out


# ---------------------------------------------------------------------------
# 2-D (node x feature) partitioned execution
# ---------------------------------------------------------------------------


def pad_features_2d(x: jnp.ndarray, p2: Partition2D) -> jnp.ndarray:
    """Pad (V, F) features to the (P*block, Q*fblock) partition layout."""
    fb = p2.feature_block(x.shape[1])
    rows = p2.block_size * p2.node_shards - x.shape[0]
    cols = fb * p2.feat_shards - x.shape[1]
    return jnp.pad(x, ((0, rows), (0, cols)))


def distributed_gcn_layer_2d(p2: Partition2D, x, w, bias, in_deg,
                             mesh: Mesh, *, order: Optional[str] = None,
                             strategy: str = "ring",
                             axes=("node", "feat"),
                             overlap: str = "none", dtype: str = "f32",
                             hops: Optional[HopLayout] = None):
    """One GCN layer on a 2-D (node x feature) device mesh (exact).

    Device (p, q) owns node block p's rows restricted to feature block q.
    Per ordering:

    combine_first: partial GEMM with the device's W row-block, closed by a
    reduce-scatter over the feature axis (fast intra-host links, each device
    receiving its own output column block), then the ring/all-gather halo along the node axis moves
    rows only ``F_out/Q`` wide -- the per-device halo bytes of the 1-D
    partition divided by Q *on top of* Table 4's in/out ratio saving.

    aggregate_first: halo first on the raw ``F_in/Q``-wide column slice
    (purely feature-parallel -- each feature shard's halo is independent),
    then the same partial-GEMM + reduce-scatter.

    Args mirror :func:`distributed_gcn_layer`; ``x`` must be in the padded
    ``(P*block, Q*fblock_in)`` layout (see :func:`pad_features_2d`) and the
    result is ``(P*block, Q*fblock_out)`` -- pad columns are exact zeros.
    ``axes`` names the (node, feature) mesh axes; ``order=None`` asks the
    scheduler's cost model.  ``overlap`` picks the node-axis ring schedule
    exactly as in :func:`distributed_gcn_layer` (the pipelined double
    buffer hides each F/Q-wide slab's wire time under the resident partial
    combine; bit-identical to the single-buffered schedule).  ``dtype``
    mirrors :func:`distributed_gcn_layer`: f32 is the unchanged bitwise
    path; bf16 halves the node-axis halo slab the ring actually moves
    (the feature-axis reduce-scatter keeps f32 partials -- its cross-
    device sum IS the accumulator); int8-agg fake-quantizes only the
    exchanged aggregation operand.  ``hops`` is the node-axis ring's
    :class:`HopLayout` at the ``F/Q`` column width, as in
    :func:`distributed_gcn_layer`.  Model-level
    code reaches this through a ``GraphExecutionPlan`` built with a 2-D
    ``mesh=`` (core/plan.py).
    """
    from repro.core.phases import _mm
    pg = p2.nodes
    _require_uniform(pg)
    node_ax, feat_ax = axes
    nsh, q_sh = pg.num_shards, p2.feat_shards
    block = pg.block_size
    f_in, f_out = int(w.shape[0]), int(w.shape[1])
    fb_in, fb_out = p2.feature_block(f_in), p2.feature_block(f_out)
    if order is None:
        from repro.core.scheduler import choose_ordering
        order = choose_ordering(_local_graph_view(pg), f_in, f_out,
                                agg_op="mean", n_mlp_layers=1)
    local = _halo_body(strategy, overlap, hops)
    kernel = None if hops is None else hops.kernel

    if dtype == "bf16":
        x = x.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
        bias = bias.astype(jnp.bfloat16)

    # zero-pad W/bias onto the (Q*fb_in, Q*fb_out) grid: pad x columns hit
    # zero W rows, pad W columns produce zero outputs -- exactness is free
    wp = jnp.zeros((q_sh * fb_in, q_sh * fb_out), w.dtype)
    wp = wp.at[:f_in, :f_out].set(w)
    bp = jnp.zeros((q_sh * fb_out,), w.dtype).at[:f_out].set(bias)

    deg = jnp.maximum(
        in_deg.astype(jnp.promote_types(x.dtype, jnp.float32)) + 1.0,
        1.0)[:, None]
    deg = pad_features(deg, block, nsh)
    # reciprocal of the (rows, 1) degree column: multiplied, never divided
    # (bitwise eager/compiled equality -- see distributed_gcn_layer)
    rdeg = 1.0 / jnp.where(deg == 0, 1.0, deg)

    expect = (nsh * block, q_sh * fb_in)
    if x.shape != expect:
        raise ValueError(f"x must be in the padded 2-D layout {expect}, "
                         f"got {tuple(x.shape)} (see pad_features_2d)")

    def fn(x_blk, src, dstl, msk, rdeg_blk, wp_, bp_):
        x_loc = x_blk.reshape(block, fb_in)
        srcl, dl, ml = src[0], dstl[0], msk[0]
        rdg = rdeg_blk[0]
        qi = jax.lax.axis_index(feat_ax)

        def w_block(fb):
            return jax.lax.dynamic_slice(wp_, (qi * fb, 0),
                                         (fb, q_sh * fb_out))

        def combine(h):
            # partial GEMM closed with a reduce-scatter over the feature
            # axis: each device receives only its own (block, fb_out)
            # column slice -- 1/Q the wire bytes of psum + local slice.
            # _mm keeps reduced (bf16) partials accumulating f32; f32
            # operands take the identical plain matmul.
            return jax.lax.psum_scatter(_mm(h, w_block(fb_in)), feat_ax,
                                        scatter_dimension=1, tiled=True)

        if order == "combine_first":
            # the node-axis halo wire carries the reduced combine output
            hq = _reduce_wire(combine(x_loc), dtype)     # (block, fb_out)
            out = (local(hq, srcl, dl, ml, block, nsh, node_ax, kernel)
                   + hq) * rdg
        else:
            xw = _reduce_wire(x_loc, dtype)
            agg = local(xw, srcl, dl, ml, block, nsh, node_ax, kernel)
            out = combine((agg + xw) * rdg)
        out = out + jax.lax.dynamic_slice(bp_, (qi * fb_out,), (fb_out,))
        return out.reshape(1, block, 1, fb_out)

    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(node_ax, None, feat_ax, None), P(node_ax), P(node_ax),
                  P(node_ax), P(node_ax, None, None),
                  P(None, None), P(None)),
        out_specs=P(node_ax, None, feat_ax, None), check_vma=False,
    )(x.reshape(nsh, block, q_sh, fb_in), *_halo_edges(pg, strategy, hops),
      rdeg.reshape(nsh, block, 1), wp, bp)
    out = out.reshape(nsh * block, q_sh * fb_out)
    return out.astype(jnp.bfloat16) if dtype == "bf16" else out


def halo_bytes_2d(p2: Partition2D, feature_len: int,
                  dtype_bytes: int = 4) -> dict:
    """Analytic per-device halo cost of the 2-D partition: the 1-D numbers
    evaluated at the F/Q column slice each device actually exchanges."""
    out = halo_bytes(p2.nodes, p2.feature_block(feature_len), dtype_bytes)
    out["feat_shards"] = p2.feat_shards
    return out


# ---------------------------------------------------------------------------
# Schedule-exact wire accounting (the static analyzer's ground truth)
# ---------------------------------------------------------------------------


def wire_dtype_bytes(dtype: str) -> int:
    """Bytes per element ACTUALLY moved by the halo collectives.

    ``_reduce_wire`` casts the exchanged slab to bf16 (2 bytes) under
    ``dtype="bf16"``; ``int8-agg`` fake-quantizes but keeps the f32
    carrier on the wire (4 bytes -- the 1-byte width is the analytic
    model's aspiration, not what the traced program ships), and f32
    ships f32.  This is the itemsize a jaxpr-level byte extraction
    (``repro.analysis.jaxpr_lint.collective_bytes``) must see.
    """
    return {"f32": 4, "bf16": 2, "int8-agg": 4}[dtype]


def schedule_wire_bytes(partition, feature_len: int, *,
                        strategy: str = "ring", overlap: str = "none",
                        dtype: str = "f32", combine_out_len=None) -> dict:
    """Schedule-exact per-device collective bytes of ONE distributed
    layer's TRACED schedule, by collective primitive.

    Unlike :func:`halo_bytes` (an analytic lower bound: cut edges x
    feature width) this prices the program the trace actually emits, so
    ``repro.analysis`` can equate it to jaxpr-extracted totals byte for
    byte:

      * single-buffered ring (``overlap="none"``): the scan body sends
        one slab per iteration over ``num_shards`` iterations (the last
        send is the schedule's redundant wrap-around hop), so
        ``ppermute`` moves ``num_shards * block * flen * wire`` bytes;
      * pipelined ring (``overlap="pipelined"``): ``num_shards - 1``
        in-flight sends, the resident slab never moves;
      * ``strategy="allgather"``: one tiled ``all_gather`` whose operand
        is the local slab (``block * flen * wire`` bytes in);
      * 2-D partitions (pass a ``Partition2D``): the halo slab narrows
        to ``feature_block(feature_len)`` columns and every layer adds
        one feature-axis ``psum_scatter`` (jaxpr ``reduce_scatter``)
        whose operand is the f32 partial GEMM ``(block,
        feat_shards * feature_block(combine_out_len))`` -- always 4
        bytes/elt: bf16 operands accumulate to f32 via
        ``preferred_element_type``.

    Wire element width comes from :func:`wire_dtype_bytes` (NOT the
    analytic ``DTYPE_BYTES`` -- int8-agg ships its f32 carrier).
    Returns per-primitive byte totals plus ``total_bytes``.
    """
    from repro.graph.partition import Partition2D
    two_d = isinstance(partition, Partition2D)
    pg = partition.nodes if two_d else partition
    if two_d and combine_out_len is None:
        raise ValueError("2-D schedules need combine_out_len (the layer's "
                         "dout) to price the feature-axis psum_scatter")
    wire = wire_dtype_bytes(dtype)
    flen = partition.feature_block(feature_len) if two_d else feature_len
    out = {"ppermute_sends": 0, "ppermute_bytes_per_send": 0,
           "ppermute_bytes": 0, "all_gather_bytes": 0,
           "reduce_scatter_bytes": 0, "psum_bytes": 0,
           "wire_dtype_bytes": wire}
    if strategy == "ring":
        sends = pg.num_shards if overlap == "none" \
            else max(pg.num_shards - 1, 0)
        per = pg.block_size * flen * wire
        out.update(ppermute_sends=sends, ppermute_bytes_per_send=per,
                   ppermute_bytes=sends * per)
    elif strategy == "allgather":
        out["all_gather_bytes"] = pg.block_size * flen * wire
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    if two_d:
        fb_out = partition.feature_block(combine_out_len)
        out["reduce_scatter_bytes"] = \
            pg.block_size * partition.feat_shards * fb_out * 4
    out["total_bytes"] = (out["ppermute_bytes"] + out["all_gather_bytes"]
                          + out["reduce_scatter_bytes"] + out["psum_bytes"])
    return out


def halo_counts(partition, feature_len: int, *, strategy: str = "ring",
                overlap: str = "none", dtype: str = "f32",
                hops: Optional[HopLayout] = None) -> dict:
    """What one distributed layer's aggregation moves in one forward, in
    the keys of ``kernels.ops.layout_counts`` summed over the mesh --
    ``edges`` (real edges, once per feature shard), ``gather_rows`` and
    ``kernel_slots`` (the edge slots the halo bodies gather: every ring
    hop reads one owner bucket, the all-gather body the device's flat
    list), ``gather_bytes`` (those rows at the exchanged width and the
    itemsize gathered) -- plus ``wire_bytes``, what ONE device sends over
    the halo collectives (``schedule_wire_bytes``: ``(P-1)`` slabs
    pipelined, ``P`` single-buffered).  With ``hops`` a hop gathers
    ``nb * (emax_p + tail)`` f32 rows of which the kernel reads
    ``nb * emax_p`` slots (:class:`HopLayout`'s shapes); without, the
    flat bucket's ``Eb`` at the wire itemsize."""
    from repro.graph.partition import Partition2D
    two_d = isinstance(partition, Partition2D)
    pg = partition.nodes if two_d else partition
    q = partition.feat_shards if two_d else 1
    flen = partition.feature_block(feature_len) if two_d else feature_len
    itemsize = wire_dtype_bytes(dtype)
    if hops is not None:
        # every device runs P hops over its own row of the (P, P) buckets
        rows, slots, itemsize = q * hops.src.size, q * hops.mask.size, 4
    else:
        per = pg.bucket_src.shape[-1] * pg.num_shards \
            if strategy == "ring" else pg.src.shape[-1]
        rows = slots = pg.num_shards * q * int(per)
    # only the halo's terms are read: the 2-D reduce-scatter, which
    # needs the layer's output width, belongs to the combine
    wire = schedule_wire_bytes(partition, feature_len, strategy=strategy,
                               overlap=overlap, dtype=dtype,
                               combine_out_len=feature_len)
    return {"edges": int(np.asarray(pg.mask).sum()) * q,
            "gather_rows": int(rows), "kernel_slots": int(slots),
            "gather_bytes": int(rows) * flen * itemsize,
            "wire_bytes": wire["ppermute_bytes"] + wire["all_gather_bytes"]}

"""1-D and 2-D graph partitioning for distributed aggregation.

**1-D (node)**: each device owns a contiguous block of destination vertices
(all edges whose dst falls in the block).  Blocks are *edge-balanced*:
boundaries are chosen so every shard carries ~|E|/P edges, not ~|V|/P
vertices -- heavy-tailed degree distributions otherwise leave one shard with
most of the work (the cluster analogue of the paper's load-imbalance
remarks).

**2-D (node x feature)**: a P-way node partition crossed with a Q-way split
of the feature axis (``partition_2d``).  Every (p, q) device owns node block
p's rows restricted to feature block q, so the halo exchange along the node
axis moves rows that are only ``F/Q`` wide -- per-device halo bytes shrink
by Q relative to the 1-D partition at the same world size, which is how the
paper's Table 4 collective term keeps shrinking once a single node axis
saturates (multi-host meshes: node axis across hosts, feature axis across
the devices within each host).

Shards are padded to identical static shapes so the whole structure stacks
into (P, ...) arrays consumable by shard_map.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph


class PartitionedGraph(NamedTuple):
    """Stacked per-shard edge lists (all shapes static, padded).

    src:        (P, Emax) int32 global source ids.
    dst_local:  (P, Emax) int32 destination id LOCAL to the shard block.
    mask:       (P, Emax) f32   1.0 for real edges, 0.0 padding.
    vtx_start:  (P,)      int32 first global vertex id of each shard block.
    block_size: python int      vertices per shard (padded).
    num_vertices: python int    real global vertex count.

    The uniform partition (``edge_balanced=False``) also groups each
    shard's edges by the block that owns their source -- the block a ring
    hop holds -- so a hop reads only its own bucket (None otherwise):

    bucket_src:  (P, P, Eb) int32 source id LOCAL to its owner block
                 ``[shard, owner]``; padding slots read rows 0, 1, 2, ...
    bucket_dst:  (P, P, Eb) int32 local destination, ascending within a
                 bucket; padding slots hold ``block_size`` (out of range:
                 ``segment_sum`` drops them) after the real edges.
    bucket_mask: (P, P, Eb) f32   1.0 for real edges, 0.0 padding.

    ``Eb`` is the largest bucket: a shard holds ``P * Eb`` slots.
    """

    src: jnp.ndarray
    dst_local: jnp.ndarray
    mask: jnp.ndarray
    vtx_start: jnp.ndarray
    block_size: int
    num_vertices: int
    bucket_src: Optional[jnp.ndarray] = None
    bucket_dst: Optional[jnp.ndarray] = None
    bucket_mask: Optional[jnp.ndarray] = None

    @property
    def num_shards(self) -> int:
        return int(self.src.shape[0])


def partition_1d(g: Graph, num_shards: int, edge_balanced: bool = True
                 ) -> PartitionedGraph:
    """1-D destination-vertex partition of ``g`` into ``num_shards`` blocks.

    ``edge_balanced=True`` picks block boundaries equalizing edge counts
    (feeds the analytic load model); ``edge_balanced=False`` gives the
    uniform layout the shard_map execution paths require
    (core.distributed._require_uniform).
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)  # already sorted by dst
    v = g.num_vertices
    block = -(-v // num_shards)  # ceil; every shard owns `block` vertex slots

    if edge_balanced:
        # Choose vertex boundaries so edge counts are ~equal, but keep the
        # owned vertex ranges within each shard's static `block` capacity.
        row_ptr = np.asarray(g.row_ptr)
        target = len(src) / num_shards
        bounds = [0]
        for p in range(1, num_shards):
            ideal = int(np.searchsorted(row_ptr, target * p))
            lo = bounds[-1] + 1
            hi = min(v, bounds[-1] + block)
            bounds.append(int(np.clip(ideal, lo, hi)))
        bounds.append(v)
    else:
        bounds = [min(v, p * block) for p in range(num_shards)] + [v]

    per_src, per_dst = [], []
    for p in range(num_shards):
        lo, hi = bounds[p], bounds[p + 1]
        sel = (dst >= lo) & (dst < hi)
        per_src.append(src[sel])
        per_dst.append(dst[sel] - lo)
    emax = max(1, max(len(s) for s in per_src))
    # pad to multiple of 8 for clean TPU sublane tiling
    emax = -(-emax // 8) * 8

    ps = np.zeros((num_shards, emax), np.int32)
    pd = np.zeros((num_shards, emax), np.int32)
    pm = np.zeros((num_shards, emax), np.float32)
    for p in range(num_shards):
        e = len(per_src[p])
        ps[p, :e] = per_src[p]
        pd[p, :e] = per_dst[p]
        pm[p, :e] = 1.0
    starts = np.array([bounds[p] for p in range(num_shards)], np.int32)
    buckets = {} if edge_balanced else dict(zip(
        ("bucket_src", "bucket_dst", "bucket_mask"),
        (jnp.asarray(a) for a in _owner_buckets(per_src, per_dst, block))))
    return PartitionedGraph(
        src=jnp.asarray(ps), dst_local=jnp.asarray(pd), mask=jnp.asarray(pm),
        vtx_start=jnp.asarray(starts), block_size=block, num_vertices=v,
        **buckets)


def _owner_buckets(per_src, per_dst, block: int):
    """Each shard's edges grouped by the block that owns their source, as
    ``(P, P, Eb)`` arrays (``PartitionedGraph.bucket_*``).  A shard's
    edges arrive sorted by destination, and a stable sort by owner keeps
    that order inside each bucket."""
    nsh = len(per_src)
    groups = []
    for s, d in zip(per_src, per_dst):
        owner = s // block
        order = np.argsort(owner, kind="stable")
        cuts = np.searchsorted(owner[order], np.arange(nsh + 1))
        groups.append([(s[order[a:b]] - o * block, d[order[a:b]])
                       for o, (a, b) in enumerate(zip(cuts, cuts[1:]))])
    eb = max(1, max(len(s) for row in groups for s, _ in row))
    bs = np.zeros((nsh, nsh, eb), np.int32)
    bd = np.full((nsh, nsh, eb), block, np.int32)
    bm = np.zeros((nsh, nsh, eb), np.float32)
    for p, row in enumerate(groups):
        for o, (s, d) in enumerate(row):
            n = len(s)
            bs[p, o, :n], bd[p, o, :n], bm[p, o, :n] = s, d, 1.0
            # padding reads distinct rows: on a TPU, many reads of one row
            # serialise in the gather
            bs[p, o, n:] = np.arange(eb - n) % block
    return bs, bd, bm


def block_buckets(pg: PartitionedGraph, tile_m: int):
    """Each owner bucket ``[shard, owner]`` regrouped by destination block
    of ``tile_m`` rows, as host arrays ``(P, P, nb, emax)`` with
    ``nb = ceil(block_size / tile_m)`` and ``emax`` the largest (shard,
    owner, block) edge count: source ids local to the owner, seg ids local
    to the destination block (``[0, tile_m)``), and a 1/0 mask; pad slots
    hold 0 in all three (the gather spreads their reads:
    ``kernels.ops._gather_slots``).  A bucket's destinations ascend, so
    its real edges are already in (block, position) order: one pass over
    them, with no loop over blocks, places them all."""
    nsh = pg.num_shards
    nb = -(-pg.block_size // tile_m)
    real = np.asarray(pg.bucket_mask) > 0
    shard, owner, _ = np.nonzero(real)
    dst = np.asarray(pg.bucket_dst)[real]
    grp = (shard * nsh + owner) * nb + dst // tile_m
    counts = np.bincount(grp, minlength=nsh * nsh * nb)
    emax = max(1, int(counts.max(initial=0)))
    pos = np.arange(len(grp)) - (np.cumsum(counts) - counts)[grp]
    src = np.zeros((nsh * nsh * nb, emax), np.int32)
    seg = np.zeros_like(src)
    mask = np.zeros(src.shape, np.float32)
    src[grp, pos] = np.asarray(pg.bucket_src)[real]
    seg[grp, pos] = dst % tile_m
    mask[grp, pos] = 1.0
    shape = (nsh, nsh, nb, emax)
    return src.reshape(shape), seg.reshape(shape), mask.reshape(shape)


def edge_balance(pg: PartitionedGraph) -> float:
    """max/mean edge load across shards (1.0 = perfect)."""
    loads = np.asarray(pg.mask).sum(axis=1)
    return float(loads.max() / max(loads.mean(), 1e-9))


class Partition2D(NamedTuple):
    """2-D (node x feature) partition: P node shards x Q feature shards.

    The graph structure is only partitioned along the node axis (``nodes``,
    a uniform :class:`PartitionedGraph`); the feature axis is a dense
    columnwise split whose block size depends on the per-layer feature
    length, so it is computed at execution time via ``feature_block``.
    """

    nodes: PartitionedGraph
    feat_shards: int

    @property
    def node_shards(self) -> int:
        return self.nodes.num_shards

    @property
    def block_size(self) -> int:
        """Vertex rows per node shard (padded) -- mirrors PartitionedGraph."""
        return self.nodes.block_size

    @property
    def num_vertices(self) -> int:
        return self.nodes.num_vertices

    def feature_block(self, feature_len: int) -> int:
        """Columns per feature shard for one layer's feature length
        (ceil-divided; callers zero-pad to ``feat_shards * feature_block``)."""
        return -(-int(feature_len) // self.feat_shards)


def partition_2d(g: Graph, node_shards: int, feat_shards: int
                 ) -> Partition2D:
    """Partition ``g`` for a (node_shards x feat_shards) device mesh.

    The node axis reuses the *uniform* 1-D partition (the shard_map layout
    requirement -- see core.distributed._require_uniform); the feature axis
    needs no host-side structure beyond its cardinality.
    """
    if node_shards < 1 or feat_shards < 1:
        raise ValueError(f"need positive shard counts, got "
                         f"{node_shards}x{feat_shards}")
    return Partition2D(nodes=partition_1d(g, node_shards,
                                          edge_balanced=False),
                       feat_shards=feat_shards)

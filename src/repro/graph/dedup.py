"""GraphACT-style pair-redundancy elimination for sampled minibatch blocks.

The paper's guideline: aggregation is the memory-bound phase, so exploit
data reuse inside it.  GraphACT (PAPERS.md, arXiv:2001.02498) observes the
sharpest form of that reuse in fanout-regular sampled blocks: many
destination vertices share the same *pair* of in-neighbors, so the sum
``x[a] + x[b]`` is recomputed once per sharing destination.  This module
detects those shared pairs on the host (the same host/accelerator split
GraphACT uses between CPU matching and FPGA aggregation) and emits a
**two-level aggregation layout**:

  * **Level 1** computes each matched pair's partial sum ONCE:
    ``partials = x[pair_left] + x[pair_right]``           (P rows).
  * **Level 2** aggregates a *shortened* edge list over the virtual
    concatenation ``[x ; partials]`` (V + P rows): every matched
    destination's two pair edges are replaced by ONE edge referencing the
    pair partial, singleton edges pass through unchanged.

Matching discipline — why f32 stays bitwise-golden
--------------------------------------------------
Candidate pairs are **leading pairs only**: for each destination with
in-degree >= 2, the candidate is its FIRST TWO edges in dst-sorted order,
and a pair is kept only when at least ``min_frequency`` destinations share
it.  XLA's ``segment_sum`` reduces each destination segment as an in-order
left fold, so the naive fold ``((0 + e1) + e2) + rest`` and the dedup fold
``(0 + (e1 + e2)) + rest`` are IEEE-identical (``0 + x == x`` exactly, and
float addition is commutative, so the canonical ``(min, max)`` pair key is
safe).  Restricting to the leading pair keeps every eliminated addition
inside that provably exact prefix — which is what lets ``plan.compile()``
hold its bitwise f32 contract with dedup enabled (tests/test_dedup.py).

The layout is plan-owned and trace-pure: ``build_dedup_layout`` runs once
at plan-build time (O(E) numpy), the arrays it emits are consumed by the
XLA path and the Pallas tier (``attach_blocked`` pre-blocks the level-2
edge list for ``kernels.ops.seg_agg_planned``), and the padding helper
(``pad_dedup_arrays``) extends a block's layout to a bucket's static
shapes with sink no-ops so ONE compiled callable serves every
fanout-regular block (models/sage_minibatch.py training loop).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

DEDUP_MODES = ("none", "pairs", "auto")


class DedupLayout(NamedTuple):
    """Two-level aggregation layout over a destination-sorted edge list.

    Level 1: ``partials = x[pair_left] + x[pair_right]`` (one row per
    matched pair).  Level 2: segment-sum ``src2``/``dst2`` over the
    virtual concatenation ``[x ; partials]`` — ``src2`` values in
    ``[0, num_vertices)`` reference original feature rows, values in
    ``[num_vertices, num_vertices + num_pairs)`` reference pair partials.
    ``dst2`` stays non-decreasing (dst-sorted), and within each matched
    destination the pair edge comes FIRST — the prefix position that makes
    the f32 left fold bitwise-equal to the naive fold.

    Static python ints (``num_pairs``/``num_edges2``/``matched_edges``/
    ``naive_edges``/``num_vertices``) are compile-time shape facts;
    ``blocked`` is the optional plan-time level-2 ``BlockedGraph`` for the
    Pallas tier (``attach_blocked``).
    """

    pair_left: jnp.ndarray      # (P,) int32 first member of each pair
    pair_right: jnp.ndarray     # (P,) int32 second member (left <= right)
    src2: jnp.ndarray           # (E2,) int32 into [x ; partials]
    dst2: jnp.ndarray           # (E2,) int32 destination, non-decreasing
    num_pairs: int
    num_edges2: int
    matched_edges: int          # original edges covered by matched pairs
    naive_edges: int            # original |E|
    num_vertices: int
    blocked: Optional[object] = None   # core.dataflow.BlockedGraph

    @property
    def edges_removed(self) -> int:
        """Edges the level-2 list no longer carries (= matched dsts)."""
        return self.naive_edges - self.num_edges2

    def flops_saved(self, feature_len: int) -> float:
        """Adds eliminated per feature column: removed edge-adds minus the
        P pair-partial adds level 1 spends computing them."""
        return float((self.edges_removed - self.num_pairs) * feature_len)


def build_dedup_layout(src, dst, num_vertices: int, *,
                       min_frequency: int = 2) -> DedupLayout:
    """Greedy leading-pair matching over a dst-sorted edge list (host side).

    For every destination with >= 2 in-edges the candidate pair is its
    first two sources in dst-sorted order (canonicalized ``(min, max)`` —
    float add is commutative so the partial is order-independent).  Pairs
    shared by at least ``min_frequency`` destinations are kept; each
    matched destination's two leading edges collapse into one edge whose
    source is ``num_vertices + pair_id``.  O(E) numpy, no Python loop over
    edges.  A block with no shareable pairs yields ``num_pairs == 0`` —
    callers treat that as "dedup resolves to none".
    """
    s = np.asarray(src, np.int64)
    d = np.asarray(dst, np.int64)
    assert s.shape == d.shape and s.ndim == 1
    e = len(s)
    deg = np.bincount(d, minlength=num_vertices)
    assert (np.diff(d) >= 0).all() if e else True, "edge list must be dst-sorted"
    starts = np.zeros(num_vertices, np.int64)
    np.cumsum(deg[:-1], out=starts[1:])

    cand = np.where(deg >= 2)[0]                 # dsts owning a leading pair
    if len(cand) == 0:
        return DedupLayout(
            pair_left=jnp.zeros(0, jnp.int32), pair_right=jnp.zeros(0, jnp.int32),
            src2=jnp.asarray(s, jnp.int32), dst2=jnp.asarray(d, jnp.int32),
            num_pairs=0, num_edges2=e, matched_edges=0, naive_edges=e,
            num_vertices=int(num_vertices))
    a = s[starts[cand]]
    b = s[starts[cand] + 1]
    keys = np.stack([np.minimum(a, b), np.maximum(a, b)], axis=1)
    uniq, inv, counts = np.unique(keys, axis=0, return_inverse=True,
                                  return_counts=True)
    kept = counts >= min_frequency
    num_pairs = int(kept.sum())
    pid_of_uniq = np.full(len(uniq), -1, np.int64)
    pid_of_uniq[kept] = np.arange(num_pairs)
    pid = pid_of_uniq[inv]                       # per candidate dst; -1 = unmatched
    matched = pid >= 0
    matched_dsts = cand[matched]

    # Collapse in place: the first edge of each matched dst becomes the pair
    # edge (src = V + pair_id, the PREFIX slot that keeps the left fold
    # exact), the second edge is dropped.  Global dst-sort is preserved.
    s2 = s.copy()
    s2[starts[matched_dsts]] = num_vertices + pid[matched]
    drop = np.zeros(e, bool)
    drop[starts[matched_dsts] + 1] = True
    src2 = s2[~drop].astype(np.int32)
    dst2 = d[~drop].astype(np.int32)
    return DedupLayout(
        pair_left=jnp.asarray(uniq[kept, 0], jnp.int32),
        pair_right=jnp.asarray(uniq[kept, 1], jnp.int32),
        src2=jnp.asarray(src2), dst2=jnp.asarray(dst2),
        num_pairs=num_pairs, num_edges2=int(len(src2)),
        matched_edges=int(2 * len(matched_dsts)), naive_edges=e,
        num_vertices=int(num_vertices), blocked=None)


def dedup_layout_for_graph(g, *, min_frequency: int = 2) -> DedupLayout:
    """``build_dedup_layout`` over a ``Graph``'s dst-sorted edge arrays."""
    return build_dedup_layout(np.asarray(g.src), np.asarray(g.dst),
                              g.num_vertices, min_frequency=min_frequency)


def attach_blocked(layout: DedupLayout, tile_m: int) -> DedupLayout:
    """Pre-block the level-2 edge list for the Pallas tier (plan time).

    The blocked layout's gather sources index the (V + P)-row virtual
    concatenation, so it must be built by ``core.dataflow
    .block_graph_arrays`` (plain ``block_graph`` would reject src >= V);
    the output row space stays the original V destinations.
    """
    from repro.core.dataflow import block_graph_arrays
    bg = block_graph_arrays(np.asarray(layout.src2), np.asarray(layout.dst2),
                            layout.num_vertices, tile_m)
    return layout._replace(blocked=bg)


def dedup_cost(layout: DedupLayout, feature_len: int, dtype_bytes: int = 4,
               include_self: bool = True) -> dict:
    """Analytic cost of the two-level aggregation (``aggregate_cost`` twin).

    flops: P pair adds + E2 level-2 adds (+ V self adds); bytes: gather one
    row per level-2 edge and per pair member, write P partials + V outputs,
    plus index traffic for both levels.  Compare with the naive
    ``phases.aggregate_cost`` of the same graph to get the modeled saving.
    """
    p, e2, v = layout.num_pairs, layout.num_edges2, layout.num_vertices
    v_self = v if include_self else 0
    flops = (p + e2 + v_self) * feature_len
    reads = (e2 + 2 * p + v_self) * feature_len * dtype_bytes
    writes = (v + p) * feature_len * dtype_bytes
    index_reads = e2 * 8 + 2 * p * 4
    byt = reads + writes + index_reads
    return {"bytes": byt, "flops": flops, "gathered_rows": e2 + 2 * p,
            "pairs": p, "flops_saved": layout.flops_saved(feature_len),
            "arithmetic_intensity": flops / max(1, byt)}


def pad_dedup_arrays(layout: DedupLayout, num_pairs: int, num_edges2: int,
                     sink: int) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray, np.ndarray]:
    """Pad a block's dedup arrays to a bucket's static shapes (host side).

    Exactness contract (mirrors ``GraphServeEngine._pad_into``): pad pairs
    are ``(sink, sink)`` — the sink row is all-zero, so their partials are
    exact zeros — and pad level-2 edges are sink self-loops appended AFTER
    the real (sorted) edges, so every real destination sees exactly the
    real fold in the real order.  Returns numpy
    ``(pair_left, pair_right, src2, dst2)`` sized ``(num_pairs,)`` /
    ``(num_edges2,)`` ready to feed one compiled callable per bucket.
    """
    assert layout.num_pairs <= num_pairs, "bucket too small for pairs"
    assert layout.num_edges2 <= num_edges2, "bucket too small for edges"
    pad_p = num_pairs - layout.num_pairs
    pad_e = num_edges2 - layout.num_edges2
    pl = np.concatenate([np.asarray(layout.pair_left, np.int32),
                         np.full(pad_p, sink, np.int32)])
    pr = np.concatenate([np.asarray(layout.pair_right, np.int32),
                         np.full(pad_p, sink, np.int32)])
    s2 = np.concatenate([np.asarray(layout.src2, np.int32),
                         np.full(pad_e, sink, np.int32)])
    d2 = np.concatenate([np.asarray(layout.dst2, np.int32),
                         np.full(pad_e, sink, np.int32)])
    return pl, pr, s2, d2

"""Aggregation and Combination as first-class composable phases (paper F1).

The paper decomposes every GCN layer into:

  * **Aggregation**  -- per-vertex reduce over in-neighbor feature rows
    (irregular gather + segmented reduction; memory-bound).
  * **Combination**  -- dense transform of per-vertex features by an MLP
    (GEMM; compute-bound).

Both are exposed here as pure functions over a destination-sorted ``Graph``.
Aggregation is implemented as a *sorted segmented sum*: collision-free (the
logical endpoint of the paper's "only inter-warp collisions / vectorize
atomics" analysis -- see DESIGN.md §2) and expressible either as
``jax.ops.segment_sum`` (XLA path) or via the Pallas ``seg_agg`` kernel.

The backward pass of Aggregation is Aggregation on the transpose graph; JAX
derives it automatically from this formulation (gather/scatter-add adjoints),
so training inherits the paper's phase structure for free.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.backend import PALLAS_TPU
from repro.graph.structure import Graph

AGGREGATORS = ("sum", "mean", "max")


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Matmul that accumulates f32 for reduced-precision operands.

    f32 x f32 runs at ``"highest"`` precision: on a TPU the default would
    round both operands to bf16 for one MXU pass, so an f32 plan would not
    compute in f32 (on CPU the product is unchanged).  Anything narrower
    (bf16 plan operands) runs with ``preferred_element_type=float32`` so
    the MXU/tensor-core accumulator is full precision.
    """
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def quantize_int8(x: jnp.ndarray) -> jnp.ndarray:
    """Per-row symmetric int8 fake-quantization of an aggregation operand.

    Each row is scaled by ``max|row| / 127`` (zero rows get scale 1),
    rounded to the int8 grid, and returned dequantized in f32 -- every
    value is exactly int8-representable times its row scale, which is what
    a real int8 gather + f32 accumulate + dequant pipeline computes, while
    staying a pure traceable f32 computation on this container.  The plan
    dtype ``"int8-agg"`` applies this ONLY to the aggregation input; the
    1-byte wire/HBM width is priced analytically (``profile.machine``).
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0)
    return q * scale


# ---------------------------------------------------------------------------
# Aggregation phase
# ---------------------------------------------------------------------------


def aggregate(g: Graph, x: jnp.ndarray, op: str = "mean",
              edge_weight: Optional[jnp.ndarray] = None,
              edge_mask: Optional[jnp.ndarray] = None,
              include_self: bool = True,
              backend: Optional[str] = None,
              layout=None, dedup=None) -> jnp.ndarray:
    """h_v = reduce_{u in N(v) (+ v)} x_u              (paper Eq. 1/2 inner term)

    Args:
      g: destination-sorted graph.
      x: (V, F) vertex features.
      op: "sum" | "mean" | "max".  mean divides by |N(v)|+1 (paper's GCN/SAG),
        matching ``mean({N(v)} ∪ {v})``.
      edge_weight: optional (E,) per-edge scalar (e.g. sym-norm GCN weights).
      edge_mask: optional (E,) 1/0 mask for padded edge lists.
      include_self: add the vertex's own row to the reduction.
      backend: a resolved tier, "xla" (segment_sum) or "pallas-tpu";
        None = xla.  Resolved by the execution planner (core/plan.py).
      layout: plan-owned ``core.dataflow.BlockedGraph`` the "pallas-tpu"
        tier aggregates over (``kernels.ops.seg_agg_planned``: the O(E)
        regrouping was done once at plan-build time, so the dispatch is
        trace-pure).  Plans always pass it (``LayerPlan.agg_layout``);
        the Pallas tier refuses to run without one.
      dedup: plan-owned ``graph.dedup.DedupLayout`` two-level layout.
        When given (sum/mean, unweighted/unmasked only — the planner
        guarantees this), aggregation runs redundancy-eliminated: level 1
        computes each matched pair's partial sum once, level 2 segment-sums
        the shortened edge list over ``[x ; partials]``.  The f32 result is
        bitwise-identical to the naive fold (see graph/dedup.py).
    """
    assert op in AGGREGATORS, op
    v, f = x.shape
    w = None
    if edge_weight is not None:
        w = edge_weight
    if edge_mask is not None:
        w = edge_mask if w is None else w * edge_mask

    use_pallas = backend == PALLAS_TPU

    if dedup is not None and dedup.num_pairs > 0 and op in ("sum", "mean") \
            and w is None:
        # Two-level redundancy-eliminated path (graph/dedup.py).  Cast the
        # operand to f32 FIRST (exact for bf16/int8-agg inputs) so the pair
        # partials are the same f32 adds the naive fold's accumulator does.
        xf = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
        partials = jnp.take(xf, dedup.pair_left, axis=0) + \
            jnp.take(xf, dedup.pair_right, axis=0)
        xp = jnp.concatenate([xf, partials], axis=0)
        if use_pallas and dedup.blocked is not None:
            from repro.kernels import ops as kops
            summed = kops.seg_agg_planned(dedup.blocked, xp, None)
        else:
            gathered2 = jnp.take(xp, dedup.src2, axis=0)
            summed = jax.ops.segment_sum(gathered2, dedup.dst2,
                                         num_segments=v)
        if include_self:
            summed = summed + x
        if op == "mean":
            denom = g.in_deg.astype(summed.dtype) + \
                (1.0 if include_self else 0.0)
            summed = summed * (1.0 / jnp.maximum(denom, 1.0))[:, None]
        return summed
    if op == "max" or not use_pallas:
        gathered = jnp.take(x, g.src, axis=0)  # (E, F) -- indexSelect kernel

    if op == "max":
        if w is not None:
            gathered = jnp.where((w > 0)[:, None], gathered, -jnp.inf)
        out = jax.ops.segment_max(gathered, g.dst, num_segments=v)
        self_term = x if include_self else jnp.full_like(x, -jnp.inf)
        out = jnp.maximum(out, self_term)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    if use_pallas:
        if layout is None:
            raise ValueError(
                "the pallas-tpu tier aggregates over a plan-owned blocked "
                "layout: dispatch through a plan from build_plan, "
                "plan_for_conv, or plan_for_phases, or call "
                "kernels.ops.seg_agg_planned with a "
                "core.dataflow.block_graph layout")
        from repro.kernels import ops as kops
        summed = kops.seg_agg_planned(layout, x, w)
    else:
        if w is not None:
            gathered = gathered * w[:, None].astype(gathered.dtype)
        if gathered.dtype != jnp.float32:
            # reduced-precision plan operand (bf16): the segmented reduce
            # must still accumulate f32 -- the plan rounds the phase
            # OUTPUT back down, never the accumulator.  f32 inputs skip
            # the cast entirely (bitwise-golden default path).
            gathered = gathered.astype(jnp.float32)
        summed = jax.ops.segment_sum(gathered, g.dst, num_segments=v)

    if include_self:
        summed = summed + x
    if op == "mean":
        denom = g.in_deg.astype(summed.dtype) + \
            (1.0 if include_self else 0.0)
        # reciprocal-multiply, not broadcast division: XLA's jitted fusion
        # rewrites (V,F)/(V,1) division non-bitwise-reproducibly vs eager;
        # the (V,1) reciprocal + multiply is identical in both, which is
        # what keeps plan.compile() bit-for-bit equal to the eager path
        summed = summed * (1.0 / jnp.maximum(denom, 1.0))[:, None]
    return summed


def aggregate_cost(g: Graph, feature_len: int, dtype_bytes: int = 4,
                   include_self: bool = True) -> dict:
    """Analytic data-access/computation counts for the Aggregation phase.

    Reproduces the accounting behind paper Table 4: bytes = read one feature
    row per edge + write one row per vertex (+ self reads); ops = one add per
    element per edge.  Independent of the *input* feature length when run
    after Combination -- the paper's Fig.5 observation.
    """
    e, v = g.num_edges, g.num_vertices
    reads = (e + (v if include_self else 0)) * feature_len * dtype_bytes
    writes = v * feature_len * dtype_bytes
    index_reads = e * 8  # src+dst ids
    flops = (e + (v if include_self else 0)) * feature_len
    return {"bytes": reads + writes + index_reads, "flops": flops,
            "gathered_rows": e, "arithmetic_intensity":
            flops / max(1, reads + writes + index_reads)}


# ---------------------------------------------------------------------------
# Combination phase
# ---------------------------------------------------------------------------


def combine(x: jnp.ndarray, weights, activation: Optional[str] = "relu",
            final_activation: bool = False) -> jnp.ndarray:
    """Dense per-vertex MLP (the sgemm kernels in paper Fig. 1).

    ``weights`` is a list of (W, b) tuples -- one entry for GCN/SAG
    (|h|->128), two for GIN (|h|->128->128), matching paper Table 1.
    """
    h = x
    n = len(weights)
    for i, (wmat, b) in enumerate(weights):
        h = _mm(h, wmat)  # f32-accumulating for reduced-precision operands
        if b is not None:
            h = h + b
        if activation and (i < n - 1 or final_activation):
            h = _act(activation)(h)
    return h


def _act(name: str):
    return {"relu": jax.nn.relu, "gelu": jax.nn.gelu, "tanh": jnp.tanh,
            "none": lambda x: x}[name]


def combine_cost(num_vertices: int, dims, dtype_bytes: int = 4) -> dict:
    """Analytic GEMM cost: 2*V*in*out flops per matmul; bytes for X, W, Y."""
    flops = 0
    byt = 0
    for din, dout in zip(dims[:-1], dims[1:]):
        flops += 2 * num_vertices * din * dout
        byt += (num_vertices * din + din * dout + num_vertices * dout) * dtype_bytes
    return {"bytes": byt, "flops": flops,
            "arithmetic_intensity": flops / max(1, byt)}


# ---------------------------------------------------------------------------
# A full phase-ordered layer (paper F2)
# ---------------------------------------------------------------------------


def phase_ordered_layer(g: Graph, x: jnp.ndarray, weights, *,
                        order: Optional[str] = None, agg_op: str = "mean",
                        edge_weight=None, activation: str = "relu",
                        plan=None) -> jnp.ndarray:
    """One graph-conv layer with explicit (or planned) phase ordering.

    ``order`` = "combine_first" (GCN/SAG style; shrinks the feature length the
    sparse phase must move -- Table 4's 4.7x) or "aggregate_first" (GIN
    semantics); None lets the planner's cost model choose.  For *linear*
    combination + sum/mean aggregation the two orderings are mathematically
    equivalent; the framework exploits that to reorder GCN/SAG for
    performance while GIN (MLP with interior nonlinearity) is pinned to
    aggregate_first to preserve semantics.

    Dispatches through a ``GraphExecutionPlan`` (built and cached per
    (graph, dims, order, agg_op) when ``plan`` is not given), so backend and
    fusion decisions live in ONE place (core/plan.py).
    """
    assert order in ("combine_first", "aggregate_first", None), order
    if plan is None:
        from repro.core.plan import plan_for_phases
        plan = plan_for_phases(g, weights, order=order, agg_op=agg_op)
    return plan.run_phases(x, weights, edge_weight=edge_weight,
                           activation=activation)

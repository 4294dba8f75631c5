"""GraphExecutionPlan: one planning/dispatch layer for GCN execution.

Everything the paper shows must be decided *together* -- and that the rest
of this repo used to decide per-call with ad-hoc flags -- is decided here
ONCE per (graph, model, device) and then replayed on every forward/backward:

  * **Phase ordering (paper F2, Table 4).**  Per layer, the analytic cost
    model (``scheduler.choose_ordering``) picks combine-first when the
    projection shrinks the feature length the sparse phase must move
    (Reddit 602->128: 4.7x fewer aggregation bytes), and honors semantic
    pins (GIN's interior ReLU forces aggregate-first).
  * **Collision-free aggregation backend (paper F3).**  XLA
    ``segment_sum`` vs the specialized Pallas TPU kernel tier, chosen by
    platform ("auto" = pallas-tpu on TPU, XLA everywhere else --
    ``backend.resolve_backend``); interpret mode is auto-detected
    (``backend.default_interpret``: compiled on a TPU, interpreted
    elsewhere), so the Pallas tier validates on a CPU container.
  * **Inter-phase dataflow fusion (paper F5, §5.1-3).**  The fused
    aggregate->combine tile executor needs a ``BlockedGraph`` regrouping
    of the edge list and a VMEM-budgeted ``tile_m``; the plan builds both
    once (cached per graph -- see ``_blocked_for``) instead of per call.
    GIN layers fuse aggregation with the *first* MLP matmul (previously
    the fused path was silently ignored for GIN).
  * **Shard partition (DESIGN.md §8.5).**  With a 1-D mesh, the plan owns
    the ``partition_1d`` vertex partition and routes layers through the
    ring / all-gather halo aggregation, with ordering still chosen by the
    same cost model (combine-first shrinks the *collective* term by the
    same in/out ratio); GIN sums over the halo and runs its MLP on each
    device's own rows.  With a 2-D mesh (two named axes, e.g.
    ``jax.make_mesh((4, 2), ("node", "feat"))``), the plan builds the
    ``partition_2d`` node x feature partition instead and routes layers
    through ``distributed_gcn_layer_2d`` -- per-device halo bytes shrink a
    further Q-fold (the multi-host tier, single-matmul layers only; see
    docs/planner.md).

  * **Locality reordering (paper F4, §5.1 guideline 1).**  Built with
    ``reorder="degree"`` (or ``"auto"``, priced by ``choose_reorder``
    against the plan's ``Machine``), the plan renumbers vertices once at
    build time (``graph.reorder.degree_reorder``) so high-degree rows
    cluster; features are permuted at ingress and logits un-permuted at
    egress *inside* the traced forward -- callers always see the natural
    vertex order.

Every dispatch path is TRACE-PURE: all host-side work (block regrouping,
reordering, partitioning) happens at plan-build time, so the whole forward
compiles.  ``plan.compile()`` returns the single jitted callable
(``CompiledPlan``, with a retrace guard); ``run_model(..., compiled=True)``
is the sugar.

Public surface:

  ``build_plan(g, cfg, in_dim, num_classes, ...)``  -> GraphExecutionPlan
  ``plan.run_model(params, x)``     full forward through all planned layers
  ``plan.compile(donate=...)``      ONE jitted callable for the forward
  ``plan.run_layer(params_i, x, layer=i)``  one layer (conv param subtree)
  ``plan.run_phases(x, weights, ...)``      raw weight-list layer (the
                                            ``phase_ordered_layer`` path)
  ``plan.describe()`` / ``plan.layer_costs(i)``  decisions + analytic costs
  ``plan.instrument(machine=...)``  characterization wrapper: one run_model
                                    yields a typed WorkloadReport
                                    (repro.profile.instrument)

Layer APIs (``GCNModel.apply``, ``GCNConv.apply``, ``phase_ordered_layer``,
the distributed example) all dispatch through plans; none of them takes raw
``impl=`` / ``blocked=`` flags anymore.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import phases
from repro.core.backend import (AUTO, PALLAS_TPU, XLA, default_interpret,
                                resolve_backend, resolve_interpret)
from repro.core.dataflow import (BlockedGraph, block_graph, fused_gcn_layer,
                                 suggest_tile_m)
from repro.core.scheduler import (AGGREGATE_FIRST, COMBINE_FIRST,
                                  choose_ordering, ordering_cost)
from repro.graph.structure import Graph
from repro.profile.spans import gauge, span

# ---------------------------------------------------------------------------
# Plan data model
# ---------------------------------------------------------------------------


@dataclass(frozen=True, eq=False)
class LayerPlan:
    """All decisions for one graph-conv layer, frozen at plan-build time."""

    index: int
    kind: str                 # "gcn" | "sage" | "gin" | "phase"
    dims: Tuple[int, ...]     # (din, [hidden...,] dout) of the combination MLP
    agg_op: str               # "sum" | "mean" | "max"
    include_self: bool
    order: str                # COMBINE_FIRST | AGGREGATE_FIRST (resolved)
    backend: str              # "xla" | "pallas-tpu" (resolved, never
                              # "auto")
    fused: bool               # inter-phase dataflow fusion (F5)
    tile_m: int               # fused tile rows (0 when unfused)
    blocked: Optional[BlockedGraph]  # shared BlockedGraph (None when unfused)
    #: plan-owned blocked layout for UNFUSED Pallas aggregation -- built for
    #: every Pallas-tier layer so the seg_agg dispatch is trace-pure
    #: (kernels/ops.seg_agg_planned), including call-time fusion fallbacks.
    agg_layout: Optional[BlockedGraph] = None

    @property
    def din(self) -> int:
        return self.dims[0]

    @property
    def dout(self) -> int:
        return self.dims[-1]

    @property
    def n_mlp(self) -> int:
        return len(self.dims) - 1


class GraphExecutionPlan:
    """Precomputed execution recipe for a model over one fixed graph."""

    def __init__(self, g: Graph, layers: Sequence[LayerPlan], *,
                 interpret: bool, mesh=None, partition=None,
                 strategy: str = "ring", axis: str = "data",
                 axes: Tuple[str, str] = ("node", "feat"), machine=None,
                 reorder: str = "none", perm=None, overlap: str = "none",
                 dtype: str = "f32", dedup: str = "none",
                 dedup_layout=None, hops=None):
        self.g = g                   # the EXECUTION graph (renumbered when
                                     # reorder="degree")
        self.layers: Tuple[LayerPlan, ...] = tuple(layers)
        self.interpret = interpret
        self.mesh = mesh
        self.partition = partition   # None | PartitionedGraph | Partition2D
        self.strategy = strategy
        self.axis = axis             # 1-D partition: the single mesh axis
        self.axes = axes             # 2-D partition: (node, feature) axes
        self.machine = machine       # Optional[repro.profile.Machine]
        self.reorder = reorder       # "none" | "degree" (resolved)
        self.overlap = overlap       # "none" | "pipelined" (resolved halo
                                     # schedule; "auto" never survives build)
        self.dtype = dtype           # "f32" | "bf16" | "int8-agg" (resolved
                                     # execution precision; never "auto")
        self.dedup = dedup           # "none" | "pairs" (resolved two-level
                                     # redundancy elimination; never "auto",
                                     # and never "pairs" with zero matches)
        self.dedup_layout = dedup_layout  # graph.dedup.DedupLayout | None
        # per layer, the ring hops' seg_agg layout (core.distributed
        # .HopLayout), or None where a hop runs XLA's segment_sum
        self.hops = tuple(hops) if hops is not None \
            else (None,) * len(self.layers)
        # perm[old_id] = new_id (graph.reorder.degree_reorder contract);
        # inv[new_id] = old_id.  Device constants the traced ingress/egress
        # gathers close over -- never recomputed per call.
        if perm is not None:
            perm = np.asarray(perm)
            inv = np.empty_like(perm)
            inv[perm] = np.arange(len(perm))
            self.perm, self.inv = jnp.asarray(perm), jnp.asarray(inv)
        else:
            self.perm = self.inv = None
        self._compiled: Dict = {}    # (donate, layer) -> CompiledPlan

    # -- properties ---------------------------------------------------------

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def distributed(self) -> bool:
        return self.partition is not None

    @property
    def halo_kernel(self) -> str:
        """"seg_agg" | "segment_sum" -- what reduces a ring hop's (or the
        all-gather's) edges on a distributed plan; "none" on a local one."""
        if not self.distributed:
            return "none"
        return "segment_sum" if self.hops[0] is None else "seg_agg"

    @property
    def partition_kind(self) -> str:
        """"none" | "1d" | "2d" -- which shard partition the plan owns."""
        from repro.graph.partition import Partition2D
        if self.partition is None:
            return "none"
        return "2d" if isinstance(self.partition, Partition2D) else "1d"

    @property
    def compile_supported(self) -> bool:
        """True when every layer's dispatch is trace-pure -- i.e. every
        Pallas-tier layer owns a plan-built blocked layout, so
        ``plan.compile()`` traces with zero host transfers.  Plans built by
        the public entry points always qualify; False only for hand-built
        plans missing ``agg_layout``."""
        return all(lp.backend != PALLAS_TPU or lp.agg_layout is not None
                   for lp in self.layers)

    # -- parameter helpers --------------------------------------------------

    def init(self, key) -> Dict:
        """Init a params pytree matching ``run_model`` ({"conv<i>": ...})."""
        from repro.core.gcn_layers import _dense_init
        keys = jax.random.split(key, max(self.num_layers, 1))
        out: Dict = {}
        for lp, k in zip(self.layers, keys):
            if lp.n_mlp == 1:
                out[f"conv{lp.index}"] = {
                    "lin": _dense_init(k, lp.dims[0], lp.dims[1])}
            else:
                ks = jax.random.split(k, lp.n_mlp)
                out[f"conv{lp.index}"] = {
                    f"mlp{j + 1}": _dense_init(ks[j], lp.dims[j],
                                               lp.dims[j + 1])
                    for j in range(lp.n_mlp)}
        return out

    @staticmethod
    def _split_params(lp: LayerPlan, params: Dict):
        """Conv param subtree -> (weights list, post-aggregation bias)."""
        if "lin" in params:
            return [(params["lin"]["w"], None)], params["lin"]["b"]
        weights = []
        j = 1
        while f"mlp{j}" in params:
            weights.append((params[f"mlp{j}"]["w"], params[f"mlp{j}"]["b"]))
            j += 1
        return weights, None

    # -- execution ----------------------------------------------------------

    def run_layer(self, params: Dict, x: jnp.ndarray, *, layer: int = 0,
                  _probe=None, graph: Optional[Graph] = None,
                  dedup_layout=None) -> jnp.ndarray:
        """One planned layer from its conv param subtree ({"lin": ...} or
        {"mlp1": ..., "mlp2": ...}).  Operates in the plan's EXECUTION
        layout: in distributed plans ``x`` must be padded to the partition
        layout, in reordered plans rows follow the renumbered vertex ids
        (``run_model`` handles both via its ingress/egress).  ``graph``
        overrides the plan's graph for this dispatch (the dynamic serving
        path -- see ``compile(dynamic=True)``); only valid for plain XLA
        unfused local plans, whose dispatch reads nothing but the edge
        arrays.  ``dedup_layout`` likewise substitutes runtime dedup
        arrays for the plan's baked two-level layout (the dynamic
        minibatch path); the plan's own layout never applies to an
        overridden graph."""
        lp = self.layers[layer]
        weights, bias_post = self._split_params(lp, params)
        if self.distributed:
            return self._run_distributed(lp, x, weights, bias_post,
                                         probe=_probe)
        dedup = dedup_layout if graph is not None or dedup_layout is not None \
            else self.dedup_layout
        return _execute_layer(self.g if graph is None else graph, lp, x,
                              weights, bias_post=bias_post, probe=_probe,
                              dtype=self.dtype, dedup=dedup)

    def _ingress(self, x: jnp.ndarray, *, _probe=None) -> jnp.ndarray:
        """Natural (V, F) features -> the plan's execution layout: the
        planned vertex renumbering (reorder), then the partition padding.
        Pure gathers/pads over plan-time constants -- trace-pure."""
        v = self.g.num_vertices
        if self.inv is not None:
            if x.shape[0] != v:
                raise ValueError(
                    f"reordered plans take features in the natural (V, F) "
                    f"layout; got {tuple(x.shape)} for V={v}")
            x = jnp.take(x, self.inv, axis=0)  # x_new[j] = x_old[inv[j]]
            if _probe is not None:
                _probe.note_reorder()
        if self.distributed and x.shape[0] == v:
            if self.partition_kind == "2d":
                from repro.core.distributed import pad_features_2d
                x = pad_features_2d(x, self.partition)
            else:
                from repro.core.distributed import pad_features
                x = pad_features(x, self.partition.block_size,
                                 self.partition.num_shards)
        return x

    def _egress(self, h: jnp.ndarray) -> jnp.ndarray:
        """Execution layout -> natural order: trim partition padding, then
        un-apply the vertex renumbering (out_old[i] = h_new[perm[i]])."""
        v = self.g.num_vertices
        if self.partition_kind == "2d":
            h = h[:v, :self.layers[-1].dout]
        elif self.distributed:
            h = h[:v]
        if self.perm is not None:
            h = jnp.take(h, self.perm, axis=0)
        return h

    def run_model(self, params: Dict, x: jnp.ndarray, *,
                  _probe=None, compiled: bool = False,
                  graph: Optional[Graph] = None,
                  dedup_layout=None) -> jnp.ndarray:
        """Full forward: planned layers with ReLU between them.

        Accepts ``x`` in the natural (V, F) layout.  Distributed plans pad
        it into the partition layout (rows for 1-D; rows and feature
        columns for 2-D -- pad columns stay exact zeros through every
        layer) and trim the padding off the final output; reordered plans
        permute rows at ingress and un-permute the logits at egress, all
        inside the (traceable) forward.

        ``compiled=True`` routes through ``plan.compile()`` -- the cached
        single jitted callable -- instead of the eager per-phase loop.

        ``graph=`` substitutes another graph's edge arrays for this
        dispatch while replaying the SAME planned decisions (the serving
        path: one plan per shape bucket, many sampled blocks through it --
        see ``compile(dynamic=True)``).  Only plain XLA unfused local
        plans accept it; ``x`` rows must match the substitute graph.
        """
        if compiled:
            if _probe is not None:
                raise ValueError(
                    "per-phase instrumentation needs eager phase "
                    "boundaries; InstrumentedPlan times the compiled "
                    "path separately (run_model(..., compiled=True))")
            if graph is not None:
                return self.compile(dynamic=True)(params, x, graph,
                                                  dedup=dedup_layout)
            return self.compile()(params, x)
        if graph is not None:
            self._check_dynamic_ok()
            if self.dedup == "pairs" and dedup_layout is None:
                raise ValueError(
                    "this plan's dedup='pairs' layout was matched on its "
                    "template graph; dynamic dispatch over a substitute "
                    "graph needs that block's own layout (pass "
                    "dedup_layout=, padded to the template's shapes)")
        h = self._ingress(x, _probe=_probe)
        for i in range(self.num_layers):
            h = self.run_layer(params[f"conv{i}"], h, layer=i, _probe=_probe,
                               graph=graph, dedup_layout=dedup_layout)
            if i < self.num_layers - 1:
                h = jax.nn.relu(h)
        return self._egress(h)

    def _check_dynamic_ok(self) -> None:
        """Dynamic (graph-as-argument) dispatch preconditions: nothing in
        the traced path may depend on the EDGE CONTENT the plan was built
        with.  XLA unfused layers qualify (segment ops read the arrays as
        data); Pallas/fused layers bake host-built blocked layouts, and
        partition/reorder bake edge-derived permutations -- all rejected."""
        problems = []
        if self.distributed:
            problems.append("partitioned plans bake edge-derived shards")
        if self.perm is not None:
            problems.append("reordered plans bake an edge-derived permute")
        for lp in self.layers:
            if lp.backend == PALLAS_TPU or lp.fused:
                problems.append(
                    f"layer {lp.index} ({lp.backend}"
                    f"{', fused' if lp.fused else ''}) bakes a host-built "
                    "blocked layout")
        if problems:
            raise ValueError(
                "dynamic graph dispatch needs edge-content-free tracing: "
                + "; ".join(problems)
                + " (build the bucket plan with backend='xla', "
                "fused=False, reorder='none', mesh=None)")

    def compile(self, *, donate: bool = False,
                layer: Optional[int] = None,
                dynamic: bool = False) -> "CompiledPlan":
        """ONE jitted callable for the planned forward (the production
        entry point).

        Local plans trace ``run_model`` under ``jax.jit``; distributed
        plans trace the same path, whose shard_map halo bodies carry their
        mesh explicitly -- either way the result is a single compiled
        executable with zero host transfers inside the traced region (all
        host-side work -- block regrouping, reordering, partitioning --
        happened at plan-build time).  Exact eager equivalence and a
        retrace-count guard are part of the contract: the returned
        ``CompiledPlan`` counts traces (``num_traces``) and raises if a
        second trace happens for an input signature it has already seen.

        Args:
          donate: donate the feature buffer to the computation
            (``jax.jit(donate_argnums=...)``) -- frees the input's memory
            on accelerators for inference serving; leave False when the
            caller reuses ``x``.
          layer: compile a single planned layer instead of the full model
            (``(conv_params, h) -> h'`` in the plan's execution layout) --
            what per-layer compiled timing in ``repro.profile`` uses.
          dynamic: compile the forward with the GRAPH as a runtime
            argument instead of a baked constant -- the serving-bucket
            mode (``repro.serve.graph_engine``).  The callable signature
            becomes ``(params, x, graph)`` where ``graph`` is any
            ``Graph`` whose ``src``/``dst``/``in_deg`` shapes match the
            plan's template graph; edge CONTENT varies per call with zero
            retraces, so one compiled callable serves every sampled block
            padded into the bucket's shape.  Requires edge-content-free
            tracing: plain XLA, unfused, local, unreordered plans only
            (``_check_dynamic_ok``); incompatible with ``layer=``.

        Compiled callables are cached per (donate, layer, dynamic) on the
        plan, so ``plan.compile()(params, x)`` in a loop never re-jits.

        Worked example::

            >>> plan = build_plan(g, cfg, in_dim, classes)
            >>> fwd = plan.compile()
            >>> out = fwd(params, x)          # traces + compiles once
            >>> out = fwd(params, x)          # cached executable
            >>> fwd.num_traces
            1
        """
        if not self.compile_supported:
            raise ValueError(
                "plan.compile() needs trace-pure dispatch on every layer; "
                "a Pallas-tier layer is missing its plan-owned blocked "
                "layout (build plans through build_plan/plan_for_* rather "
                "than by hand)")
        if dynamic:
            if layer is not None:
                raise ValueError("dynamic compilation covers the full "
                                 "forward; layer= is incompatible")
            self._check_dynamic_ok()
        key = (bool(donate), layer, bool(dynamic))
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._compiled[key] = CompiledPlan(self, donate=donate,
                                                    layer=layer,
                                                    dynamic=dynamic)
        return fn

    def run_phases(self, x: jnp.ndarray, weights, *, layer: int = 0,
                   edge_weight=None, activation: str = "relu",
                   bias_post=None, _probe=None) -> jnp.ndarray:
        """Raw weight-list execution (the ``phase_ordered_layer`` entry).

        ``weights`` is a list of (W, b) tuples with biases applied *inside*
        the combination MLP (``phases.combine`` semantics); ``bias_post``
        is an optional extra bias added after aggregation (conv semantics).
        Like ``run_model``, takes and returns the natural vertex order: on
        a reordered plan rows are permuted in and un-permuted out (but
        per-edge ``edge_weight`` is rejected there -- the caller's edge
        order does not survive the renumbering's re-sort).
        """
        if self.perm is not None:
            if edge_weight is not None:
                raise ValueError(
                    "edge_weight is indexed by the caller's edge order, "
                    "which a reordered plan re-sorts; use reorder='none' "
                    "or fold the weights into the graph at plan build")
            # reorder permute ONLY -- run_phases always executes the local
            # path, so partition padding (_ingress's other job) must not
            # apply even on distributed plans
            x = jnp.take(x, self.inv, axis=0)
            if _probe is not None:
                _probe.note_reorder()
        h = _execute_layer(self.g, self.layers[layer], x, weights,
                           edge_weight=edge_weight, activation=activation,
                           bias_post=bias_post, probe=_probe,
                           dtype=self.dtype, dedup=self.dedup_layout)
        if self.perm is not None:
            h = jnp.take(h, self.perm, axis=0)
        return h

    def _run_distributed(self, lp: LayerPlan, x, weights, bias_post, *,
                         probe=None):
        """One layer on the mesh: the halo aggregation, then the combine
        on the rows each device owns.  Single-matmul layers (GCN/SAGE)
        run both in ``distributed_gcn_layer`` (either phase order); GIN
        sums first and applies its MLP with the interior ReLU as the local
        path does (``phases.combine``, scope ``l<i>.combine``)."""
        from repro.core.distributed import (distributed_gcn_layer,
                                            distributed_gcn_layer_2d,
                                            halo_aggregate)
        # halo feature length: what the exchange moves under this ordering;
        # overlap rides along so the probe prices the schedule that
        # actually dispatched (exposed vs. overlapped collective time);
        # the quant error reported for reduced plans is the layer-ingress
        # operand's (the per-shard exchange operand is shard_map-internal)
        agg_len = lp.din if lp.order == AGGREGATE_FIRST else lp.dout
        hops = self.hops[lp.index]
        qerr = 0.0
        if probe is not None and self.dtype != "f32":
            qerr = _quant_err(x, _reduce_in(x, self.dtype))
        if lp.n_mlp > 1:
            if self.dtype == "bf16":
                weights = [(w.astype(jnp.bfloat16),
                            None if b is None else b.astype(jnp.bfloat16))
                           for (w, b) in weights]
            xw = _reduce_in(x, self.dtype)     # the wire carries the reduced x
            thunk = lambda: halo_aggregate(  # noqa: E731
                self.partition, xw, self.mesh, op=lp.agg_op,
                in_deg=self.g.in_deg, strategy=self.strategy,
                axis=self.axis, overlap=self.overlap, hops=hops)
        else:
            (w, b_inline), = weights
            bias = bias_post if bias_post is not None else b_inline
            if bias is None:
                bias = jnp.zeros((w.shape[1],), x.dtype)
            if self.partition_kind == "2d":
                thunk = lambda: distributed_gcn_layer_2d(  # noqa: E731
                    self.partition, x, w, bias, self.g.in_deg, self.mesh,
                    order=lp.order, strategy=self.strategy, axes=self.axes,
                    overlap=self.overlap, dtype=self.dtype, hops=hops)
            else:
                thunk = lambda: distributed_gcn_layer(  # noqa: E731
                    self.partition, x, w, bias, self.g.in_deg, self.mesh,
                    order=lp.order, strategy=self.strategy, axis=self.axis,
                    overlap=self.overlap, dtype=self.dtype, hops=hops)

        def halo():
            with jax.named_scope("halo"):
                return thunk()
        h = _phase(probe, "distributed", halo, lp=lp,
                   feature_len=agg_len, overlap=self.overlap,
                   quant_error=qerr, combine=lp.n_mlp == 1)
        if lp.n_mlp == 1:
            return h
        h = _round(h, self.dtype)
        h = _round(_phase(probe, "combine",
                          lambda: phases.combine(h, weights),
                          lp=lp, dims=lp.dims), self.dtype)
        return h if bias_post is None else h + bias_post

    def instrument(self, machine=None, warmup: int = 0):
        """Wrap this plan for characterization (``repro.profile``).

        Returns an ``InstrumentedPlan`` whose ``run_model`` / ``run_layer``
        / ``run_phases`` execute the SAME dispatch path as this plan while
        recording per-layer, per-phase FLOPs / bytes / wall time into a
        ``WorkloadReport`` (with ``to_json()`` / ``to_markdown()``).

        ``machine`` is a ``repro.profile.Machine`` (or registry name, e.g.
        ``"a100"``); defaults to the plan's own machine or the first layer
        backend's natural preset.

        Worked example (the one-call characterization path)::

            >>> report = build_plan(g, cfg, in_dim, classes).instrument(
            ...     machine=A100).run_model(params, x)
            >>> report.output.shape            # the forward result
            (220, 7)
            >>> print(report.to_markdown())    # Table-3/4-style breakdown
        """
        from repro.profile.instrument import InstrumentedPlan
        from repro.profile.machine import get_machine
        if machine is not None:
            machine = get_machine(machine)
        return InstrumentedPlan(self, machine=machine, warmup=warmup)

    # -- introspection ------------------------------------------------------

    def describe(self) -> List[Dict]:
        """One dict per layer: every planned decision + modeled agg cost.

        ``reorder`` is the resolved locality decision ("none" | "degree"),
        ``dtype`` the resolved execution precision ("f32" | "bf16" |
        "int8-agg" -- never "auto"),
        and ``compiled`` the trace-purity capability (``plan.compile()``
        works iff True -- always, for plans built by the public entry
        points).  ``agg_edges`` / ``agg_gather_rows`` /
        ``agg_kernel_slots`` / ``agg_gather_bytes`` count one forward's
        aggregation layout (``agg_counts``: the Pallas blocking, or the
        halo's edge slots on a mesh; 0 on local XLA layers), and
        ``halo_wire_bytes`` what one device sends over the halo
        (``halo_wire_bytes``; 0 on local plans), and ``halo_kernel``
        what reduces the halo's edges (``"seg_agg"`` | ``"segment_sum"``;
        ``"none"`` on local plans).
        """
        out = []
        compiled_ok = self.compile_supported
        for lp, counts, wire in zip(self.layers, self.agg_counts(),
                                    self.halo_wire_bytes()):
            oc = ordering_cost(self.g, lp.din, lp.dout, lp.order)
            out.append({
                "layer": lp.index, "kind": lp.kind,
                "din": lp.din, "dout": lp.dout,
                "order": lp.order, "backend": lp.backend,
                "fused": lp.fused, "tile_m": lp.tile_m,
                "interpret": self.interpret,
                "distributed": self.distributed,
                "partition": self.partition_kind,
                "overlap": self.overlap, "dtype": self.dtype,
                "reorder": self.reorder, "compiled": compiled_ok,
                "dedup": self.dedup,
                "agg_bytes": oc.agg_bytes, "agg_flops": oc.agg_flops,
                **{f"agg_{k}": v for k, v in counts.items()},
                "halo_wire_bytes": wire, "halo_kernel": self.halo_kernel,
            })
        return out

    def agg_counts(self) -> List[Dict[str, int]]:
        """Per layer, what one forward's aggregation moves (``edges``,
        ``gather_rows``, ``kernel_slots``, ``gather_bytes``): over the
        Pallas layout the layer dispatches (``kernels.ops.layout_counts``:
        the fused tile's blocking, the dedup level-2 blocking, or the
        unfused ``agg_layout``), or, on distributed layers, over the halo
        bodies' edge slots summed over the mesh
        (``core.distributed.halo_counts``).  All 0 on local XLA layers,
        which build no blocked layout."""
        from repro.kernels.ops import layout_counts
        keys = ("edges", "gather_rows", "kernel_slots", "gather_bytes")
        if self.distributed:
            return [{k: c[k] for k in keys} for c in self._halo_counts()]
        dedup = self.dedup_layout
        if dedup is not None and (dedup.num_pairs == 0
                                  or dedup.blocked is None):
            dedup = None
        itemsize = 2 if self.dtype == "bf16" else 4
        out = []
        for lp in self.layers:
            fused = lp.fused and lp.blocked is not None and \
                _fused_agg_op(lp) is not None
            bg = dedup.blocked if dedup is not None else (
                lp.blocked if fused else lp.agg_layout)
            if lp.backend != PALLAS_TPU or bg is None:
                out.append(dict.fromkeys(keys, 0))
                continue
            width = lp.din if fused or lp.order == AGGREGATE_FIRST \
                else lp.dout
            # the dedup path gathers from an f32 [x ; partials] concat
            out.append(layout_counts(
                bg, width, 4 if dedup is not None else itemsize,
                f_out=lp.dims[1] if fused else 0))
        return out

    def halo_wire_bytes(self) -> List[int]:
        """Per layer, the bytes one device sends over the halo collectives
        in one forward (``core.distributed.halo_counts``); 0 on local
        plans."""
        if not self.distributed:
            return [0] * self.num_layers
        return [c["wire_bytes"] for c in self._halo_counts()]

    def _halo_counts(self) -> List[Dict[str, int]]:
        from repro.core.distributed import halo_counts
        return [halo_counts(self.partition,
                            lp.din if lp.order == AGGREGATE_FIRST
                            else lp.dout, strategy=self.strategy,
                            overlap=self.overlap, dtype=self.dtype,
                            hops=hops)
                for lp, hops in zip(self.layers, self.hops)]

    def layer_costs(self, layer: int = 0) -> Dict:
        """Analytic per-phase costs of one planned layer (Table 3/4)."""
        lp = self.layers[layer]
        agg_len = lp.din if lp.order == AGGREGATE_FIRST else lp.dout
        return {
            "order": lp.order,
            "aggregation": phases.aggregate_cost(self.g, agg_len),
            "combination": phases.combine_cost(self.g.num_vertices, lp.dims),
            "ordering_cost": ordering_cost(self.g, lp.din, lp.dout, lp.order),
        }


class CompiledPlan:
    """A plan's forward as ONE jitted callable, with a retrace guard.

    Built by ``plan.compile()``.  ``__call__(params, x)`` runs the compiled
    executable; the first call per input signature traces (``num_traces``
    counts), and a re-trace for a signature that was already traced raises
    ``RuntimeError`` -- the guard that catches accidental cache-busting
    (e.g. weak types or recreated plans) instead of silently recompiling
    every step.
    """

    def __init__(self, plan: "GraphExecutionPlan", *, donate: bool = False,
                 layer: Optional[int] = None, dynamic: bool = False):
        self.plan = plan
        self.donate = donate
        self.layer = layer
        self.dynamic = dynamic
        self._num_traces = 0
        self._seen = set()

        def fwd(params, x):
            self._num_traces += 1   # runs at TRACE time only
            # the layout this executable reads, as gauges agg.<count>.l<i>,
            # and on a mesh the halo's bytes, halo.wire_bytes.l<i>
            for i, counts in enumerate(plan.agg_counts()):
                for k, v in counts.items():
                    gauge(f"agg.{k}.l{i}", v)
            if plan.distributed:
                for i, v in enumerate(plan.halo_wire_bytes()):
                    gauge(f"halo.wire_bytes.l{i}", v)
            if layer is None:
                return plan.run_model(params, x)
            return plan.run_layer(params, x, layer=layer)

        def fwd_dynamic(params, x, src, dst, in_deg, *ded):
            self._num_traces += 1   # runs at TRACE time only
            g = plan.g._replace(src=src, dst=dst, in_deg=in_deg,
                                row_ptr=None)
            lay = None
            if ded:
                # runtime two-level dedup arrays (shapes fixed by the
                # plan's template layout; content varies per block)
                pl, pr, s2, d2 = ded
                lay = plan.dedup_layout._replace(
                    pair_left=pl, pair_right=pr, src2=s2, dst2=d2,
                    blocked=None)
            return plan.run_model(params, x, graph=g, dedup_layout=lay)

        if dynamic:
            self._fn = jax.jit(fwd_dynamic,
                               donate_argnums=(1,) if donate else ())
        else:
            self._fn = jax.jit(fwd, donate_argnums=(1,) if donate else ())

    @property
    def num_traces(self) -> int:
        """How many times the callable has been traced (compiled)."""
        return self._num_traces

    def lower(self, params, x):
        """Ahead-of-time lowering of this static forward for ``(params,
        x)`` -- ``.compile()`` on the result gives the executable a call
        runs, for inspecting its HLO (``as_text()``) and its device memory
        (``memory_analysis()``)."""
        if self.dynamic:
            raise ValueError("lower() covers static compiled plans")
        return self._fn.lower(params, x)

    def op_scopes(self, params, x) -> Dict[str, str]:
        """``{HLO op name: scope path}`` of the executable a call with
        ``(params, x)`` runs, such as ``fusion.1 -> l0.aggregate/gather``,
        ``seg_agg.2 -> l0.aggregate/seg_agg`` or ``dot.3 -> l1.combine``:
        what a profiler trace's device op time is put down to.  Compiles the
        forward once more; nothing on the call path uses it."""
        return hlo_op_scopes(self.lower(params, x).compile().as_text())

    @staticmethod
    def _signature(params, *arrays):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return (tuple((tuple(a.shape), str(getattr(a, "dtype", type(a))))
                      for a in arrays), treedef,
                tuple((tuple(p.shape), str(p.dtype)) for p in leaves))

    def _graph_args(self, graph: Graph):
        """Validate + destructure a runtime graph for the dynamic mode.

        Shape mismatches are raised HERE (a bucket-contract violation the
        serving engine must catch), never silently absorbed by a retrace."""
        t = self.plan.g
        if graph.num_vertices != t.num_vertices or \
                graph.src.shape != t.src.shape or \
                graph.in_deg.shape != t.in_deg.shape:
            raise ValueError(
                f"dynamic graph shape {graph.num_vertices}V/"
                f"{graph.src.shape[0]}E does not match the bucket template "
                f"{t.num_vertices}V/{t.src.shape[0]}E -- pad the block "
                "into the bucket before dispatch")
        return (jnp.asarray(graph.src), jnp.asarray(graph.dst),
                jnp.asarray(graph.in_deg))

    def _dedup_args(self, dedup):
        """Validate + destructure runtime dedup arrays (dynamic mode on a
        ``dedup='pairs'`` plan).  ``dedup`` is a ``DedupLayout`` (or the
        4-tuple of its arrays) padded to the template layout's shapes."""
        t = self.plan.dedup_layout
        if hasattr(dedup, "pair_left"):
            dedup = (dedup.pair_left, dedup.pair_right,
                     dedup.src2, dedup.dst2)
        pl, pr, s2, d2 = (jnp.asarray(a) for a in dedup)
        if pl.shape[0] != t.num_pairs or s2.shape[0] != t.num_edges2:
            raise ValueError(
                f"dynamic dedup shapes {pl.shape[0]}P/{s2.shape[0]}E2 do "
                f"not match the bucket template {t.num_pairs}P/"
                f"{t.num_edges2}E2 -- pad via graph.dedup.pad_dedup_arrays")
        return (pl, pr, s2, d2)

    def __call__(self, params, x, graph: Optional[Graph] = None,
                 dedup=None):
        if self.dynamic:
            if graph is None:
                raise ValueError("dynamic compiled plans take (params, x, "
                                 "graph)")
            args = (x,) + self._graph_args(graph)
            if self.plan.dedup == "pairs":
                if dedup is None:
                    raise ValueError(
                        "this dynamic plan was compiled with dedup='pairs'; "
                        "pass the block's padded dedup layout (dedup=)")
                args = args + self._dedup_args(dedup)
            elif dedup is not None:
                raise ValueError("dedup arrays passed to a dedup='none' "
                                 "compiled plan")
        else:
            if graph is not None:
                raise ValueError("this compiled plan is static; build it "
                                 "with plan.compile(dynamic=True) to pass "
                                 "a runtime graph")
            args = (x,)
        with span("plan.call"):
            with span("plan.guard"):
                sig = self._signature(params, *args)
                seen = sig in self._seen
            before = self._num_traces
            out = self._fn(params, *args)
            if self._num_traces > before and seen:
                raise RuntimeError(
                    "plan.compile() retraced for an input signature it "
                    "already compiled -- something is busting the jit "
                    "cache (weak types? fresh arrays with different "
                    "dtypes?)")
            if not seen:
                self._seen.add(sig)
            return out


_OP_META = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{op name: scope path}`` from a compiled module's text: each
    instruction's ``metadata={op_name=...}`` with the transformation
    frames (``jit(fwd)``, ``jvp(...)``) and the primitive's own name
    dropped.  Ops outside every named scope are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_META.match(line)
        if m is None:
            continue
        path = [p for p in m.group(2).split("/")[:-1] if "(" not in p]
        if path:
            out[m.group(1)] = "/".join(path)
    return out


# ---------------------------------------------------------------------------
# Layer execution core (the ONE place ordering x backend x fusion composes)
# ---------------------------------------------------------------------------


def _fused_agg_op(lp: LayerPlan) -> Optional[str]:
    """Map a layer's aggregation semantics onto fused_gcn_layer's modes."""
    if lp.agg_op == "mean":
        return "mean" if lp.include_self else None
    if lp.agg_op == "sum":
        return "sum_self" if lp.include_self else "sum"
    return None  # max: non-linear, cannot fuse


def _can_fuse(lp: LayerPlan, weights, edge_weight) -> bool:
    if not (lp.fused and lp.blocked is not None and edge_weight is None):
        return False
    if _fused_agg_op(lp) is None:
        return False
    # An inline bias on the fused matmul is exact when it applies after the
    # reduction (aggregate-first) or commutes with it (mean of a constant
    # row is that row); otherwise fall back to the unfused path.
    b0 = weights[0][1]
    return b0 is None or lp.order == AGGREGATE_FIRST or lp.agg_op == "mean"


def _phase(probe, name: str, thunk, *, lp: LayerPlan, **meta):
    """Run one phase under the named scope ``l<layer>.<phase>``,
    optionally observed by an instrumentation probe.

    The scope names the phase's ops in the traced program's metadata
    (``CompiledPlan.op_scopes``); it acts at trace time only.  ``probe``
    is the characterization hook (``repro.profile.instrument``): None in
    production (the thunk runs directly); when set, ``probe.run`` times
    the phase and records its analytic cost.  Keeping the hook HERE means
    reports always describe the dispatch path that actually ran, not a
    parallel re-implementation.
    """
    with jax.named_scope(f"l{lp.index}.{name}"):
        if probe is None:
            return thunk()
        return probe.run(name, thunk, lp=lp, **meta)


def _round(h: jnp.ndarray, dtype: str) -> jnp.ndarray:
    """Round a phase output back to the plan dtype's storage precision.
    Identity for f32 and int8-agg (whose phase outputs stay f32)."""
    return h.astype(jnp.bfloat16) if dtype == "bf16" else h


def _reduce_in(h: jnp.ndarray, dtype: str) -> jnp.ndarray:
    """Reduced-precision image of one phase operand: bf16 cast, int8
    per-row fake-quant, or identity for f32."""
    if dtype == "bf16":
        return h.astype(jnp.bfloat16)
    if dtype == "int8-agg":
        return phases.quantize_int8(h)
    return h


def _quant_err(orig: jnp.ndarray, reduced: jnp.ndarray) -> float:
    """Max abs error a precision reduction introduced (probe-time only:
    forces a host sync, so production dispatch never calls it)."""
    return float(jnp.max(jnp.abs(  # analysis: allow(host-in-trace)
        orig.astype(jnp.float32) - reduced.astype(jnp.float32))))


def _dedup_fused_inputs(dedup, xa):
    """Level-1 partials + the (V + P)-row concat for a FUSED dedup layer.

    Mirrors ``phases.aggregate``'s dedup path: cast to f32 first (exact),
    add each matched pair once, stack the partials under the features so
    the fused kernel's gather (over ``dedup.blocked``, the level-2 edge
    list) references them like ordinary rows.
    """
    xf = xa if xa.dtype == jnp.float32 else xa.astype(jnp.float32)
    partials = jnp.take(xf, dedup.pair_left, axis=0) + \
        jnp.take(xf, dedup.pair_right, axis=0)
    return jnp.concatenate([xf, partials], axis=0)


def _execute_layer(g: Graph, lp: LayerPlan, x: jnp.ndarray, weights, *,
                   edge_weight=None, activation: str = "relu",
                   bias_post=None, probe=None,
                   dtype: str = "f32", dedup=None) -> jnp.ndarray:
    """Execute one layer per its plan: fusion > ordering > backend.

    ``dtype`` is the plan's resolved execution precision.  ``"f32"`` takes
    the unmodified path (every cast below is guarded, so the default stays
    bitwise-golden).  ``"bf16"`` casts the operands once at entry and
    rounds each phase output back to bf16 -- reductions and matmuls still
    accumulate f32 (kernel scratch / ``preferred_element_type``).
    ``"int8-agg"`` fake-quantizes ONLY the aggregation operand (per-row
    symmetric scales via ``phases.quantize_int8``), aggregates the
    int8-representable rows in f32, and leaves combination in full f32.

    ``dedup`` is the plan's two-level pair-redundancy layout
    (``graph.dedup.DedupLayout``) or None.  Unfused paths hand it to
    ``phases.aggregate``; the fused path swaps the layer's blocked layout
    for the layout's level-2 blocking and feeds the kernel the
    ``[x ; partials]`` concat.  It only applies where the planner admitted
    it (sum/mean, no edge weights) -- anything else falls back naive.
    """
    entry_err = 0.0
    if dtype == "bf16":
        xr = x.astype(jnp.bfloat16)
        if probe is not None:
            entry_err = _quant_err(x, xr)
        x = xr
        weights = [(w.astype(jnp.bfloat16),
                    None if b is None else b.astype(jnp.bfloat16))
                   for (w, b) in weights]
        if bias_post is not None:
            bias_post = bias_post.astype(jnp.bfloat16)
    mlp_dims = tuple([int(w.shape[0]) for (w, _) in weights] +
                     [int(weights[-1][0].shape[1])])
    if _can_fuse(lp, weights, edge_weight):
        w0, b0 = weights[0]
        fused_dims = (int(w0.shape[0]), int(w0.shape[1]))
        xa, agg_err = x, entry_err
        if dtype == "int8-agg":
            xa = phases.quantize_int8(x)
            if probe is not None:
                agg_err = _quant_err(x, xa)
        # dedup rides the fused path by swapping in the level-2 blocking
        # and the [x ; partials] gather source; the in-tile reduce + GEMM
        # and the self/mean terms (which index the first V rows) are
        # untouched.
        fbg, fx = lp.blocked, xa
        if dedup is not None and dedup.num_pairs > 0 \
                and dedup.blocked is not None:
            fbg, fx = dedup.blocked, _dedup_fused_inputs(dedup, xa)
        if len(weights) == 1:
            # Whole layer fused: aggregate(+)combine never leaves the tile.
            # An inline b0 is exact applied post-aggregation here (that is
            # what _can_fuse admitted), so fold it into the final bias.
            bias = b0 if bias_post is None else (
                bias_post if b0 is None else b0 + bias_post)
            h = _phase(
                probe, "fused_agg_combine",
                lambda: fused_gcn_layer(fbg, fx, w0, bias,
                                        agg_op=_fused_agg_op(lp),
                                        in_deg=g.in_deg, backend=lp.backend),
                lp=lp, dims=fused_dims, quant_error=agg_err)
            return _round(h, dtype)
        # Multi-layer MLP (GIN): fuse aggregation with the FIRST matmul --
        # exact because sum/mean aggregation is linear and the interior
        # nonlinearity only applies after that matmul.
        h = _phase(
            probe, "fused_agg_combine",
            lambda: fused_gcn_layer(fbg, fx, w0, b0,
                                    agg_op=_fused_agg_op(lp),
                                    in_deg=g.in_deg, backend=lp.backend),
            lp=lp, dims=fused_dims, quant_error=agg_err)
        h = _round(phases._act(activation)(h), dtype)
        h = _phase(probe, "combine",
                   lambda hh=h: phases.combine(hh, weights[1:],
                                               activation=activation),
                   lp=lp, dims=mlp_dims[1:])
        h = _round(h, dtype)
    elif lp.order == COMBINE_FIRST:
        h = _phase(probe, "combine",
                   lambda: phases.combine(x, weights, activation=activation),
                   lp=lp, dims=mlp_dims, quant_error=entry_err)
        h = _round(h, dtype)
        ha, agg_err = h, 0.0
        if dtype == "int8-agg":
            ha = phases.quantize_int8(h)
            if probe is not None:
                agg_err = _quant_err(h, ha)
        h = _phase(probe, "aggregate",
                   lambda hh=ha: phases.aggregate(
                       g, hh, op=lp.agg_op, edge_weight=edge_weight,
                       include_self=lp.include_self, backend=lp.backend,
                       layout=lp.agg_layout, dedup=dedup),
                   lp=lp, feature_len=int(h.shape[-1]), quant_error=agg_err)
        h = _round(h, dtype)
    else:
        xa, agg_err = x, entry_err
        if dtype == "int8-agg":
            xa = phases.quantize_int8(x)
            if probe is not None:
                agg_err = _quant_err(x, xa)
        h = _phase(probe, "aggregate",
                   lambda: phases.aggregate(
                       g, xa, op=lp.agg_op, edge_weight=edge_weight,
                       include_self=lp.include_self, backend=lp.backend,
                       layout=lp.agg_layout, dedup=dedup),
                   lp=lp, feature_len=int(x.shape[-1]), quant_error=agg_err)
        h = _round(h, dtype)
        h = _phase(probe, "combine",
                   lambda hh=h: phases.combine(hh, weights,
                                               activation=activation),
                   lp=lp, dims=mlp_dims)
        h = _round(h, dtype)
    if bias_post is not None:
        h = h + bias_post
    return h


# ---------------------------------------------------------------------------
# Plan construction + caching
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict = {}      # (graph_key, spec_key) -> (src_ref, plan)
_BLOCKED_CACHE: Dict = {}   # (graph_key, tile_m)   -> (src_ref, BlockedGraph)
_CACHE_LIMIT = 64


_REORDER_CACHE: Dict = {}   # graph_key -> (src_ref, reordered Graph, perm)

#: plan-cache accounting (the serving engine's eviction policy reads these):
#: hits/misses count ``_cached_plan`` lookups, evictions count every entry
#: dropped -- FIFO aging in ``_evict_oldest`` AND explicit
#: ``clear_plan_cache(keep=...)`` sweeps.
_PLAN_CACHE_STATS: Dict[str, int] = {"hits": 0, "misses": 0, "evictions": 0}


def plan_cache_stats() -> Dict[str, int]:
    """Observable plan-cache state: ``{size, limit, hits, misses,
    evictions, blocked_size, reorder_size}``.

    ``size`` counts live ``_PLAN_CACHE`` entries; ``hits``/``misses`` count
    cached-plan lookups since the last full ``clear_plan_cache()``;
    ``evictions`` counts entries dropped by FIFO aging or by
    ``clear_plan_cache(keep=...)``.  The serving engine's eviction policy
    (``repro.serve.graph_engine``) polls this to decide when to sweep
    transient per-request plans, and tests assert on it -- previously the
    cache internals were private and untestable.
    """
    return {"size": len(_PLAN_CACHE), "limit": _CACHE_LIMIT,
            "blocked_size": len(_BLOCKED_CACHE),
            "reorder_size": len(_REORDER_CACHE),
            **_PLAN_CACHE_STATS}


def clear_plan_cache(keep=None) -> int:
    """Drop cached plans (and their blocked/reorder cache lines).

    ``keep=None`` wipes everything and resets the hit/miss/eviction
    counters (the test-isolation path).  ``keep=<iterable of
    GraphExecutionPlan>`` is the serving engine's eviction policy: every
    cached plan NOT in ``keep`` is evicted, while the kept plans -- e.g.
    the engine's per-bucket compiled plans -- and the blocked/reorder
    layouts of their graphs survive, so a bounded bucket set keeps a
    bounded cache no matter how many transient per-request graphs were
    planned.  ``evictions`` counts every dropped line -- plan entries AND
    the blocked/reorder layouts swept with them -- and the hit/miss
    counters keep accumulating across the sweep.  Returns the number of
    plan entries dropped.
    """
    if keep is None:
        n = len(_PLAN_CACHE)
        _PLAN_CACHE.clear()
        _BLOCKED_CACHE.clear()
        _REORDER_CACHE.clear()
        _PLAN_CACHE_STATS.update(hits=0, misses=0, evictions=0)
        return n
    keep_plans = {id(p) for p in keep}
    keep_graphs = {_graph_key(p.g) for p in keep}
    drop = [k for k, (_, plan) in _PLAN_CACHE.items()
            if id(plan) not in keep_plans]
    for k in drop:
        del _PLAN_CACHE[k]
    blocked_drop = [k for k in _BLOCKED_CACHE if k[0] not in keep_graphs]
    for k in blocked_drop:
        del _BLOCKED_CACHE[k]          # key = (graph_key, tile_m)
    reorder_drop = [k for k in _REORDER_CACHE if k not in keep_graphs]
    for k in reorder_drop:
        del _REORDER_CACHE[k]          # key = graph_key
    # every dropped line counts -- plan entries AND the blocked/reorder
    # layouts swept with them (the stats docstring's contract); hit/miss
    # counters are untouched, so they survive an eviction cycle
    _PLAN_CACHE_STATS["evictions"] += \
        len(drop) + len(blocked_drop) + len(reorder_drop)
    return len(drop)


def _graph_key(g: Graph):
    if isinstance(g.src, jax.core.Tracer):
        raise ValueError(
            "build_plan needs a concrete Graph; build the plan outside jit "
            "and close over it (plans precompute host-side structures)")
    return (id(g.src), int(g.num_vertices), int(g.src.shape[0]))


def _evict_oldest(cache: Dict) -> None:
    """FIFO eviction: transient graphs (e.g. per-batch sampled blocks) age
    out one at a time instead of wiping hot full-graph entries wholesale."""
    while len(cache) >= _CACHE_LIMIT:
        cache.pop(next(iter(cache)))
        # every dropped line counts, whichever cache aged it out
        _PLAN_CACHE_STATS["evictions"] += 1


def _blocked_for(g: Graph, tile_m: int) -> BlockedGraph:
    """Build (or reuse) the BlockedGraph for (graph, tile_m).

    The regrouping is O(E) host work; plans for the same graph -- across
    rebuilds, convs, and benchmark scenarios -- share one copy.
    """
    key = (_graph_key(g), tile_m)
    hit = _BLOCKED_CACHE.get(key)
    if hit is not None and hit[0] is g.src:
        return hit[1]
    _evict_oldest(_BLOCKED_CACHE)
    bg = block_graph(g, tile_m)
    _BLOCKED_CACHE[key] = (g.src, bg)
    return bg


def _reordered_for(g: Graph):
    """Degree-reordered twin of ``g`` (cached): the O(V log V + E) renumber
    runs once per graph; every plan spec (fused/unfused, any backend) on
    the same graph shares one reordered copy -- and therefore one
    BlockedGraph cache line per tile."""
    key = _graph_key(g)
    hit = _REORDER_CACHE.get(key)
    if hit is not None and hit[0] is g.src:
        return hit[1], hit[2]
    from repro.graph.reorder import degree_reorder
    _evict_oldest(_REORDER_CACHE)
    g2, perm = degree_reorder(g)
    _REORDER_CACHE[key] = (g.src, g2, perm)
    return g2, perm


def _cached_plan(g: Graph, spec_key, builder):
    key = (_graph_key(g), spec_key)
    hit = _PLAN_CACHE.get(key)
    if hit is not None and hit[0] is g.src:
        _PLAN_CACHE_STATS["hits"] += 1
        return hit[1]
    _PLAN_CACHE_STATS["misses"] += 1
    _evict_oldest(_PLAN_CACHE)
    plan = builder()
    _PLAN_CACHE[key] = (g.src, plan)
    return plan


def _plan_layer(g: Graph, index: int, kind: str, dims: Tuple[int, ...], *,
                agg_op: str, ordering: str, backend: str, fused: bool,
                include_self: bool = True, machine=None,
                dtype: str = "f32") -> LayerPlan:
    """Resolve one layer's ordering / backend / fusion decisions.

    ``machine`` (``repro.profile.Machine``, optional) parameterizes the two
    hardware-aware decisions: the ordering cost model prices roofline time
    on it and ``suggest_tile_m`` sizes the fused tile for its memory
    hierarchy.  None keeps the tier's natural preset.

    ``dtype`` is the plan's RESOLVED execution precision (never "auto"):
    the fused tile is sized at the storage width the kernel's gathered
    rows actually occupy, so bf16 plans get the doubled effective
    on-chip budget ``dtype_model`` surfaces as ``tile_rows``.  int8-agg
    sizes at 4 bytes like f32 -- its fake-quantized aggregation operand
    is carried as f32 on device (only the analytic wire model prices the
    1-byte width).
    """
    semantic = AGGREGATE_FIRST if len(dims) > 2 else COMBINE_FIRST
    if ordering in (COMBINE_FIRST, AGGREGATE_FIRST):
        order = ordering if len(dims) <= 2 else AGGREGATE_FIRST  # GIN pinned
    else:
        order = choose_ordering(g, dims[0], dims[-1], agg_op=agg_op,
                                n_mlp_layers=len(dims) - 1,
                                semantic_order=semantic, machine=machine)
    backend = resolve_backend(backend)
    fused = bool(fused) and agg_op in ("sum", "mean")
    tile_m, blocked = 0, None
    align = 8
    if fused:
        avg_deg = g.num_edges / max(1, g.num_vertices)
        tile_m = suggest_tile_m(dims[0], dims[1], avg_deg,
                                dtype_bytes=2 if dtype == "bf16" else 4,
                                backend=backend, machine=machine)
        # a tile larger than the graph only pads; clamp to |V| rounded up,
        # keeping the sublane alignment
        tile_m = max(align, min(tile_m, -(-g.num_vertices // align) * align))
        if backend == PALLAS_TPU:
            # the kernel's own working set against the VMEM limit it is
            # compiled with, at 4-byte rows (a dedup layer gathers f32
            # partials even in a bf16 plan); refuses fused=True where no
            # tile fits
            from repro.kernels.ops import fit_fused_tile_m, tpu_vmem_budget
            tile_m = fit_fused_tile_m(tile_m, dims[0], dims[1], 4,
                                      budget=tpu_vmem_budget(),
                                      align=align)
        blocked = _blocked_for(g, tile_m)
    agg_layout = None
    if backend == PALLAS_TPU:
        # plan-owned layout for the UNFUSED seg_agg path (also the fusion
        # fallback's), so dispatch never regroups on the host (trace-pure)
        atile = max(align, min(128, -(-g.num_vertices // align) * align))
        agg_layout = _blocked_for(g, atile)
    return LayerPlan(index=index, kind=kind, dims=tuple(int(d) for d in dims),
                     agg_op=agg_op, include_self=include_self, order=order,
                     backend=backend, fused=fused, tile_m=tile_m,
                     blocked=blocked, agg_layout=agg_layout)


def _mesh_key(mesh):
    """Cache key for a mesh: identity PLUS shape/axis names, so an address
    reused by a differently-shaped mesh can never alias a cached plan."""
    if mesh is None:
        return None
    return (id(mesh), tuple(getattr(mesh, "axis_names", ())),
            tuple(mesh.devices.shape))


def build_plan(g: Graph, cfg, in_dim: int, num_classes: int, *,
               backend: str = AUTO, fused: Optional[bool] = None,
               ordering: Optional[str] = None, mesh=None,
               num_shards: int = 0, strategy: str = "ring",
               axis: str = "data", interpret: Optional[bool] = None,
               machine=None, reorder: str = "none",
               overlap: str = "none", dtype: str = "f32",
               dedup: str = "none",
               dedup_pad: Optional[tuple] = None) -> GraphExecutionPlan:
    """Plan a full model (``GCNModelConfig``) over one graph.

    Overrides: ``backend`` ("auto" resolves per platform -- see
    ``core.backend.resolve_backend``), ``fused`` / ``ordering`` (default
    from cfg), ``mesh`` (+ optionally ``num_shards``) for the shard
    partition, ``machine`` (a ``repro.profile.Machine`` or registry name:
    parameterizes the hardware-aware decisions -- ordering cost model, fused
    tile sizing, the ``reorder="auto"`` pricing -- and becomes the default
    for ``plan.instrument()``).
    Plans are cached: calling again with the same graph and
    arguments returns the same plan object (and any rebuilt plan on the
    same graph reuses the cached BlockedGraph).

    The ``reorder=`` contract (paper §5.1 guideline 1 as a planned
    decision):

      * ``"none"`` (default): execute in the caller's vertex numbering.
      * ``"degree"``: apply ``graph.reorder.degree_reorder`` ONCE at plan
        build (cached per graph); the plan stores perm/inverse, permutes
        features at ingress and un-permutes logits at egress *inside* the
        (traced) forward -- callers always pass and receive the natural
        vertex order, and ``plan.compile()`` bakes the gathers into the
        compiled executable.
      * ``"auto"``: decide from ``graph.reorder.choose_reorder`` --
        reuse-distance stats of the gather stream priced against the
        plan's ``machine`` (its on-chip row budget at ``in_dim``); picks
        "degree" only when the renumbering materially improves the modeled
        hit ratio.

    ``plan.describe()`` reports the resolved decision per layer.

    The ``overlap=`` contract (the distributed halo SCHEDULE, a planned
    decision like ordering/reorder):

      * ``"none"`` (default): single-buffered ring -- each hop's send waits
        behind its partial combine, collective time fully exposed.
      * ``"pipelined"``: double-buffered ring -- each ``ppermute`` is
        issued first and rides under the resident slab's partial combine;
        bit-for-bit equal outputs (eager and compiled), P-1 sends instead
        of P.  Requires ``strategy="ring"``.
      * ``"auto"``: priced by ``core.distributed.choose_overlap`` against
        the plan's ``machine`` (per-hop link bytes+latency vs. per-hop
        combine work, summed over the layers' exchanged widths); resolves
        to "pipelined" only when the hidden collective time is material.

    Local plans (``mesh=None``) always resolve to ``"none"``; the resolved
    schedule is stored on the plan, surfaced in ``describe()``, priced in
    ``plan.instrument()`` reports (exposed vs. overlapped collective
    time), and part of the plan cache key.

    The ``dtype=`` contract (execution precision as a planned decision):

      * ``"f32"`` (default): full precision -- bitwise-identical to every
        pre-dtype plan, eager and under ``plan.compile()``.
      * ``"bf16"``: aggregate AND combine run on bf16 operands with f32
        accumulators (kernel scratch / ``preferred_element_type``); halo
        exchanges move bf16 payloads -- exactly half the f32 bytes.
      * ``"int8-agg"``: only the AGGREGATION operand is quantized (per-row
        symmetric int8 scales, f32 accumulate, dequantized before
        combination stays f32).  Never auto-chosen -- the quantization
        error is a semantic opt-in.
      * ``"auto"``: resolved by ``profile.machine.choose_dtype`` against
        the plan's ``machine`` -- HBM aggregation traffic, matmul peak per
        precision (``Machine.native_bf16``), and the sharded halo's
        ``hop_time`` on the reduced payload.  Flips between presets:
        bf16 on TPU_V5E/A100, f32 on the paper's V100.

    The resolved dtype is stored on the plan (``plan.dtype``), surfaced in
    ``describe()``, recorded per phase by ``plan.instrument()`` (with the
    measured quantization error), and part of the plan cache key.

    The ``dedup=`` contract (redundancy-eliminated aggregation as a
    planned decision -- GraphACT-style, see ``graph.dedup``):

      * ``"none"`` (default): the naive per-edge fold, unchanged.
      * ``"pairs"``: ``dedup_layout_for_graph`` runs ONCE at plan build --
        greedy leading-pair matching over the dst-sorted edge list -- and
        the plan aggregates two-level: matched pair partials computed once
        (level 1), then a shortened edge list over ``[x ; partials]``
        (level 2).  f32 results stay BITWISE-identical to the naive fold,
        eager and under ``plan.compile()`` (the matching discipline only
        regroups the provably exact prefix of each segment's left fold).
        A graph with zero matchable pairs resolves back to "none".
      * ``"auto"``: priced by ``profile.machine.choose_dedup`` against the
        plan's ``machine`` -- modeled HBM aggregation bytes of the
        two-level layout vs. the naive fold at the widest layer's feature
        length; picks "pairs" only when the modeled saving is material
        (fanout-regular sampled blocks), "none" on sparse full-graph
        layers where few destinations share a leading pair.

    Dedup applies to the sum/mean aggregation paths (XLA, the Pallas
    tier, and the fused executor); distributed plans and ``max``
    aggregation coerce it to "none".  The resolved mode is stored on the
    plan (``plan.dedup``), surfaced in ``describe()``, recorded by
    ``plan.instrument()`` (``dedup_pairs`` / ``dedup_flops_saved``), and
    part of the plan cache key.

    ``dedup_pad=(num_pairs, num_edges2)`` pads the template layout's
    arrays to those static CAPACITIES with sink no-ops on the last vertex
    row (``graph.dedup.pad_dedup_arrays``) -- the bucket-plan form: a
    ``compile(dynamic=True)`` callable built from the padded template
    accepts any sampled block's runtime dedup arrays padded to the same
    shapes, so ONE compiled train/serve step covers blocks whose matched
    pair counts vary.  ``num_edges2`` is normally the bucket's full edge
    capacity and ``num_pairs`` its ``num_edges // 4`` upper bound (a kept
    pair needs >= 2 matched destinations x 2 edges).  Only meaningful
    with ``dedup != "none"``.

    The ``mesh=`` / ``num_shards=`` contract:

      * ``mesh=None`` (default): a local, single-device plan;
        ``num_shards`` / ``strategy`` / ``axis`` are ignored.
      * 1-D ``mesh`` (one named axis): the 1-D vertex partition.
        ``num_shards`` defaults to the mesh size when 0; ``axis`` names the
        mesh axis to shard over (default "data").
      * 2-D ``mesh`` (two named axes, (node, feature) in order): the 2-D
        node x feature partition (``graph.partition.partition_2d``); shard
        counts come from the mesh shape, ``num_shards``/``axis`` are
        ignored.  ``strategy`` ("ring" | "allgather") picks the node-axis
        halo pattern in both distributed forms.
      * On a mesh the layers run XLA ops, and ``backend`` picks what
        reduces a ring hop, by the same tier decision as one chip
        (``describe()``'s ``halo_kernel``): ``pallas-tpu`` (``"auto"`` on
        a TPU) gathers into ``seg_agg`` over owner buckets blocked once
        here (``core.distributed.hop_layouts``); ``xla`` (``"auto"`` on
        the CPU) keeps XLA's ``segment_sum``.

    Worked example (local planning, CPU container)::

        >>> spec = reduced_graph(CORA, 220, 24)
        >>> g, x = make_synthetic_graph(spec), make_features(spec)
        >>> plan = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
        ...                   spec.num_classes)         # backend="auto"
        >>> plan.describe()[0]["backend"]               # xla on CPU
        'xla'
        >>> out = plan.run_model(plan.init(jax.random.PRNGKey(0)), x)

    Worked example (2-D multi-host partition, 8 devices)::

        >>> mesh = jax.make_mesh((4, 2), ("node", "feat"))
        >>> plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
        ...                   mesh=mesh)                # 4 node x 2 feat
        >>> plan.partition_kind
        '2d'
        >>> with mesh:
        ...     out = plan.run_model(params, x)         # (V, num_classes)
    """
    agg = cfg.aggregator
    if mesh is not None:
        from repro.launch.mesh import auto_axes
        mesh = auto_axes(mesh)
    use_fused = cfg.fused if fused is None else bool(fused)
    req_order = cfg.ordering if ordering is None else ordering
    if machine is not None:
        from repro.profile.machine import get_machine
        machine = get_machine(machine)
    if reorder not in ("none", "degree", "auto"):
        raise ValueError(f"unknown reorder {reorder!r}; expected "
                         "'none' | 'degree' | 'auto'")
    if overlap not in ("none", "pipelined", "auto"):
        raise ValueError(f"unknown overlap {overlap!r}; expected "
                         "'none' | 'pipelined' | 'auto'")
    if overlap == "pipelined" and mesh is not None and strategy != "ring":
        raise ValueError("overlap='pipelined' requires strategy='ring'; "
                         "the all-gather halo has no per-hop structure "
                         "to pipeline")
    if dtype not in ("f32", "bf16", "int8-agg", "auto"):
        raise ValueError(f"unknown dtype {dtype!r}; expected "
                         "'f32' | 'bf16' | 'int8-agg' | 'auto'")
    if dedup not in ("none", "pairs", "auto"):
        raise ValueError(f"unknown dedup {dedup!r}; expected "
                         "'none' | 'pairs' | 'auto'")
    if dedup_pad is not None:
        if dedup == "none":
            raise ValueError("dedup_pad= is only meaningful with "
                             "dedup='pairs'/'auto'")
        dedup_pad = (int(dedup_pad[0]), int(dedup_pad[1]))
    spec_key = (cfg.name, cfg.conv, agg, tuple(cfg.hidden_dims),
                cfg.num_layers, int(in_dim), int(num_classes), backend,
                use_fused, req_order, _mesh_key(mesh), num_shards, strategy,
                axis, interpret, machine.name if machine else None, reorder,
                overlap, dtype, dedup, dedup_pad)

    def builder():
        # -- locality reorder decision (F4 / §5.1-1), before anything that
        #    depends on the vertex numbering (partition, blocked layouts)
        g_exec, perm, decision = g, None, reorder
        if decision != "none":
            g2, p = _reordered_for(g)
            if decision == "auto":
                from repro.graph.reorder import choose_reorder
                from repro.profile.machine import machine_for_backend
                dec_machine = machine or machine_for_backend(
                    resolve_backend(XLA if mesh is not None else backend))
                decision = choose_reorder(g, g2, p, int(in_dim),
                                          dec_machine)
            if decision == "degree":
                g_exec, perm = g2, p

        axes = ("node", "feat")
        if mesh is not None:
            axis_names = tuple(getattr(mesh, "axis_names", ()))
            if len(axis_names) == 2 and cfg.conv == "gin":
                raise ValueError(
                    "2-D (node x feature) plans split each matmul over the "
                    "feature axis; GIN's interior ReLU needs whole rows -- "
                    "use a 1-D mesh")
            if len(axis_names) == 2:                       # 2-D: node x feat
                from repro.graph.partition import partition_2d
                axes = axis_names
                p_nodes = int(mesh.shape[axis_names[0]])
                q_feats = int(mesh.shape[axis_names[1]])
                partition = partition_2d(g_exec, p_nodes, q_feats)
            else:                                          # 1-D vertex shard
                from repro.graph.partition import partition_1d
                shards = num_shards or int(mesh.devices.size)
                partition = partition_1d(g_exec, shards, edge_balanced=False)
            lay_backend, lay_fused = XLA, False  # shard_map path is XLA
        else:
            partition = None
            lay_backend, lay_fused = backend, use_fused

        hid = cfg.hidden_dims[0]
        dims_list = []
        d = in_dim
        for i in range(cfg.num_layers):
            dout = hid if i < cfg.num_layers - 1 else num_classes
            dims_list.append((d, cfg.hidden_dims[-1], dout)
                             if cfg.conv == "gin" else (d, dout))
            d = dout

        # -- execution precision (a planned decision like ordering):
        #    "auto" is priced HERE, from the layer dims and shard count,
        #    BEFORE the layers are planned -- the fused tile sizing
        #    consumes the resolved dtype's effective on-chip budget
        dt = dtype
        if dt == "auto":
            from repro.profile.machine import choose_dtype, \
                machine_for_backend
            dec_machine = machine or machine_for_backend(
                resolve_backend(lay_backend))
            shards = 1
            if partition is not None:
                shards = getattr(partition, "num_shards", None) or \
                    getattr(partition, "nodes", partition).num_shards
            # price the widest layer: the one whose bytes dominate
            widest = max(dims_list, key=lambda ds: ds[0] * ds[-1])
            dt = choose_dtype(g_exec.num_vertices, g_exec.num_edges,
                              widest[0], widest[-1], machine=dec_machine,
                              num_shards=int(shards))

        layers = [
            _plan_layer(g_exec, i, cfg.conv, dims, agg_op=agg,
                        ordering=req_order, backend=lay_backend,
                        fused=lay_fused, machine=machine, dtype=dt)
            for i, dims in enumerate(dims_list)]

        # -- pair-redundancy elimination (a planned decision like dtype):
        #    the host-side matching runs ONCE here; "auto" prices the
        #    two-level layout's modeled HBM bytes against the naive fold.
        #    Distributed plans and max aggregation coerce to "none" (the
        #    shard halo path folds per shard; max has no shareable adds).
        dd, dlayout = dedup, None
        if partition is not None or agg == "max":
            dd = "none"
        if dd != "none":
            from repro.graph.dedup import attach_blocked, \
                dedup_layout_for_graph
            lay = dedup_layout_for_graph(g_exec)
            if dd == "auto":
                from repro.profile.machine import choose_dedup, \
                    machine_for_backend
                dec_machine = machine or machine_for_backend(
                    resolve_backend(lay_backend))
                widest = max(dims_list, key=lambda ds: ds[0] * ds[-1])
                dd = choose_dedup(g_exec.num_vertices, g_exec.num_edges,
                                  widest[0], num_pairs=lay.num_pairs,
                                  num_edges2=lay.num_edges2,
                                  machine=dec_machine, dtype=dt)
            if dd == "pairs" and lay.num_pairs == 0:
                dd = "none"                 # nothing matchable: no-op plan
            if dd == "pairs" and dedup_pad is not None:
                # bucket form: pad the template layout to the requested
                # static capacities with sink no-ops (last vertex row)
                from repro.graph.dedup import pad_dedup_arrays
                pcap, ecap = dedup_pad
                pl_, pr_, s2_, d2_ = pad_dedup_arrays(
                    lay, pcap, ecap, g_exec.num_vertices - 1)
                lay = lay._replace(
                    pair_left=jnp.asarray(pl_), pair_right=jnp.asarray(pr_),
                    src2=jnp.asarray(s2_), dst2=jnp.asarray(d2_),
                    num_pairs=pcap, num_edges2=ecap)
            if dd == "pairs":
                if any(lp.fused and lp.blocked is not None for lp in layers) \
                        or any(lp.backend == PALLAS_TPU for lp in layers):
                    tiles = [lp.blocked.tile_m for lp in layers
                             if lp.fused and lp.blocked is not None]
                    atile = tiles[0] if tiles else max(
                        8, min(128, -(-g_exec.num_vertices // 8) * 8))
                    lay = attach_blocked(lay, atile)
                dlayout = lay

        # -- halo overlap schedule (a planned decision like ordering):
        #    resolved HERE so describe()/instrument()/the cache all state
        #    the schedule dispatch will actually run; local plans have no
        #    collective to schedule
        ov = overlap if partition is not None else "none"
        hops = None
        if partition is not None:
            from repro.graph.partition import Partition2D
            if isinstance(partition, Partition2D):
                pg_nodes = partition.nodes
                width = partition.feature_block
            else:
                pg_nodes, width = partition, (lambda f: f)
            # what each layer's exchange actually moves (dout under
            # combine-first, din otherwise; the F/Q column slice on a
            # 2-D partition)
            lens = [width(lp.din if lp.order == AGGREGATE_FIRST
                          else lp.dout) for lp in layers]
            if ov == "auto":
                from repro.core.distributed import choose_overlap
                from repro.profile.machine import machine_for_backend
                # one schedule per plan, priced on every layer's exchange
                ov = choose_overlap(pg_nodes, lens,
                                    machine or machine_for_backend(XLA),
                                    strategy=strategy)
            # -- the ring hop's reduce: the same tier decision as the
            #    one-chip aggregation (the layers' own backend stays XLA)
            if strategy == "ring" and resolve_backend(backend) == PALLAS_TPU:
                from repro.core.distributed import hop_layouts
                hops = hop_layouts(pg_nodes, lens)

        return GraphExecutionPlan(
            g_exec, layers, interpret=resolve_interpret(interpret),
            mesh=mesh, partition=partition, strategy=strategy, axis=axis,
            axes=axes, machine=machine, reorder=decision, perm=perm,
            overlap=ov, dtype=dt, dedup=dd, dedup_layout=dlayout,
            hops=hops)

    return _cached_plan(g, spec_key, builder)


def plan_for_conv(conv, g: Graph, *, machine=None) -> GraphExecutionPlan:
    """Single-layer plan for a standalone conv (GCNConv / SAGEConv / GINConv
    ``apply`` without a model-level plan).

    The conv's own ``ordering`` / ``backend`` / ``fused`` attributes are the
    requested decisions; this resolves them once per (conv spec, graph) and
    caches the plan, so repeated ``conv.apply(params, g, x)`` calls pay no
    planning cost.  ``machine`` (a ``repro.profile.Machine`` or registry
    name) parameterizes the hardware-aware decisions exactly as in
    ``build_plan`` -- ordering cost model and fused tile sizing -- and is
    part of the cache key (previously it was silently dropped and
    standalone convs always planned with preset defaults).

    Worked example::

        >>> conv = GCNConv(din=24, dout=8)      # backend="auto"
        >>> plan = plan_for_conv(conv, g)
        >>> plan.num_layers, plan.layers[0].kind
        (1, 'gcn')
        >>> out = plan.run_layer(conv_params, x)  # == conv.apply(...)
    """
    kind = type(conv).__name__.replace("Conv", "").lower()
    dims = (conv.din, conv.hidden, conv.dout) if kind == "gin" \
        else (conv.din, conv.dout)
    agg_op = "sum" if kind == "gin" else "mean"
    backend = getattr(conv, "backend", AUTO)
    fused = bool(getattr(conv, "fused", False))
    if machine is not None:
        from repro.profile.machine import get_machine
        machine = get_machine(machine)
    spec_key = ("conv", kind, dims, conv.ordering, backend, fused,
                machine.name if machine else None)

    def builder():
        lp = _plan_layer(g, 0, kind, dims, agg_op=agg_op,
                         ordering=conv.ordering, backend=backend,
                         fused=fused, machine=machine)
        return GraphExecutionPlan(g, [lp],
                                  interpret=default_interpret(),
                                  machine=machine)

    return _cached_plan(g, spec_key, builder)


def plan_for_phases(g: Graph, weights, *, order: Optional[str] = None,
                    agg_op: str = "mean", backend: str = AUTO,
                    fused: bool = False, machine=None) -> GraphExecutionPlan:
    """Single-layer plan for a raw weight list (``phase_ordered_layer``).

    ``weights`` is a list of (W, b) tuples; the layer dims are inferred
    from the weight shapes.  ``order=None`` lets the scheduler's cost model
    decide (paper F2): it picks combine-first whenever the projection
    shrinks the feature length the sparse phase must move.  ``machine``
    (a ``repro.profile.Machine`` or registry name) parameterizes the
    hardware-aware decisions as in ``build_plan`` and keys the cache.

    Worked example::

        >>> w = jnp.zeros((24, 8))              # 24 -> 8 shrinks
        >>> plan = plan_for_phases(g, [(w, None)], agg_op="mean")
        >>> plan.layers[0].order
        'combine_first'
        >>> out = plan.run_phases(x, [(w, None)], activation="none")
    """
    dims = tuple([int(w.shape[0]) for (w, _) in weights] +
                 [int(weights[-1][0].shape[1])])
    if machine is not None:
        from repro.profile.machine import get_machine
        machine = get_machine(machine)
    spec_key = ("phase", dims, order, agg_op, backend, fused,
                machine.name if machine else None)

    def builder():
        lp = _plan_layer(g, 0, "phase", dims, agg_op=agg_op,
                         ordering=order or AUTO, backend=backend,
                         fused=fused, machine=machine)
        return GraphExecutionPlan(g, [lp],
                                  interpret=default_interpret(),
                                  machine=machine)

    return _cached_plan(g, spec_key, builder)

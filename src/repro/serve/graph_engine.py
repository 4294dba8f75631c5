"""GraphServeEngine: GCN node-prediction serving through bucketed compiled plans.

The paper characterizes GCN *inference* as the GPU workload that matters;
this engine is the repo's millions-of-users path for it.  It instantiates
the shared serving core (``repro.serve.core.SlotServeCore``) for graph
traffic the same way ``ServeEngine`` instantiates it for LM decode:

  * **Admission** (host-side, the data-pipeline half): each node-prediction
    request samples its 2-hop frontier (``graph.sampling.two_hop_batch``,
    the paper's SAG setting) from one long-lived RNG, merges both hops into
    one destination-sorted union block, and picks the smallest *shape
    bucket* that fits.
  * **Dispatch** (device-side, the planned half): every bucket
    ``(num_seeds, num_inputs, num_edges)`` owns exactly ONE
    ``plan.compile(dynamic=True, donate=True)`` callable (each call pads a
    fresh feature buffer, so donating it lets the device recycle the
    bucket's input allocation under sustained load) -- the vLLM/aphrodite
    ``_BATCH_SIZES_TO_CAPTURE`` idiom applied to graphs: the sampled block
    is padded into the bucket's static shapes (zero feature rows, sink
    self-edges, zero in-degrees) and executed with the edge arrays as
    runtime data, so ANY block that fits the bucket replays the same
    compiled executable with zero retraces.  Padding is exact: pad edges
    only touch the sink row, so real rows are bit-identical to an eager
    forward on the unpadded block.
  * **Lifecycle / stats**: slots bound in-flight requests and are reused on
    completion; per-request latency percentiles (p50/p95/p99) and
    throughput report through the ``WorkloadReport`` machinery
    (``workload_report()``).

Each request's host work is recorded as spans with its ``rid``
(``repro.profile.spans``): ``serve.queue``; ``serve.admit`` with its
children ``serve.sample`` and ``serve.union``; ``serve.dispatch`` with its
children ``serve.pad``, ``serve.transfer``, ``serve.execute`` and
``serve.readback``.  Counters ``serve.pad_rows`` (bucket rows beyond the
real frontier) and ``serve.h2d_bytes`` (bytes moved to the device) add up
over requests.

Requests too large for every bucket are *bucket misses*: served through a
per-request eager plan (correct but slow) and counted -- the smoke gate
hard-fails on any miss.  Per-request plans are what the plan-cache
eviction policy exists for: ``warmup()`` pins the bucket plans and the
engine sweeps transient plans via ``core.plan.clear_plan_cache(keep=...)``
whenever the cache crosses ``plan_cache_watermark``.

Worked example (docs/serving.md walks the full lifecycle)::

    engine = GraphServeEngine(g, PAPER_MODELS["gcn"], params, features,
                              num_classes=7, fanouts=(5, 5))
    engine.warmup()                      # compile every bucket up front
    engine.submit(GraphRequest(rid=0, seeds=np.array([3, 17, 401])))
    done = engine.run()
    done[0].logits                       # (3, 7) seed logits
    print(engine.workload_report().to_markdown())
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import build_plan, clear_plan_cache, plan_cache_stats
from repro.graph.sampling import SampledBlock, two_hop_batch
from repro.graph.structure import Graph, graph_from_coo
from repro.profile.spans import count, span
from repro.serve.core import SlotServeCore


class Bucket(NamedTuple):
    """One serving shape bucket; every field is a static compiled dim.

    ``num_seeds`` bounds the request batch (seed vertices per request),
    ``num_inputs`` the padded frontier rows, ``num_edges`` the padded
    union edge list.  A sampled block *fits* iff seeds/edges fit and the
    frontier leaves a sink row for pad edges when padding is needed
    (``fits``).
    """

    num_seeds: int
    num_inputs: int
    num_edges: int

    def fits(self, seeds: int, inputs: int, edges: int) -> bool:
        """True iff a block of these REAL sizes can pad into this bucket.

        Pad edges are sink self-loops on the last row, so when any edge
        padding is needed (``edges < num_edges``) the frontier must leave
        at least one pad row free to serve as the sink."""
        if seeds > self.num_seeds or edges > self.num_edges:
            return False
        limit = self.num_inputs if edges == self.num_edges \
            else self.num_inputs - 1
        return inputs <= limit


def default_buckets(fanouts: Tuple[int, int],
                    seed_levels: Sequence[int] = (4, 16, 64),
                    max_inputs: Optional[int] = None) -> Tuple[Bucket, ...]:
    """Worst-case bucket ladder for ``two_hop_batch`` sampling.

    One bucket per seed level: ``sample_neighbors`` emits exactly
    ``n * fanout`` edges per hop and at most ``n * (1 + fanout)`` frontier
    vertices, so the worst case is closed-form -- hop-1 inputs
    ``s*(1+f1)``, union frontier ``s*(1+f1)*(1+f2)``, union edges
    ``s*f1 + s*(1+f1)*f2`` -- plus one reserved sink row for pad edges.
    ``max_inputs`` (e.g. ``g.num_vertices``) caps the frontier dim.
    """
    f1, f2 = int(fanouts[0]), int(fanouts[1])
    out = []
    for s in sorted(int(v) for v in seed_levels):
        n1 = s * (1 + f1)
        frontier = n1 * (1 + f2)
        if max_inputs is not None:
            frontier = min(frontier, int(max_inputs))
        out.append(Bucket(num_seeds=s, num_inputs=frontier + 1,
                          num_edges=s * f1 + n1 * f2))
    return tuple(out)


@dataclasses.dataclass
class GraphRequest:
    """One node-prediction request: logits for a batch of seed vertices."""

    rid: int
    seeds: np.ndarray                     # (s,) global vertex ids
    # filled by the engine
    logits: Optional[np.ndarray] = None   # (s, num_classes)
    bucket: Optional[Bucket] = None       # None => served as a bucket miss
    frontier_size: int = 0                # real union-frontier rows
    edge_count: int = 0                   # real union edges
    done: bool = False
    enqueue_t: float = 0.0
    finish_t: float = 0.0
    prep: Any = dataclasses.field(default=None, repr=False)


@dataclasses.dataclass
class PreparedBlock:
    """Host-side admission product: the sampled union block, bucketed."""

    frontier: np.ndarray                  # (n,) global frontier vertex ids
    graph: Graph                          # unpadded dst-sorted union graph
    seed_pos: np.ndarray                  # (s,) seed rows within frontier
    bucket: Optional[Bucket]              # None = no bucket fits (miss)


def _index_of(haystack: np.ndarray, needles: np.ndarray) -> np.ndarray:
    """Positions of ``needles`` inside sorted unique ``haystack``."""
    pos = np.searchsorted(haystack, needles)
    assert (np.asarray(haystack)[pos] == np.asarray(needles)).all(), \
        "frontier must cover the needles"
    return pos.astype(np.int32)


def union_two_hop(hop2: SampledBlock, hop1: SampledBlock,
                  seeds: np.ndarray) -> Tuple[np.ndarray, Graph, np.ndarray]:
    """Merge a (hop2, hop1) sampled pair into ONE union block.

    Both hops' edges are renumbered into the hop-2 input frontier (a
    superset of hop-1 inputs and seeds) and concatenated into a single
    destination-sorted multigraph over ``len(frontier)`` vertices -- the
    sampled-subgraph inference form, where a 2-layer planned forward over
    the union graph yields seed logits at ``seed_pos``.  One graph per
    request is what lets one ``plan.compile(dynamic=True)`` callable per
    bucket serve the whole model.
    """
    frontier = np.asarray(hop2.input_ids)
    pos_h1 = _index_of(frontier, hop1.input_ids)
    seed_pos = _index_of(frontier, seeds)
    # hop2 edges: src already frontier-local, dst indexes hop1.input_ids
    src = np.concatenate([np.asarray(hop2.graph.src),
                          pos_h1[np.asarray(hop1.graph.src)]])
    dst = np.concatenate([pos_h1[np.asarray(hop2.graph.dst)],
                          seed_pos[np.asarray(hop1.graph.dst)]])
    g = graph_from_coo(src, dst, len(frontier))
    return frontier, g, seed_pos


class GraphServeEngine(SlotServeCore):
    """Continuous-batching GCN inference on the shared serving core.

    Two instantiations of one loop: where the LM ``ServeEngine``'s
    admission is prefill-into-slot and its step is one batched decode,
    this engine's admission is sample+bucket (host pipeline work) and its
    step drains every active slot through its bucket's single compiled
    callable.  See the module docstring for the serving contract and
    ``docs/serving.md`` for the full lifecycle.
    """

    def __init__(self, g: Graph, cfg, params, features, num_classes: int, *,
                 buckets: Optional[Sequence[Tuple[int, int, int]]] = None,
                 fanouts: Tuple[int, int] = (5, 5), max_batch: int = 8,
                 seed: int = 0, machine=None, ordering: Optional[str] = None,
                 plan_cache_watermark: int = 32, donate: bool = True):
        super().__init__(max_batch)
        self.g = g
        self.cfg = cfg
        self.params = params
        self.features = np.asarray(features, np.float32)
        self.in_dim = int(self.features.shape[1])
        self.num_classes = int(num_classes)
        self.fanouts = (int(fanouts[0]), int(fanouts[1]))
        self.machine = machine
        self.ordering = ordering
        self.plan_cache_watermark = int(plan_cache_watermark)
        # donate the padded feature buffer to each bucket call: every call
        # builds a fresh padded x, so under sustained load the device
        # reuses the bucket's feature allocation instead of holding two.
        # (On CPU XLA ignores donation with a one-time warning; harmless.)
        self.donate = bool(donate)
        self.rng = np.random.default_rng(seed)
        if buckets is None:
            buckets = default_buckets(self.fanouts,
                                      max_inputs=g.num_vertices)
        # selection order: smallest padded frontier, then edges, then seeds
        self.buckets: Tuple[Bucket, ...] = tuple(sorted(
            (Bucket(*b) for b in buckets),
            key=lambda b: (b.num_inputs, b.num_edges, b.num_seeds)))
        self._plans: Dict[Bucket, Any] = {}      # bucket -> plan
        self._fns: Dict[Bucket, Any] = {}        # bucket -> CompiledPlan
        self._bucket_hits: Dict[Bucket, int] = {b: 0 for b in self.buckets}
        self._bucket_misses = 0
        self._cache_sweeps = 0
        self._warmed = False

    # ----------------------------------------------------------- bucket mgmt

    def _template_graph(self, bucket: Bucket) -> Graph:
        """Deterministic template with the bucket's static shapes (edge
        CONTENT is irrelevant -- it is replaced per call by the dynamic
        compiled plan; only shapes and the plan's cost-model inputs
        |V|, |E| matter)."""
        n, e = bucket.num_inputs, bucket.num_edges
        idx = np.arange(e, dtype=np.int32) % n
        return graph_from_coo(idx, idx, n)

    def _bucket_plan(self, bucket: Bucket):
        plan = self._plans.get(bucket)
        if plan is None:
            plan = build_plan(self._template_graph(bucket), self.cfg,
                              self.in_dim, self.num_classes, backend="xla",
                              fused=False, ordering=self.ordering,
                              machine=self.machine)
            self._plans[bucket] = plan
            self._fns[bucket] = plan.compile(dynamic=True,
                                             donate=self.donate)
        return plan, self._fns[bucket]

    def select_bucket(self, num_seeds: int, num_inputs: int,
                      num_edges: int) -> Optional[Bucket]:
        """Smallest fitting bucket (selection order: padded frontier rows,
        then edges, then seeds); None when every bucket is too small --
        a bucket MISS, served eagerly and counted in ``stats()``."""
        for b in self.buckets:
            if b.fits(num_seeds, num_inputs, num_edges):
                return b
        return None

    def warmup(self) -> Dict[str, int]:
        """Compile every bucket BEFORE admission and pin the bucket plans.

        Traces each bucket's single dynamic callable once on its template
        shapes (so first-request latency is honest -- no hidden compile),
        then sweeps the plan cache down to exactly the bucket plans
        (``clear_plan_cache(keep=...)``).  Idempotent; returns
        ``{bucket-name: num_traces}`` -- every value is 1 after a fresh
        warm-up and STAYS 1 through serving (the zero-retrace contract).
        """
        for b in self.buckets:
            plan, fn = self._bucket_plan(b)
            if fn.num_traces == 0:
                x = jnp.zeros((b.num_inputs, self.in_dim), jnp.float32)
                fn(self.params, x, plan.g)
        clear_plan_cache(keep=list(self._plans.values()))
        self._cache_sweeps += 1
        self._warmed = True
        return {self._bucket_name(b): self._fns[b].num_traces
                for b in self.buckets}

    @staticmethod
    def _bucket_name(b: Bucket) -> str:
        return f"s{b.num_seeds}/v{b.num_inputs}/e{b.num_edges}"

    def init_params(self, key):
        """Params pytree for the engine's model (any bucket plan's
        ``init`` -- the shapes depend only on (cfg, in_dim, classes))."""
        plan, _ = self._bucket_plan(self.buckets[0])
        return plan.init(key)

    # ----------------------------------------------------------- preparation

    def prepare(self, seeds: np.ndarray, rid=None) -> PreparedBlock:
        """Host-side admission work for one request: sample the 2-hop
        frontier (fresh draws from the engine's long-lived RNG;
        ``serve.sample``), merge into the union block and select the
        bucket (``serve.union``)."""
        seeds = np.asarray(seeds, np.int32)
        with span("serve.sample", rid=rid):
            hop2, hop1 = two_hop_batch(self.g, seeds, self.fanouts,
                                       rng=self.rng)
        with span("serve.union", rid=rid):
            frontier, ug, seed_pos = union_two_hop(hop2, hop1, seeds)
            bucket = self.select_bucket(len(seeds), len(frontier),
                                        ug.num_edges)
        return PreparedBlock(frontier=frontier, graph=ug, seed_pos=seed_pos,
                             bucket=bucket)

    def _pad_into(self, prep: PreparedBlock, bucket: Bucket
                  ) -> Tuple[np.ndarray, ...]:
        """Pad the union block into the bucket's static shapes, on the
        host: ``(x, src, dst, in_deg)``.

        Exactness contract: pad feature rows are zero, pad edges are
        sink self-loops on the LAST row (preserving the dst-sort), pad
        in-degrees are zero -- so every real row sees exactly the real
        edge set in the real (sorted) order, and the padded compiled
        result is bit-identical to the unpadded eager forward.
        """
        n, e = len(prep.frontier), prep.graph.num_edges
        pad_e = bucket.num_edges - e
        sink = bucket.num_inputs - 1
        src = np.concatenate([np.asarray(prep.graph.src, np.int32),
                              np.full(pad_e, sink, np.int32)])
        dst = np.concatenate([np.asarray(prep.graph.dst, np.int32),
                              np.full(pad_e, sink, np.int32)])
        in_deg = np.zeros(bucket.num_inputs, np.int32)
        in_deg[:n] = np.asarray(prep.graph.in_deg, np.int32)
        x = np.zeros((bucket.num_inputs, self.in_dim), np.float32)
        x[:n] = self.features[prep.frontier]
        count("serve.pad_rows", bucket.num_inputs - n)
        return x, src, dst, in_deg

    # ------------------------------------------------------------- execution

    def run_prepared(self, prep: PreparedBlock, rid=None) -> np.ndarray:
        """Serve one prepared block through its bucket's compiled callable
        (the production path); falls back to ``run_eager`` on a miss.

        Spans, each with ``rid``: ``serve.pad`` (host pad),
        ``serve.transfer`` (host to device, waited for), ``serve.execute``
        (the bucket call, waited for) and ``serve.readback``."""
        if prep.bucket is None:
            return self.run_eager(prep)
        plan, fn = self._bucket_plan(prep.bucket)
        with span("serve.pad", rid=rid):
            host = self._pad_into(prep, prep.bucket)
        with span("serve.transfer", rid=rid):
            x, src, dst, in_deg = jax.block_until_ready(
                [jnp.asarray(a) for a in host])
        count("serve.h2d_bytes", sum(a.nbytes for a in host))
        g = Graph(src=src, dst=dst, in_deg=in_deg, out_deg=in_deg,
                  num_vertices=prep.bucket.num_inputs)
        with span("serve.execute", rid=rid):
            out = fn(self.params, x, g).block_until_ready()
        with span("serve.readback", rid=rid):
            return np.asarray(out)[prep.seed_pos]

    def run_eager(self, prep: PreparedBlock) -> np.ndarray:
        """Unpadded eager reference for a prepared block.

        With a bucket: the SAME bucket plan replays its planned decisions
        eagerly on the unpadded union graph (``run_model(graph=...)``) --
        the oracle the padded compiled path must match bit-for-bit.
        Without one (a miss): a per-request plan is built for the union
        graph -- correct, but host planning work per request; these
        transient plans are what the cache eviction policy sweeps.
        """
        x = jnp.asarray(self.features[prep.frontier])
        if prep.bucket is not None:
            plan, _ = self._bucket_plan(prep.bucket)
            out = plan.run_model(self.params, x, graph=prep.graph)
        else:
            plan = build_plan(prep.graph, self.cfg, self.in_dim,
                              self.num_classes, backend="xla", fused=False,
                              ordering=self.ordering, machine=self.machine)
            out = plan.run_model(self.params, x)
        return np.asarray(out)[prep.seed_pos]

    # ------------------------------------------------------------ core hooks

    def _admit_into_slot(self, slot: int, req: GraphRequest) -> bool:
        with span("serve.admit", rid=req.rid):
            req.prep = self.prepare(req.seeds, rid=req.rid)
        req.bucket = req.prep.bucket
        req.frontier_size = len(req.prep.frontier)
        req.edge_count = req.prep.graph.num_edges
        if req.bucket is None:
            self._bucket_misses += 1
        return False                       # always needs a dispatch step

    def _step(self) -> List[GraphRequest]:
        if not self._active:
            return []
        finished = []
        for slot in sorted(self._active):
            req = self._active[slot]
            with span("serve.dispatch", rid=req.rid) as sp:
                req.logits = self.run_prepared(req.prep, rid=req.rid)
            if req.bucket is not None:
                self._bucket_hits[req.bucket] += 1
            finished.append(self._complete(slot, t=sp.t1))
        self._steps += 1
        self._maybe_sweep_plan_cache()
        return finished

    def _maybe_sweep_plan_cache(self) -> None:
        """The eviction policy: whenever transient per-request plans push
        the global plan cache past the watermark, sweep everything but
        the pinned bucket plans."""
        if self._plans and \
                plan_cache_stats()["size"] > self.plan_cache_watermark:
            clear_plan_cache(keep=list(self._plans.values()))
            self._cache_sweeps += 1

    # ---------------------------------------------------------------- stats

    def retraces(self) -> int:
        """Compiled-callable traces beyond the one each bucket is allowed
        (> 0 means the zero-retrace serving contract was violated)."""
        return sum(max(0, fn.num_traces - 1) for fn in self._fns.values())

    def stats(self) -> Dict[str, Any]:
        """Core serving stats plus the graph engine's bucket/cache view."""
        out = super().stats()
        out.update(
            warmed=self._warmed,
            bucket_hits=sum(self._bucket_hits.values()),
            bucket_misses=self._bucket_misses,
            retraces=self.retraces(),
            cache_sweeps=self._cache_sweeps,
            plan_cache=plan_cache_stats(),
            buckets=[{"num_seeds": b.num_seeds, "num_inputs": b.num_inputs,
                      "num_edges": b.num_edges,
                      "hits": self._bucket_hits[b],
                      "compiled": self._fns[b].num_traces
                      if b in self._fns else 0}
                     for b in self.buckets])
        return out

    def serving_summary(self) -> Dict[str, Any]:
        """The ``WorkloadReport.serving`` section: request count, latency
        percentiles, throughput, and the bucket/retrace counters the
        smoke gate hard-fails on."""
        s = self.stats()
        return {"requests": s["served"],
                "p50_ms": s["p50_ms"], "p95_ms": s["p95_ms"],
                "p99_ms": s["p99_ms"],
                "throughput_rps": s["throughput_rps"],
                "bucket_misses": s["bucket_misses"],
                "retraces": s["retraces"],
                "buckets": s["buckets"]}

    def workload_report(self, machine=None):
        """One ``WorkloadReport`` for the serving session.

        Per-phase records come from an instrumented eager forward over the
        busiest bucket's template shapes (the same dispatch path the
        compiled callable traced); the per-request latency percentiles /
        throughput / bucket counters ride along as ``report.serving`` and
        are schema-validated with the rest of the report.
        """
        busiest = max(self.buckets,
                      key=lambda b: (self._bucket_hits[b], -b.num_inputs))
        plan, _ = self._bucket_plan(busiest)
        x = jnp.zeros((busiest.num_inputs, self.in_dim), jnp.float32)
        report = plan.instrument(machine=machine or self.machine) \
            .run_model(self.params, x)
        report.serving = self.serving_summary()
        return report.validate()

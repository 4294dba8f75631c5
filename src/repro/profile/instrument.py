"""InstrumentedPlan / WorkloadReport: one forward pass -> Table-3/4 breakdown.

``plan.instrument(machine=A100)`` wraps a ``GraphExecutionPlan`` so that one
``run_model`` call records, per layer and per *executed* phase, what the
paper's Tables 3-5 tabulate: phase name, backend tier, ordering, analytic
FLOPs / bytes / arithmetic intensity, collective bytes (distributed plans),
and measured wall time -- into a typed ``WorkloadReport`` with ``to_json()``
and ``to_markdown()`` renderers.

The records come from a probe threaded through the SAME dispatch code the
plan replays in production (``core.plan._execute_layer``), not a parallel
re-implementation -- so ``WorkloadReport.mismatches(plan)`` is a real
regression guard: it cross-checks the decisions ``plan.describe()`` *claims*
against the phases that actually executed (ordering from the phase sequence,
backend from the aggregation record, fusion from whether the fused phase
ran).

``run_model(..., compiled=True)`` additionally times the plan's COMPILED
path (``plan.compile()`` -- whole forward and per layer) and attaches the
wall times to the report, so one call states the eager-vs-compiled speedup
per layer alongside the per-phase breakdown.

Wall times follow the repo-wide convention (repro.profile.bench): on CPU
they are correctness-shaped observables, not accelerator predictions; the
analytic FLOP/byte columns are machine-independent and exact.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax

from repro.profile.machine import Machine, machine_for_backend

_DTYPE_BYTES = 4  # the framework's f32 feature convention

#: every phase name a record may carry (schema-validated)
PHASES = ("aggregate", "combine", "fused_agg_combine", "distributed")

SCHEMA = "repro.profile/workload-report"
SCHEMA_VERSION = 1


class WorkloadReportError(ValueError):
    """A WorkloadReport violated its schema (empty/ill-typed records)."""


@dataclass(frozen=True)
class PhaseRecord:
    """One executed phase of one layer, with analytic costs + wall time.

    ``feature_len`` is the feature length the phase actually moved (for
    aggregation phases this is the paper's Table-4 variable: dout under
    combine-first, din under aggregate-first).  ``bound`` classifies the
    phase's arithmetic intensity against the report's Machine balance.

    ``dtype`` is the storage precision this phase's reduced operand used
    (the plan's ``dtype=`` decision as dispatched: ``"int8-agg"`` plans
    record their combine phases as ``"f32"`` because only the aggregation
    operand is quantized).  ``quant_error`` is the max-abs difference
    between the phase's full-precision operand and its reduced form,
    observed at probe time -- exactly 0.0 on f32 plans (the bitwise-golden
    contract), necessarily nonzero somewhere on any reduced-precision run
    (``validate()`` enforces both directions).

    Distributed records additionally split the modeled collective wall
    time by the plan's halo SCHEDULE (``overlap=``):
    ``exposed_collective_time`` is the seconds of wire time the schedule
    leaves on the critical path, ``overlapped_collective_time`` the
    seconds hidden under the per-hop partial combine -- analytic from
    ``core.distributed.overlap_model`` on the report's Machine, priced for
    the overlap mode the dispatch actually ran (the probe receives it from
    the dispatch call, and ``mismatches()`` cross-checks it against
    ``describe()``).  Both are 0.0 on non-distributed phases.
    """

    layer: int
    phase: str              # one of PHASES
    order: str
    backend: str
    fused: bool
    feature_len: int
    flops: float
    bytes: float
    collective_bytes: float
    wall_time_s: float
    bound: str              # "memory" | "compute" vs the report's Machine
    exposed_collective_time: float = 0.0     # modeled s, on critical path
    overlapped_collective_time: float = 0.0  # modeled s, hidden under hops
    dtype: str = "f32"      # storage precision of the dispatched operand
    quant_error: float = 0.0  # max|full - reduced| observed at probe time
    #: schedule-exact collective bytes of the TRACED halo program
    #: (``core.distributed.schedule_wire_bytes``): what the ppermute /
    #: all_gather / psum_scatter eqns actually put on the wire, per
    #: device -- the quantity ``repro.analysis.jaxpr_lint`` extracts
    #: from the jaxpr and equates byte-for-byte.  ``collective_bytes``
    #: stays the analytic cut-edge LOWER BOUND (min_halo_bytes); both
    #: are 0.0 on non-distributed phases.
    wire_collective_bytes: float = 0.0
    #: pair-redundancy elimination (``dedup="pairs"`` plans): matched pair
    #: count of the two-level layout this aggregation dispatched over, and
    #: the analytic adds it eliminated vs. the naive fold at this record's
    #: feature length (``graph.dedup.DedupLayout.flops_saved``).  Both 0
    #: on non-aggregation phases and on ``dedup="none"`` plans; the flops/
    #: bytes columns of a dedup record already price the TWO-LEVEL layout
    #: (``graph.dedup.dedup_cost``), so these state the delta explicitly.
    dedup_pairs: int = 0
    dedup_flops_saved: float = 0.0

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(1.0, self.bytes)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "layer": self.layer, "phase": self.phase, "order": self.order,
            "backend": self.backend, "fused": self.fused,
            "feature_len": self.feature_len, "flops": self.flops,
            "bytes": self.bytes,
            "arithmetic_intensity": self.arithmetic_intensity,
            "collective_bytes": self.collective_bytes,
            "exposed_collective_time": self.exposed_collective_time,
            "overlapped_collective_time": self.overlapped_collective_time,
            "wall_time_s": self.wall_time_s, "bound": self.bound,
            "dtype": self.dtype, "quant_error": self.quant_error,
            "wire_collective_bytes": self.wire_collective_bytes,
            "dedup_pairs": self.dedup_pairs,
            "dedup_flops_saved": self.dedup_flops_saved,
        }


class _Probe:
    """Threaded through ``core.plan._execute_layer`` to observe dispatch.

    ``run(name, thunk, lp=..., **meta)`` executes the phase, blocks on its
    result for a wall time, derives the phase's analytic cost from the
    graph + layer plan, and appends a PhaseRecord.  Record order IS
    execution order (the ordering consistency check depends on that).
    """

    def __init__(self, plan, machine: Machine):
        self.plan = plan
        self.machine = machine
        self.records: List[PhaseRecord] = []
        self.reorder_applied = False   # set by the plan's ingress permute

    def note_reorder(self) -> None:
        """Called by ``GraphExecutionPlan._ingress`` when the planned vertex
        renumbering is actually applied -- the observation
        ``WorkloadReport.mismatches`` checks describe()'s ``reorder``
        against."""
        self.reorder_applied = True

    def run(self, name: str, thunk, *, lp, **meta):
        from repro.core.backend import resolve_backend
        t0 = time.perf_counter()
        out = thunk()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        flops, byt, coll, flen, exp_s, ovl_s = self._cost(name, lp, meta)
        ai = flops / max(1.0, byt)
        # the phase's storage precision as the plan dispatched it: int8-agg
        # quantizes ONLY the aggregation operand, so its combine records
        # stay f32 (mismatches() checks describe() against this rule)
        pd = getattr(self.plan, "dtype", "f32")
        rec_dtype = "f32" if (pd == "int8-agg" and name == "combine") else pd
        # backend as the dispatch layer resolves it at call time -- NOT
        # lp.backend verbatim, so a plan that regressed to storing an
        # unresolved "auto" is caught by mismatches() as
        # describe-vs-dispatch drift
        self.records.append(PhaseRecord(
            layer=lp.index, phase=name, order=lp.order,
            backend=resolve_backend(lp.backend) if name != "combine"
            else "xla",
            fused=(name == "fused_agg_combine"),
            feature_len=int(flen), flops=float(flops), bytes=float(byt),
            collective_bytes=float(coll), wall_time_s=float(dt),
            bound=self.machine.classify(ai),
            exposed_collective_time=float(exp_s),
            overlapped_collective_time=float(ovl_s),
            dtype=rec_dtype,
            quant_error=float(meta.get("quant_error", 0.0)),
            wire_collective_bytes=(
                self._wire_bytes(lp, flen, meta)
                if name == "distributed" else 0.0),
            dedup_pairs=self._dedup_layout(name).num_pairs
            if self._dedup_layout(name) else 0,
            dedup_flops_saved=float(
                self._dedup_layout(name).flops_saved(int(flen)))
            if self._dedup_layout(name) else 0.0))
        return out

    def _dedup_layout(self, phase_name: str):
        """The plan's two-level layout when this phase dispatched over it
        (aggregation phases of a resolved ``dedup="pairs"`` plan)."""
        if phase_name not in ("aggregate", "fused_agg_combine"):
            return None
        if getattr(self.plan, "dedup", "none") != "pairs":
            return None
        return getattr(self.plan, "dedup_layout", None)

    # -- analytic per-phase costs (same models the scheduler prices) --------

    def _agg_cost(self, name, lp, flen):
        """Aggregation-side analytic cost: the two-level ``dedup_cost``
        when this phase dispatched over the plan's pair layout (that IS
        the program the probe timed), ``aggregate_cost`` otherwise."""
        from repro.core.phases import aggregate_cost
        lay = self._dedup_layout(name)
        if lay is not None:
            from repro.graph.dedup import dedup_cost
            return dedup_cost(lay, flen, include_self=lp.include_self)
        return aggregate_cost(self.plan.g, flen,
                              include_self=lp.include_self)

    def _cost(self, name, lp, meta):
        from repro.core.phases import aggregate_cost, combine_cost
        g = self.plan.g
        v = g.num_vertices
        if name == "aggregate":
            flen = meta["feature_len"]
            c = self._agg_cost(name, lp, flen)
            return c["flops"], c["bytes"], 0.0, flen, 0.0, 0.0
        if name == "combine":
            dims = meta["dims"]
            c = combine_cost(v, dims)
            return c["flops"], c["bytes"], 0.0, dims[-1], 0.0, 0.0
        if name == "fused_agg_combine":
            # aggregate + first matmul in one tile: the (V, din) intermediate
            # never round-trips HBM, so its write+read bytes are subtracted.
            din, dout = meta["dims"]
            agg = self._agg_cost(name, lp, din)
            comb = combine_cost(v, (din, dout))
            saved = 2 * v * din * _DTYPE_BYTES
            byt = max(agg["bytes"] + comb["bytes"] - saved, 1)
            return agg["flops"] + comb["flops"], byt, 0.0, din, 0.0, 0.0
        if name == "distributed":
            # the layer behind shard_map (its combine too, unless the
            # dispatch records that as a phase of its own: GIN's MLP);
            # collective term from the halo model at the feature length
            # the exchange actually moves, and the exposed/overlapped
            # wall-time split from the overlap model priced for the halo
            # schedule the dispatch passed along.
            flen = meta["feature_len"]
            agg = aggregate_cost(g, flen, include_self=lp.include_self)
            comb = combine_cost(v, lp.dims if meta.get("combine", True)
                                else ())
            coll = self._halo_bytes(flen)
            exp_s, ovl_s = self._overlap_times(
                flen, meta.get("overlap",
                               getattr(self.plan, "overlap", "none")))
            return (agg["flops"] + comb["flops"],
                    agg["bytes"] + comb["bytes"], coll, flen, exp_s, ovl_s)
        raise ValueError(f"unknown phase {name!r}")

    def _halo_bytes(self, feature_len: int) -> float:
        from repro.core.distributed import halo_bytes, halo_bytes_2d
        from repro.profile.machine import DTYPE_BYTES
        if self.plan.partition_kind == "2d":
            base = float(halo_bytes_2d(self.plan.partition,
                                       feature_len)["min_halo_bytes"])
        elif self.plan.partition_kind == "1d":
            base = float(halo_bytes(self.plan.partition,
                                    feature_len)["min_halo_bytes"])
        else:
            return 0.0
        # the halo model counts f32 elements; a reduced-precision plan
        # exchanges the wire slab at its storage width, so the collective
        # bytes scale by the dtype's element size (bf16 = exactly half f32)
        pd = getattr(self.plan, "dtype", "f32")
        return base * DTYPE_BYTES.get(pd, 4) / 4.0

    def _wire_bytes(self, lp, feature_len: int, meta) -> float:
        """Schedule-exact per-device collective bytes of this layer's
        traced halo program (``schedule_wire_bytes``) -- the side of the
        accounting the static analyzer equates to jaxpr extraction."""
        from repro.core.distributed import schedule_wire_bytes
        kind = self.plan.partition_kind
        if kind == "none":
            return 0.0
        acc = schedule_wire_bytes(
            self.plan.partition, int(feature_len),
            strategy=getattr(self.plan, "strategy", "ring"),
            overlap=meta.get("overlap",
                             getattr(self.plan, "overlap", "none")),
            dtype=getattr(self.plan, "dtype", "f32"),
            combine_out_len=lp.dout if kind == "2d" else None)
        return float(acc["total_bytes"])

    def _overlap_times(self, feature_len: int, overlap: str):
        """(exposed_s, overlapped_s) collective wall-time split for one
        distributed layer, from the same ``overlap_model`` pricing that
        ``choose_overlap`` applies -- analytic, so eager and compiled runs
        of one plan report the identical split.  ``overlap="pipelined"``
        moves the hidden share of each hop's wire time into the overlapped
        column; ``"none"`` leaves every hop fully exposed."""
        from repro.core.distributed import overlap_model
        kind = self.plan.partition_kind
        if kind == "2d":
            p2 = self.plan.partition
            pg, flen = p2.nodes, p2.feature_block(feature_len)
        elif kind == "1d":
            pg, flen = self.plan.partition, feature_len
        else:
            return 0.0, 0.0
        m = overlap_model(pg, flen, self.machine,
                          strategy=getattr(self.plan, "strategy", "ring"))
        if overlap == "pipelined":
            return (float(m["exposed_pipelined_s"]),
                    float(m["overlapped_pipelined_s"]))
        return float(m["exposed_none_s"]), 0.0


# ---------------------------------------------------------------------------
# WorkloadReport
# ---------------------------------------------------------------------------


_FIELD_TYPES = {
    "layer": int, "phase": str, "order": str, "backend": str, "fused": bool,
    "feature_len": int, "flops": (int, float), "bytes": (int, float),
    "arithmetic_intensity": (int, float), "collective_bytes": (int, float),
    "exposed_collective_time": (int, float),
    "overlapped_collective_time": (int, float),
    "wall_time_s": (int, float), "bound": str,
    "dtype": str, "quant_error": (int, float),
    "wire_collective_bytes": (int, float),
    "dedup_pairs": int, "dedup_flops_saved": (int, float),
}


def validate_report_dict(d: Dict[str, Any]) -> List[str]:
    """Structural validation of a report in dict form; returns problems.

    Works on freshly rendered ``to_dict()`` output AND on deserialized
    ``to_json()`` artifacts -- the totals-vs-phases cross-check is only
    meaningful for the latter (a live report recomputes totals from its
    records, a JSON file can be edited or truncated independently).
    """
    problems: List[str] = []
    if d.get("schema") != SCHEMA or d.get("version") != SCHEMA_VERSION:
        problems.append("schema header mismatch")
    phases_list = d.get("phases", [])
    if not phases_list:
        problems.append("empty phase records")
    for i, rec in enumerate(phases_list):
        for k, t in _FIELD_TYPES.items():
            if k not in rec:
                problems.append(f"phases[{i}]: missing field {k!r}")
            elif not isinstance(rec[k], t) or isinstance(rec[k], bool) \
                    and t is not bool:
                problems.append(
                    f"phases[{i}].{k}: bad type {type(rec[k]).__name__}")
        if rec.get("phase") not in PHASES:
            problems.append(f"phases[{i}]: unknown phase "
                            f"{rec.get('phase')!r}")
        if rec.get("bound") not in ("memory", "compute"):
            problems.append(f"phases[{i}]: bad bound {rec.get('bound')!r}")
        if rec.get("dtype") not in ("f32", "bf16", "int8-agg"):
            problems.append(f"phases[{i}]: bad dtype {rec.get('dtype')!r}")
        for k in ("flops", "bytes", "collective_bytes", "wall_time_s",
                  "exposed_collective_time", "overlapped_collective_time",
                  "quant_error", "wire_collective_bytes"):
            if isinstance(rec.get(k), (int, float)) and rec[k] < 0:
                problems.append(f"phases[{i}].{k}: negative")
        if rec.get("dtype") == "f32" and \
                isinstance(rec.get("quant_error"), (int, float)) and \
                rec["quant_error"] != 0:
            problems.append(
                f"phases[{i}].quant_error: nonzero on an f32 record "
                "(the bitwise-golden contract forbids rounding)")
        if rec.get("phase") != "distributed":
            for k in ("exposed_collective_time",
                      "overlapped_collective_time",
                      "wire_collective_bytes"):
                if isinstance(rec.get(k), (int, float)) and rec[k] != 0:
                    problems.append(
                        f"phases[{i}].{k}: nonzero on non-distributed phase")
        if rec.get("phase") not in ("aggregate", "fused_agg_combine"):
            for k in ("dedup_pairs", "dedup_flops_saved"):
                if isinstance(rec.get(k), (int, float)) and rec[k] != 0:
                    problems.append(
                        f"phases[{i}].{k}: nonzero on non-aggregation phase")
    # a plan that RESOLVED to dedup="pairs" proved matchable pairs exist at
    # build time (zero-match graphs coerce back to "none"), so a report
    # whose aggregation records all carry dedup_pairs == 0 means the
    # two-level dispatch silently did not run
    layer_descr = (d.get("plan") or {}).get("layers", [])
    if any(ld.get("dedup") == "pairs" for ld in layer_descr
           if isinstance(ld, dict)):
        agg_recs = [rec for rec in phases_list
                    if rec.get("phase") in ("aggregate",
                                            "fused_agg_combine")]
        if agg_recs and not any(
                isinstance(rec.get("dedup_pairs"), int)
                and rec["dedup_pairs"] > 0 for rec in agg_recs):
            problems.append(
                "dedup='pairs' plan with dedup_pairs == 0 on every "
                "aggregation record (matching was possible -- the plan "
                "resolved to 'pairs' -- but the two-level path did not "
                "dispatch)")
    reduced = [rec for rec in phases_list
               if rec.get("dtype") in ("bf16", "int8-agg")]
    if reduced and not any(
            isinstance(rec.get("quant_error"), (int, float))
            and rec["quant_error"] > 0 for rec in reduced):
        problems.append(
            "reduced-dtype report with quant_error == 0 everywhere "
            "(rounding must be observed somewhere, or the reduced path "
            "silently did not run)")
    tot = d.get("totals", {})
    for k in ("flops", "bytes", "collective_bytes"):
        if k not in tot:
            problems.append(f"totals.{k}: missing")
            continue
        s = sum(r[k] for r in phases_list
                if isinstance(r.get(k), (int, float)))
        if abs(s - tot[k]) > 1e-6 * max(1.0, abs(s)):
            problems.append(f"totals.{k} != sum of phases")
    comp = d.get("compiled")
    if comp is not None:            # optional: compiled-timing reports only
        if not isinstance(comp.get("model_s"), (int, float)) \
                or comp["model_s"] < 0:
            problems.append("compiled.model_s: missing/negative")
        layers_s = comp.get("layers_s", [])
        if not isinstance(layers_s, list) or any(
                not isinstance(t, (int, float)) or t < 0 for t in layers_s):
            problems.append("compiled.layers_s: ill-typed")
    serving = d.get("serving")
    if serving is not None:         # optional: serving-session reports only
        problems += _validate_serving(serving)
    return problems


def _validate_serving(s: Dict[str, Any]) -> List[str]:
    """Schema checks for a report's ``serving`` section (the per-request
    latency/throughput view ``GraphServeEngine.workload_report`` attaches):
    required counters present, non-negative, percentiles monotone."""
    problems: List[str] = []
    for k in ("requests", "bucket_misses", "retraces"):
        v = s.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"serving.{k}: missing/negative")
    for k in ("p50_ms", "p95_ms", "p99_ms", "throughput_rps"):
        v = s.get(k)
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
            problems.append(f"serving.{k}: missing/negative")
    pcts = [s.get(k) for k in ("p50_ms", "p95_ms", "p99_ms")]
    if all(isinstance(p, (int, float)) for p in pcts) and \
            not (pcts[0] <= pcts[1] <= pcts[2]):
        problems.append("serving percentiles not monotone "
                        "(p50 <= p95 <= p99)")
    buckets = s.get("buckets")
    if not isinstance(buckets, list):
        problems.append("serving.buckets: missing")
    else:
        for i, b in enumerate(buckets):
            for k in ("num_seeds", "num_inputs", "num_edges", "hits"):
                v = b.get(k) if isinstance(b, dict) else None
                if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                    problems.append(
                        f"serving.buckets[{i}].{k}: missing/negative")
    return problems


@dataclass
class WorkloadReport:
    """Typed per-phase characterization of one instrumented forward pass.

    ``records`` are in execution order.  ``output`` carries the forward
    result (so ``plan.instrument(...).run_model(...)`` is one call that
    yields BOTH the model output and the report); it is excluded from
    ``to_dict``/``to_json``.
    """

    machine: Machine
    plan_summary: Dict[str, Any]
    records: List[PhaseRecord]
    output: Any = None
    #: compiled wall times when the run also measured ``plan.compile()``:
    #: {"model_s": float, "layers_s": [float per layer]} (None otherwise)
    compiled_times: Optional[Dict[str, Any]] = None
    #: whether the plan's ingress reorder permute was observed executing
    reorder_applied: bool = False
    #: serving-session stats when the report describes a serving workload
    #: (``GraphServeEngine.workload_report``): requests, p50/p95/p99 ms,
    #: throughput_rps, bucket_misses, retraces, per-bucket hit counts
    #: (None for plain characterization reports)
    serving: Optional[Dict[str, Any]] = None
    #: which instrumented entry produced the records ("model" sees the
    #: full ingress/egress path; "layer"/"phases" skip it)
    entry: str = "model"

    # -- aggregation ---------------------------------------------------------

    def totals(self) -> Dict[str, float]:
        """Summed FLOPs / bytes / collective bytes / wall time over phases."""
        return {
            "flops": sum(r.flops for r in self.records),
            "bytes": sum(r.bytes for r in self.records),
            "collective_bytes": sum(r.collective_bytes
                                    for r in self.records),
            "wall_time_s": sum(r.wall_time_s for r in self.records),
        }

    def layer_records(self, layer: int) -> List[PhaseRecord]:
        return [r for r in self.records if r.layer == layer]

    def eager_layer_time(self, layer: int) -> float:
        """Summed eager wall time of one layer's recorded phases."""
        return sum(r.wall_time_s for r in self.layer_records(layer))

    def compiled_speedup(self) -> Optional[Dict[str, Any]]:
        """Eager-vs-compiled speedups when compiled times were measured.

        Returns ``{"model": eager_total/compiled_model, "layers": [per
        layer]}`` -- the paper-style "how much does removing the eager
        dispatch + phase barriers buy" number -- or None for eager-only
        reports.  CPU-container caveat as everywhere in ``repro.profile``:
        wall times are correctness-shaped observables, not accelerator
        predictions.
        """
        ct = self.compiled_times
        if not ct:
            return None
        eager_total = sum(r.wall_time_s for r in self.records)
        layers = []
        for i, ls in enumerate(ct.get("layers_s", [])):
            layers.append(self.eager_layer_time(i) / max(ls, 1e-12))
        return {"model": eager_total / max(ct["model_s"], 1e-12),
                "layers": layers}

    # -- renderers -----------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        m = self.machine
        out = {
            "schema": SCHEMA,
            "version": SCHEMA_VERSION,
            "machine": {"name": m.name, "kind": m.kind,
                        "peak_flops": m.peak_flops, "hbm_bw": m.hbm_bw,
                        "balance": m.balance},
            "plan": dict(self.plan_summary),
            "phases": [r.to_dict() for r in self.records],
            "totals": self.totals(),
        }
        if self.compiled_times is not None:
            out["compiled"] = {**self.compiled_times,
                               "speedup": self.compiled_speedup()}
        if self.serving is not None:
            out["serving"] = dict(self.serving)
        return out

    def to_json(self, indent: int = 2) -> str:
        """Stable JSON rendering (sorted keys) of ``to_dict``."""
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    def to_markdown(self) -> str:
        """Paper-style per-phase breakdown table (Tables 3/4 in one view)."""
        m = self.machine
        tot = self.totals()
        t_all = max(tot["wall_time_s"], 1e-12)
        lines = [
            f"## Workload report — {m.name}",
            "",
            f"Machine: {m.name} ({m.kind}): peak "
            f"{m.peak_flops / 1e12:.1f} TFLOP/s, HBM "
            f"{m.hbm_bw / 1e9:.0f} GB/s, balance {m.balance:.1f} FLOP/B",
            f"Plan: {self.plan_summary.get('num_layers', '?')} layer(s), "
            f"partition={self.plan_summary.get('partition', 'none')}, "
            f"interpret={self.plan_summary.get('interpret')}",
            "",
            "| layer | phase | order | backend | FLOPs | bytes | AI (F/B) "
            "| bound | collective B | time (us) | time % |",
            "|---|---|---|---|---|---|---|---|---|---|---|",
        ]
        for r in self.records:
            lines.append(
                f"| {r.layer} | {r.phase} | {r.order} | {r.backend} | "
                f"{r.flops:.3e} | {r.bytes:.3e} | "
                f"{r.arithmetic_intensity:.2f} | {r.bound} | "
                f"{r.collective_bytes:.3g} | {r.wall_time_s * 1e6:.1f} | "
                f"{100 * r.wall_time_s / t_all:.1f} |")
        lines.append(
            f"| total |  |  |  | {tot['flops']:.3e} | {tot['bytes']:.3e} | "
            f"{tot['flops'] / max(1.0, tot['bytes']):.2f} |  | "
            f"{tot['collective_bytes']:.3g} | "
            f"{tot['wall_time_s'] * 1e6:.1f} | 100.0 |")
        ded = [r for r in self.records if r.dedup_pairs > 0]
        if ded:
            saved = sum(r.dedup_flops_saved for r in ded)
            naive = saved + tot["flops"]
            lines += [
                "",
                f"Dedup: {ded[0].dedup_pairs} matched pairs — "
                f"{saved:.3e} aggregation FLOPs eliminated "
                f"({100 * saved / max(naive, 1e-12):.1f}% of the naive "
                "fold's total)",
            ]
        exp = sum(r.exposed_collective_time for r in self.records)
        ovl = sum(r.overlapped_collective_time for r in self.records)
        if exp or ovl:
            lines += [
                "",
                f"Collective: {exp * 1e6:.1f} us exposed, "
                f"{ovl * 1e6:.1f} us overlapped "
                f"({100 * ovl / max(exp + ovl, 1e-12):.0f}% hidden behind "
                "the combine GEMM)",
            ]
        if self.serving is not None:
            s = self.serving
            lines += [
                "",
                f"Serving: {s['requests']} requests at "
                f"{s['throughput_rps']:.1f} req/s — p50 {s['p50_ms']:.2f} ms"
                f", p95 {s['p95_ms']:.2f} ms, p99 {s['p99_ms']:.2f} ms "
                f"({s['bucket_misses']} bucket misses, "
                f"{s['retraces']} retraces)",
            ]
        sp = self.compiled_speedup()
        if sp is not None:
            ct = self.compiled_times
            per_layer = ", ".join(
                f"layer {i}: {s:.2f}x" for i, s in enumerate(sp["layers"]))
            lines += [
                "",
                f"Compiled (plan.compile): {ct['model_s'] * 1e6:.1f} us vs "
                f"eager {t_all * 1e6:.1f} us — {sp['model']:.2f}x"
                + (f" ({per_layer})" if per_layer else ""),
            ]
        return "\n".join(lines)

    # -- validation ----------------------------------------------------------

    def validate(self) -> "WorkloadReport":
        """Raise ``WorkloadReportError`` on schema violations.

        Checked (``validate_report_dict``): non-empty phase records, every
        record field present with the right type, phase/bound vocabulary,
        non-negative costs, totals consistent with the records.  Returns
        self so call sites can chain
        (``plan.instrument().run_model(p, x).validate()``).
        """
        problems = validate_report_dict(self.to_dict())
        if problems:
            raise WorkloadReportError(
                "WorkloadReport schema violations: " + "; ".join(problems))
        return self

    def mismatches(self, plan) -> List[str]:
        """Cross-check ``plan.describe()`` against the dispatched phases.

        What is genuinely *observed* (not copied from the plan) and
        therefore guarded: the executed phase sequence (ordering -- the
        combine/aggregate records are appended in execution order),
        whether the fused path actually ran (``run_phases`` with an inline
        bias may legitimately fall back at call time -- that fallback is
        exactly the drift this reports; model-path plans must always come
        back clean), the call-time backend *resolution* (a plan
        storing an unresolved "auto" disagrees with what
        dispatch resolves), whether the planned ``reorder`` permute
        actually ran at ingress (observed only by ``run_model`` -- the
        entry that owns ingress/egress), the storage ``dtype`` each phase
        record carries (must equal describe()'s planned dtype, except
        combine under ``"int8-agg"`` which stays ``"f32"`` -- only the
        aggregation operand is quantized), the halo ``overlap`` schedule the
        distributed dispatch actually priced (a record with overlapped
        collective time on a plan describing ``overlap="none"`` -- or the
        reverse -- is describe-vs-dispatch drift), and the ``compiled``
        capability
        (a report carrying compiled times contradicts a describe() that
        claims ``plan.compile()`` is unsupported).  Kernel-entry tier
        selection below this layer is covered by tests/test_plan.py's
        mocked-platform tests, not here.  Empty list == describe() is
        truthful.
        """
        out: List[str] = []
        for d in plan.describe():
            if self.entry == "model" and "reorder" in d:
                observed_reorder = "degree" if self.reorder_applied \
                    else "none"
                if d["reorder"] != observed_reorder:
                    out.append(
                        f"layer {d['layer']}: describe reorder="
                        f"{d['reorder']} but ingress observed "
                        f"{observed_reorder}")
            if self.compiled_times is not None and \
                    d.get("compiled") is False:
                out.append(f"layer {d['layer']}: describe compiled=False "
                           "but a compiled run was measured")
            recs = self.layer_records(d["layer"])
            if not recs:
                continue
            seq = [r.phase for r in recs]
            fused_ran = "fused_agg_combine" in seq
            if bool(d["fused"]) != fused_ran:
                out.append(f"layer {d['layer']}: describe fused={d['fused']} "
                           f"but executed phases {seq}")
            if "dtype" in d:
                for r in recs:
                    want = "f32" if (d["dtype"] == "int8-agg"
                                     and r.phase == "combine") else d["dtype"]
                    if r.dtype != want:
                        out.append(
                            f"layer {d['layer']}: describe dtype="
                            f"{d['dtype']} but {r.phase} record carries "
                            f"{r.dtype}")
            agg = [r for r in recs
                   if r.phase in ("aggregate", "fused_agg_combine",
                                  "distributed")]
            if "dedup" in d:
                for r in recs:
                    if r.phase not in ("aggregate", "fused_agg_combine"):
                        continue
                    observed_dd = "pairs" if r.dedup_pairs > 0 else "none"
                    if d["dedup"] != observed_dd:
                        out.append(
                            f"layer {d['layer']}: describe dedup="
                            f"{d['dedup']} but {r.phase} record carries "
                            f"dedup_pairs={r.dedup_pairs}")
            for r in agg:
                if r.backend != d["backend"]:
                    out.append(f"layer {d['layer']}: describe backend="
                               f"{d['backend']} but {r.phase} used "
                               f"{r.backend}")
            dist = [r for r in recs if r.phase == "distributed"]
            if "overlap" in d:
                for r in dist:
                    if r.exposed_collective_time == 0 and \
                            r.overlapped_collective_time == 0:
                        continue   # single shard: nothing moves, no signal
                    observed_ov = ("pipelined"
                                   if r.overlapped_collective_time > 0
                                   else "none")
                    if d["overlap"] != observed_ov:
                        out.append(
                            f"layer {d['layer']}: describe overlap="
                            f"{d['overlap']} but probe recorded "
                            f"{observed_ov} collective split")
            if not fused_ran and "aggregate" in seq and "combine" in seq:
                observed = ("combine_first"
                            if seq.index("combine") < seq.index("aggregate")
                            else "aggregate_first")
                if observed != d["order"]:
                    out.append(f"layer {d['layer']}: describe order="
                               f"{d['order']} but executed {seq}")
        return out


# ---------------------------------------------------------------------------
# InstrumentedPlan
# ---------------------------------------------------------------------------


class InstrumentedPlan:
    """A ``GraphExecutionPlan`` whose runs yield ``WorkloadReport``s.

    Built by ``plan.instrument(machine=...)``; ``machine`` defaults to the
    plan's own (``build_plan(..., machine=)``) or the first layer backend's
    natural preset.  Each ``run_*`` executes the plan's REAL dispatch path
    eagerly (per-phase wall times need phase boundaries, so no whole-model
    jit) and returns a fresh report whose ``.output`` is the forward result.
    """

    def __init__(self, plan, machine: Optional[Machine] = None,
                 warmup: int = 0):
        self.plan = plan
        self.machine = machine or getattr(plan, "machine", None) or \
            machine_for_backend(plan.layers[0].backend)
        self.warmup = warmup

    def _summary(self) -> Dict[str, Any]:
        p = self.plan
        return {
            "num_layers": p.num_layers,
            "partition": p.partition_kind,
            "interpret": p.interpret,
            "layers": p.describe(),
        }

    def _report(self, probe: _Probe, out, entry: str) -> WorkloadReport:
        return WorkloadReport(machine=self.machine,
                              plan_summary=self._summary(),
                              records=probe.records, output=out,
                              reorder_applied=probe.reorder_applied,
                              entry=entry)

    @staticmethod
    def _time(fn, *args) -> float:
        """Median wall seconds of ``fn(*args)`` via the ONE shared timing
        harness (``repro.profile.bench.timeit``, warmup absorbs the jit
        trace/compile) -- compiled and bench numbers share a protocol."""
        from repro.profile.bench import timeit
        return timeit(fn, *args, warmup=1, iters=3) / 1e6

    def _compiled_times(self, params, x) -> Dict[str, Any]:
        """Wall times of ``plan.compile()`` -- the whole forward plus each
        planned layer compiled standalone (``plan.compile(layer=i)``), so
        the report can state eager-vs-compiled speedup per layer.  The
        replay walks the same ingress/layer/ReLU sequence ``run_model``
        executes, in the plan's execution layout."""
        plan = self.plan
        model_s = self._time(plan.compile(), params, x)
        layers_s = []
        h = plan._ingress(x)
        for i in range(plan.num_layers):
            sub = params[f"conv{i}"]
            fl = plan.compile(layer=i)
            layers_s.append(self._time(fl, sub, h))
            h = fl(sub, h)
            if i < plan.num_layers - 1:
                h = jax.nn.relu(h)
        return {"model_s": model_s, "layers_s": layers_s}

    def run_model(self, params, x, *, compiled: bool = False
                  ) -> WorkloadReport:
        """Instrumented full forward; returns the WorkloadReport (the model
        output rides along as ``report.output``).

        ``compiled=True`` additionally measures the ``plan.compile()`` path
        (whole model and per layer) and attaches the wall times as
        ``report.compiled_times`` -- ``report.compiled_speedup()`` /
        ``to_markdown()`` then state the eager-vs-compiled speedup.  The
        eager per-phase records are unchanged: phase boundaries need eager
        dispatch, so the compiled executable is timed as a whole.
        """
        for _ in range(self.warmup):
            jax.block_until_ready(self.plan.run_model(params, x))
        probe = _Probe(self.plan, self.machine)
        out = self.plan.run_model(params, x, _probe=probe)
        report = self._report(probe, out, "model")
        if compiled:
            report.compiled_times = self._compiled_times(params, x)
        return report

    def run_layer(self, params, x, *, layer: int = 0) -> WorkloadReport:
        """Instrumented single layer (conv param subtree)."""
        probe = _Probe(self.plan, self.machine)
        out = self.plan.run_layer(params, x, layer=layer, _probe=probe)
        return self._report(probe, out, "layer")

    def run_phases(self, x, weights, **kw) -> WorkloadReport:
        """Instrumented raw weight-list layer (``plan.run_phases``)."""
        probe = _Probe(self.plan, self.machine)
        out = self.plan.run_phases(x, weights, _probe=probe, **kw)
        return self._report(probe, out, "phases")

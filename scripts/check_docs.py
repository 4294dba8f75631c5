#!/usr/bin/env python
"""Docs gate (scripts/smoke.sh step 3).

Fails (exit 1, listing every violation) unless:

  * README.md and docs/planner.md exist and are non-trivial,
  * every public planner-surface symbol has a real docstring,
  * the planner entry points' docstrings carry worked examples / the
    documented mesh contract (the pieces ISSUE reviews keep asking for).

Run from anywhere: ``python scripts/check_docs.py``.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

#: module path -> public symbols whose docstrings are part of the contract
PUBLIC_SURFACE = {
    "repro.core.plan": [
        "build_plan", "plan_for_conv", "plan_for_phases",
        "GraphExecutionPlan", "GraphExecutionPlan.run_model",
        "GraphExecutionPlan.run_layer", "GraphExecutionPlan.run_phases",
        "GraphExecutionPlan.describe", "GraphExecutionPlan.layer_costs",
        "GraphExecutionPlan.instrument", "GraphExecutionPlan.compile",
        "CompiledPlan", "plan_cache_stats", "clear_plan_cache",
    ],
    "repro.serve.core": [
        "SlotServeCore", "SlotServeCore.submit", "SlotServeCore.run",
        "SlotServeCore.stats",
    ],
    "repro.serve.graph_engine": [
        "GraphServeEngine", "GraphServeEngine.warmup",
        "GraphServeEngine.prepare", "GraphServeEngine.run_prepared",
        "GraphServeEngine.run_eager", "GraphServeEngine.select_bucket",
        "GraphServeEngine.workload_report", "GraphServeEngine.stats",
        "GraphRequest", "Bucket", "Bucket.fits", "default_buckets",
        "union_two_hop",
    ],
    "repro.graph.reorder": [
        "degree_reorder", "choose_reorder", "reuse_distance_stats",
    ],
    "repro.graph.dedup": [
        "DedupLayout", "DedupLayout.flops_saved", "build_dedup_layout",
        "dedup_layout_for_graph", "dedup_cost", "pad_dedup_arrays",
        "attach_blocked",
    ],
    "repro.models.sage_minibatch": [
        "PlannedSageTrainer", "PlannedSageTrainer.train",
        "PlannedSageTrainer.step", "PlannedSageTrainer.save",
        "PlannedSageTrainer.restore", "PlannedSageTrainer.predict",
        "train_minibatch_planned",
    ],
    "repro.kernels.ops": [
        "seg_agg_planned", "gather_seg_agg", "kernel_tile_e",
        "kernel_edges", "layout_counts", "fused_agg_combine",
    ],
    "repro.core.backend": ["resolve_backend", "default_interpret"],
    "repro.core.distributed": [
        "distributed_gcn_layer", "distributed_gcn_layer_2d",
        "pad_features_2d", "halo_bytes", "halo_bytes_2d",
        "overlap_model", "choose_overlap", "schedule_wire_bytes",
        "wire_dtype_bytes",
    ],
    "repro.graph.partition": [
        "partition_1d", "partition_2d", "Partition2D", "PartitionedGraph",
    ],
    "repro.core.dataflow": ["suggest_tile_m", "fused_gcn_layer"],
    "repro.core.phases": ["aggregate", "combine", "phase_ordered_layer"],
    "repro.profile.machine": [
        "Machine", "Machine.tile_budget", "Machine.classify",
        "Machine.hop_time", "Machine.matmul_peak", "get_machine",
        "machine_for_backend", "choose_dtype", "dtype_model",
        "choose_dedup", "dedup_model",
    ],
    "repro.profile.instrument": [
        "InstrumentedPlan", "InstrumentedPlan.run_model", "WorkloadReport",
        "WorkloadReport.to_json", "WorkloadReport.to_markdown",
        "WorkloadReport.validate", "WorkloadReport.mismatches",
        "PhaseRecord",
    ],
    "repro.profile.bench": [
        "BenchSpec", "BenchContext", "run_specs", "timeit", "write_csv",
        "bench_graph",
    ],
    "repro.analysis.report": [
        "Finding", "AnalysisReport", "AnalysisReport.add",
        "AnalysisReport.ok", "AnalysisReport.to_json",
        "AnalysisReport.to_markdown", "AnalysisReport.counts",
    ],
    "repro.analysis.jaxpr_lint": [
        "lint_plan", "lint_callable", "collective_bytes",
        "plan_expected_collectives", "check_donation", "iter_eqns",
    ],
    "repro.analysis.ast_lint": [
        "lint_tree", "lint_file", "lint_source",
    ],
    "repro.analysis.selftest": ["run_selftest", "check_suppression"],
}

#: docstring must contain these substrings (entry point -> requirements)
CONTENT_REQUIREMENTS = {
    ("repro.core.plan", "build_plan"): [">>>", "mesh", "num_shards",
                                        "reorder", "degree", "auto",
                                        "overlap", "pipelined", "dtype",
                                        "bf16", "dedup", "pairs",
                                        "dedup_pad"],
    ("repro.profile.machine", "choose_dtype"): [
        ">>>", "bf16", "native_bf16", "halo"],
    ("repro.profile.machine", "choose_dedup"): [
        ">>>", "pairs", "fanout", "Machine"],
    ("repro.core.distributed", "choose_overlap"): [
        "pipelined", "hop", "Machine", ">>>"],
    ("repro.core.distributed", "overlap_model"): [
        "exposed", "overlapped", "hop_time"],
    ("repro.core.plan", "plan_for_conv"): [">>>"],
    ("repro.core.plan", "plan_for_phases"): [">>>"],
    ("repro.core.backend", "resolve_backend"): ["auto", "xla",
                                                "pallas-tpu"],
    ("repro.core.plan", "GraphExecutionPlan.instrument"): [
        ">>>", "WorkloadReport", "machine"],
    ("repro.core.plan", "GraphExecutionPlan.compile"): [
        ">>>", "donate", "retrace", "layer", "dynamic"],
    ("repro.kernels.ops", "gather_seg_agg"): ["kernel_edges", "seg_agg"],
    ("repro.analysis.jaxpr_lint", "lint_plan"): [
        "eager", "compiled", "donate", "dynamic", "never execute"],
    ("repro.analysis.ast_lint", "lint_source"): ["pragma", "allow"],
    ("repro.core.distributed", "schedule_wire_bytes"): [
        "Schedule-exact", "ring", "overlap", "reduce_scatter",
        "wire_dtype_bytes"],
    ("repro.serve.graph_engine", "GraphServeEngine.warmup"): [
        "compile", "admission", "clear_plan_cache"],
}

REQUIRED_FILES = {
    ROOT / "README.md": ["Quickstart", "smoke.sh", "chip_smoke.py",
                         "JAX_PLATFORMS=cpu"],
    ROOT / "docs" / "planner.md": ["decision table", "pallas-tpu",
                                   "partition_2d", "characterization.md",
                                   "plan.compile", "reorder",
                                   "degree_reorder",
                                   "Overlapped halo execution",
                                   "choose_overlap", "pipelined",
                                   "double-buffered", "bench_overlap",
                                   "Reduced-precision execution",
                                   "choose_dtype", "int8-agg",
                                   "bench_dtype", "quant_error",
                                   "Redundancy-eliminated aggregation",
                                   "choose_dedup", "dedup_model",
                                   "DedupLayout", "two-level",
                                   "bench_dedup", "dedup_pairs"],
    ROOT / "docs" / "characterization.md": [
        "Machine", "TPU_V5E", "TPU_V5P", "A100", "H100", "V100",
        "WorkloadReport", "to_markdown", "BenchSpec", "instrument",
        "workload-report", "balance", "compiled", "hop_time",
        "link_latency_s", "exposed_collective_time",
        "overlapped_collective_time", "dtype", "dtype_model",
        "matmul_peak"],
    ROOT / "docs" / "serving.md": [
        "GraphServeEngine", "SlotServeCore", "bucket", "warmup",
        "clear_plan_cache", "plan_cache_stats", "dynamic", "retrace",
        "p50", "p99", "throughput", "bench_serve", "two_hop_batch",
        "bit-identical", "eviction"],
    ROOT / "docs" / "training.md": [
        "PlannedSageTrainer", "GraphPipeline", "Checkpointer",
        "dedup", "choose_dedup", "dedup_pad", "bucket", "retrace",
        "plan_cache_stats", "batch_at", "deterministic", "resume",
        "bitwise", "tolerance"],
    ROOT / "docs" / "analysis.md": [
        "no-callbacks", "no-f64", "bf16-f32-accum", "donation",
        "collective-bytes", "dynamic-edge-free", "dedup-accounting",
        "host-in-trace",
        "tracer-branch", "broadcast-div", "acc-dtype", "grid-arity",
        "allow(", "allow-file(", "--strict", "--selftest",
        "wire_collective_bytes", "schedule_wire_bytes",
        "plan_for_phases", "tf.aliasing_output", "planner.md"],
}

MIN_DOC_LEN = 40  # a one-word docstring is not documentation


def _resolve(mod, dotted: str):
    obj = mod
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


def main() -> int:
    import importlib

    problems = []
    for path, needles in REQUIRED_FILES.items():
        if not path.is_file():
            problems.append(f"missing file: {path.relative_to(ROOT)}")
            continue
        text = path.read_text()
        if len(text) < 500:
            problems.append(f"{path.relative_to(ROOT)}: suspiciously short")
        for needle in needles:
            if needle not in text:
                problems.append(
                    f"{path.relative_to(ROOT)}: must mention {needle!r}")

    for mod_name, symbols in PUBLIC_SURFACE.items():
        try:
            mod = importlib.import_module(mod_name)
        except Exception as e:  # noqa: BLE001
            problems.append(f"cannot import {mod_name}: {e}")
            continue
        if not (mod.__doc__ and len(mod.__doc__) >= MIN_DOC_LEN):
            problems.append(f"{mod_name}: missing module docstring")
        for name in symbols:
            try:
                obj = _resolve(mod, name)
            except AttributeError:
                problems.append(f"{mod_name}.{name}: symbol missing")
                continue
            doc = getattr(obj, "__doc__", None)
            if not (doc and len(doc.strip()) >= MIN_DOC_LEN):
                problems.append(f"{mod_name}.{name}: missing/trivial "
                                "docstring")

    for (mod_name, sym), needles in CONTENT_REQUIREMENTS.items():
        try:
            doc = _resolve(importlib.import_module(mod_name), sym).__doc__ \
                or ""
        except Exception:  # noqa: BLE001
            continue  # already reported above
        for needle in needles:
            if needle not in doc:
                problems.append(
                    f"{mod_name}.{sym}: docstring must contain {needle!r}")

    if problems:
        print("check_docs: FAILED")
        for p in problems:
            print(f"  - {p}")
        return 1
    n = sum(len(v) for v in PUBLIC_SURFACE.values())
    print(f"check_docs: OK ({len(REQUIRED_FILES)} docs, {n} public symbols)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

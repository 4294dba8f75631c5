"""Benchmark harness entry: one module per paper table/figure.

Every module is a list of declarative ``BenchSpec``s (``mod.SPECS``)
executed by the ONE shared harness (``repro.profile.bench.run_specs``),
which owns warmup/timing, the ``name,us_per_call,derived...`` stdout echo,
and a per-module CSV artifact under ``experiments/bench/`` (header row,
stable column order -- what ``experiments/make_tables.py::bench_tables``
reads instead of re-parsing stdout).

  bench_breakdown       Fig. 1  execution-time breakdown
  bench_agg_vs_pgr      Fig. 2  Aggregation vs PageRank + reorder guideline
  bench_phase_metrics   Fig. 2(f,g)/Table 3  hybrid patterns x Machines
  bench_ordering        Table 4 phase-ordering impact (+distributed halo)
  bench_feature_length  Fig. 5  input/output length sweeps
  bench_kernels         beyond-paper: Pallas kernels + fused dataflow
  bench_plan            planner sweep: backend x ordering x fusion scenarios
  bench_overlap         overlap x strategy x partition halo-pipelining matrix
  bench_serve           serving: GraphServeEngine offered-load latency sweep
  bench_dtype           dtype x feature_len precision matrix + choose_dtype flip
  bench_dedup           pair-redundancy elimination: dedup savings + choose_dedup flip
  roofline              deliverable (g): dry-run roofline table

Usage: PYTHONPATH=src python -m benchmarks.run [--dry-run] [module ...]

``--dry-run`` routes through the execution planner only: every scenario
plan is built, run INSTRUMENTED (a schema-validated ``WorkloadReport`` per
scenario -- empty phase records or describe()-vs-dispatch drift fail), and
validated on tiny graphs with no timing -- the pre-merge smoke check
(scripts/smoke.sh).  A selected module whose specs declare no dry-run
scenarios is a HARD failure: a scenario silently skipped here would merge
unvalidated.
"""

import sys
import traceback


def _run_module(name: str, mod, dry: bool) -> None:
    """Run one module's specs through the shared harness + its post hook."""
    from repro.profile.bench import BENCH_ARTIFACT_DIR, run_specs

    specs = getattr(mod, "SPECS", None)
    if not specs:
        raise RuntimeError(f"{name} declares no SPECS; its scenarios would "
                           "be silently skipped -- declare BenchSpecs")
    if dry and not any(s.dry == "run" for s in specs):
        raise RuntimeError(
            f"{name} has no dry-run-capable specs; its scenarios would be "
            "silently skipped -- mark specs dry='run' or drop it from the "
            "dry-run selection")
    rows = run_specs(
        specs, dry=dry,
        csv=BENCH_ARTIFACT_DIR / f"{name}{'.dry' if dry else ''}.csv")
    post = getattr(mod, "post_run", None)
    if post is not None:
        post(rows, dry=dry)


def main() -> None:
    argv = sys.argv[1:]
    dry = "--dry-run" in argv
    argv = [a for a in argv if a != "--dry-run"]

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_agg_vs_pgr, bench_breakdown, bench_dedup,
                            bench_dtype, bench_feature_length,
                            bench_kernels, bench_ordering, bench_overlap,
                            bench_phase_metrics, bench_plan, bench_serve,
                            roofline)
    modules = {
        "bench_breakdown": bench_breakdown,
        "bench_agg_vs_pgr": bench_agg_vs_pgr,
        "bench_phase_metrics": bench_phase_metrics,
        "bench_ordering": bench_ordering,
        "bench_feature_length": bench_feature_length,
        "bench_kernels": bench_kernels,
        "bench_plan": bench_plan,
        "bench_overlap": bench_overlap,
        "bench_serve": bench_serve,
        "bench_dtype": bench_dtype,
        "bench_dedup": bench_dedup,
        "roofline": roofline,
    }
    if dry:
        # bench_serve's dry sweep is the serving acceptance gate (bucket
        # misses, retraces, padded-vs-eager drift, empty serving stats),
        # bench_overlap's is the halo-pipelining gate (bitwise
        # pipelined==none, compiled contract, modeled-time ordering), and
        # bench_dtype's is the precision gate (f32 bitwise under compile,
        # reduced dtypes banded, choose_dtype preset flip, bf16 halo
        # halving), and bench_dedup's is the redundancy-elimination gate
        # (zero matched pairs on a fanout-regular block, an analytic
        # aggregation-FLOP reduction under the floor, f32 drift from the
        # naive plan, or a missing choose_dedup workload flip hard-fail)
        # -- all hard-fail the smoke check alongside the planner matrix.
        selected = argv or ["bench_plan", "bench_overlap", "bench_serve",
                            "bench_dtype", "bench_dedup"]
    else:
        selected = argv or list(modules)

    failures = 0
    for name in selected:
        print(f"# === {name}{' (dry)' if dry else ''} ===")
        try:
            _run_module(name, modules[name], dry)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        raise SystemExit(
            f"{failures} benchmark module(s) failed"
            + (" (dry-run)" if dry else ""))


if __name__ == '__main__':
    main()

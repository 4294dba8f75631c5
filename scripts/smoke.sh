#!/usr/bin/env bash
# Pre-merge smoke check (the documented gate for every PR):
#   1. tier-1 pytest (ROADMAP.md "Tier-1 verify"),
#   2. the benchmark harness dry-run, which builds + validates the full
#      backend x ordering x fusion x reorder x partition (1-D and 2-D)
#      matrix through the GraphExecutionPlan -- every scenario runs
#      INSTRUMENTED and emits a WorkloadReport that is schema-validated
#      (empty phase records or violations fail) and cross-checked against
#      plan.describe() (planner drift fails), every scenario ALSO checks
#      the compiled contract (plan.compile() output bit-for-bit equal to
#      eager dispatch, no retrace on the second call), the plan/compiled
#      cells land the eager-vs-compiled speedup CSV under
#      experiments/bench/ -- and the run FAILS if any scenario in the
#      matrix is skipped without a logged reason.  The dry run ALSO runs
#      the halo-overlap matrix (bench_overlap: overlap x strategy x
#      partition on 8 fake devices -- HARD-FAILS if any overlap cell is
#      silently skipped, if the pipelined schedule's output differs by a
#      single bit from the single-buffered one eager or compiled, or if
#      the modeled pipelined time exceeds the single-buffered model) and
#      drains the GraphServeEngine offered-load sweep (bench_serve):
#      every closed-loop level AND the open-loop Poisson points warm up
#      the bucket ladder, serve the synthetic workload, and HARD-FAIL on
#      bucket misses, retraces after warmup(), empty serving stats, or
#      padded-vs-eager bit drift (docs/serving.md).  The dry run ALSO
#      sweeps the dtype x feature_len precision matrix (bench_dtype):
#      every cell builds through build_plan(dtype=...) and HARD-FAILS if
#      the f32 plan is not bitwise-identical under plan.compile(), if a
#      reduced-precision (bf16 / int8-agg) cell drifts outside the ONE
#      shared tolerance band or silently runs f32 (no observed
#      quant_error), if choose_dtype fails to flip between the V100 and
#      TPU_V5E presets, if the instrumented bf16 halo bytes are not
#      EXACTLY half of f32's on 8 fake devices, or if any dtype cell is
#      skipped without a logged reason.  The dry run ALSO gates the
#      pair-redundancy elimination (bench_dedup): the fanout-regular
#      sampled block HARD-FAILS on zero matched pairs, on an analytic
#      aggregation-FLOP reduction below the 20% floor, on any f32 bit
#      drift between the dedup='pairs' plan (eager or compiled) and the
#      naive plan, on instrumented aggregation records missing their
#      dedup_pairs counts, or if choose_dedup fails to flip between the
#      fanout-regular block ('pairs') and the sparse full-graph layer
#      ('none') on the same machine preset,
#   3. the docs gate (README + docs/planner.md + docs/characterization.md
#      + docs/serving.md + docs/analysis.md exist, public
#      planner/profile/serving/analysis symbols documented --
#      scripts/check_docs.py),
#   4. the static analysis gate (scripts/analyze.py): --strict traces the
#      full backend x fusion x partition x dtype x overlap plan matrix to
#      jaxprs + lowered HLO WITHOUT executing and hard-fails on any
#      error-severity contract violation (host callbacks, f64, bf16
#      accumulation, missing donation markers, collective byte totals
#      that disagree with schedule_wire_bytes, edge-content leaking into
#      dynamic bucket plans, plus the AST rules over src/repro/);
#      --selftest then seeds one known violation per rule and hard-fails
#      if ANY rule misses its plant (docs/analysis.md).
#
# Usage: scripts/smoke.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
JAX_PLATFORMS=cpu python -m pytest -x -q "$@"

echo "== planner + overlap + serving + dtype dry-run (backend x ordering x"
echo "   fusion x reorder x partition; instrumented: one schema-validated"
echo "   WorkloadReport per scenario, compiled contract: bitwise eager"
echo "   equality + no retrace; overlap matrix: silently skipped overlap"
echo "   cells or a compiled-bitwise/pipelined-schedule break hard-fail;"
echo "   serving: bucketed offered-load drain, closed- and open-loop --"
echo "   bucket misses, retraces, or empty serving stats hard-fail;"
echo "   dtype matrix: f32 bitwise drift, band violations, a missing"
echo "   choose_dtype preset flip, or non-halved bf16 halo bytes"
echo "   hard-fail; dedup matrix: zero matched pairs on the fanout-"
echo "   regular block, an unreduced analytic aggregation-FLOP count,"
echo "   f32 drift from the naive plan, or a missing choose_dedup"
echo "   workload flip hard-fail) =="
python -m benchmarks.run --dry-run

echo "== docs gate =="
python scripts/check_docs.py

echo "== static analysis gate (plan matrix -> jaxpr/HLO, no execution;"
echo "   then the rule self-test: every rule must catch its plant) =="
python scripts/analyze.py --strict
python scripts/analyze.py --selftest

echo "smoke: OK"

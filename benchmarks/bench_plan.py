"""Planner sweep: ONE harness comparing backend x ordering x fusion x
reorder x partition, eager AND compiled.

Every scenario is expressed as a ``build_plan`` override, so this module
exercises exactly the dispatch layer production code uses -- no hand-built
kernel calls.  One row per scenario carries the plan's decisions
(order/RESOLVED backend/tile_m/interpret/reorder) plus measured wall-clock,
and one row per model shows the decisions the planner takes when left on
"auto".  The ``plan/compiled`` spec times ``plan.compile()`` against the
eager dispatch loop and lands an eager-vs-compiled wall-time CSV
(``experiments/bench/bench_plan_compiled*.csv``).

Under dry-run (the ``benchmarks/run.py --dry-run`` path / scripts/smoke.sh)
every scenario additionally runs INSTRUMENTED: the plan executes through
``plan.instrument(machine=...)``, and the resulting ``WorkloadReport`` is
schema-validated (``report.validate()``) and cross-checked against
``plan.describe()`` (``report.mismatches``) -- empty phase records, schema
violations, or planner drift all fail the smoke gate.  Every matrix
scenario ALSO validates the compiled contract: ``plan.compile()`` output
must equal the eager forward bit-for-bit and the second invocation must
not retrace.  ``post_run`` accounts for every scenario in the matrix:
anything skipped is reported with a reason, and a scenario missing
without one raises.

The partition scenarios (1-D and 2-D meshes, including a degree-reordered
variant of each kind) run in a subprocess with 8 fake host devices so the
main process keeps its single real device (the same rule
tests/test_distributed.py follows); the child validates a WorkloadReport
AND the compiled bitwise/retrace contract per partition scenario too.

A backend is only *natively* exercised on its own platform; everywhere else
the Pallas tiers run in interpret mode.  The dry run prints exactly which
tiers were compiled vs interpreted so a GPU-less container can no longer
silently validate nothing but XLA paths.
"""

from __future__ import annotations

import itertools
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro.core.backend import default_interpret, platform
from repro.core.plan import build_plan
from repro.core.scheduler import AGGREGATE_FIRST, COMBINE_FIRST
from repro.launch.mesh import make_mesh
from repro.models.gcn import make_paper_model
from repro.profile.bench import BenchSpec, run_specs
from repro.profile.machine import TPU_V5E

BACKENDS = ("xla", "pallas-tpu")
ORDERINGS = (None, COMBINE_FIRST, AGGREGATE_FIRST)  # None = cost model
FUSION = (False, True)

#: local matrix cells: (backend, ordering, fused, reorder) -- the full
#: backend x ordering x fusion product at reorder="none" (the PR 3 matrix)
#: plus every backend x fusion cell under degree reordering and one
#: "auto" reorder cell exercising the choose_reorder pricing path.
MATRIX_POINTS = tuple(
    (b, o, f, "none")
    for b, o, f in itertools.product(BACKENDS, ORDERINGS, FUSION)
) + tuple(
    (b, None, f, "degree")
    for b, f in itertools.product(BACKENDS, FUSION)
) + (("xla", None, False, "auto"),)

#: eager-vs-compiled timing cells: (backend, fused, reorder)
COMPILED_POINTS = (
    ("xla", False, "none"),
    ("xla", True, "none"),
    ("xla", False, "degree"),
)

#: (kind, mesh shape, mesh axis names, halo strategy, reorder) --
#: subprocess matrix (one degree-reordered variant per partition kind)
PARTITIONS = (
    ("1d", (8,), ("data",), "ring", "none"),
    ("1d", (8,), ("data",), "allgather", "none"),
    ("2d", (4, 2), ("node", "feat"), "ring", "none"),
    ("2d", (4, 2), ("node", "feat"), "allgather", "none"),
    ("2d", (2, 4), ("node", "feat"), "ring", "none"),
    ("1d", (8,), ("data",), "ring", "degree"),
    ("2d", (4, 2), ("node", "feat"), "ring", "degree"),
)


def _scenario_name(backend, ordering, fused, reorder="none"):
    base = (f"plan/gcn/{backend}/{ordering or 'auto'}/"
            f"{'fused' if fused else 'unfused'}")
    return base if reorder == "none" else f"{base}/reorder-{reorder}"


def _partition_name(kind, shape, strategy, reorder="none"):
    base = (f"plan/gcn/partition-{kind}/{'x'.join(map(str, shape))}/"
            f"{strategy}")
    return base if reorder == "none" else f"{base}/reorder-{reorder}"


def _compiled_name(backend, fused, reorder):
    return (f"plan/compiled/gcn/{backend}/"
            f"{'fused' if fused else 'unfused'}/{reorder}")


def expected_matrix():
    """Every scenario name the dry run must account for."""
    names = [_scenario_name(*pt) for pt in MATRIX_POINTS]
    names += [_partition_name(k, s, st, r) for k, s, _, st, r in PARTITIONS]
    names += [_compiled_name(*pt) for pt in COMPILED_POINTS]
    return names


def _check_compiled_contract(name, plan, params, x, eager_out):
    """The plan.compile() acceptance contract, enforced per dry scenario:
    bit-for-bit equality with the eager forward and no retrace on the
    second invocation."""
    fn = plan.compile()
    out_c = fn(params, x)
    fn(params, x)
    if not np.array_equal(np.asarray(out_c), np.asarray(eager_out)):
        err = float(np.abs(np.asarray(out_c) -
                           np.asarray(eager_out)).max())
        raise RuntimeError(
            f"{name}: plan.compile() output differs from eager dispatch "
            f"(max |diff|={err:.3e}); the compiled contract is bitwise")
    if fn.num_traces != 1:
        raise RuntimeError(f"{name}: plan.compile() traced "
                           f"{fn.num_traces}x for one signature")


def _setup(ctx):
    m = make_paper_model("gcn", ctx.spec)
    return m, m.init(jax.random.PRNGKey(0))


def _scenario(ctx, point):
    """One (backend, ordering, fusion, reorder) cell of the local matrix."""
    backend, ordering, fused, reorder = point
    spec, g, x = ctx.spec, ctx.g, ctx.x
    m, params = ctx.state
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      backend=backend, ordering=ordering, fused=fused,
                      reorder=reorder)
    d0 = plan.describe()[0]
    derived = dict(order=d0["order"], backend=d0["backend"],
                   fused=d0["fused"], tile_m=d0["tile_m"],
                   interpret=d0["interpret"], reorder=d0["reorder"],
                   agg_bytes=d0["agg_bytes"])
    name = _scenario_name(backend, ordering, fused, reorder)
    if ctx.dry:
        # instrumented validation: run through the plan's real dispatch,
        # schema-check the WorkloadReport, and fail on planner drift
        report = plan.instrument(machine=ctx.machine).run_model(params, x)
        report.validate()
        drift = report.mismatches(plan)
        if drift:
            raise RuntimeError(
                f"{name}: describe() disagrees with dispatch: {drift}")
        assert report.output.shape == (spec.num_vertices, spec.num_classes)
        _check_compiled_contract(name, plan, params, x, report.output)
        ctx.emit(name, 0.0, report_phases=len(report.records), **derived)
    elif backend != "xla":
        # interpret-mode wall-clock is meaningless; describe only
        ctx.emit(name, 0.0, **derived)
    else:
        fn = plan.compile()
        ctx.emit(name, ctx.time(fn, params, x), **derived)


def _compiled(ctx, point):
    """Eager-vs-compiled wall time for one (backend, fused, reorder) cell.

    Timing mode: median wall time of the eager dispatch loop vs the
    ``plan.compile()`` executable.  Dry-run: the instrumented compiled run
    (``InstrumentedPlan.run_model(compiled=True)``) -- schema + drift +
    compiled-contract validation, with the measured (tiny-graph) times
    still emitted so the CSV artifact always carries a real speedup
    column.
    """
    backend, fused, reorder = point
    spec, g, x = ctx.spec, ctx.g, ctx.x
    m, params = ctx.state
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      backend=backend, fused=fused, reorder=reorder)
    name = _compiled_name(backend, fused, reorder)
    d0 = plan.describe()[0]
    derived = dict(backend=d0["backend"], fused=d0["fused"],
                   reorder=d0["reorder"])
    if ctx.dry:
        report = plan.instrument(machine=ctx.machine).run_model(
            params, x, compiled=True)
        report.validate()
        drift = report.mismatches(plan)
        if drift:
            raise RuntimeError(
                f"{name}: describe() disagrees with dispatch: {drift}")
        _check_compiled_contract(name, plan, params, x, report.output)
        eager_us = report.totals()["wall_time_s"] * 1e6
        compiled_us = report.compiled_times["model_s"] * 1e6
        ctx.emit(name, compiled_us, eager_us=round(eager_us, 2),
                 compiled_us=round(compiled_us, 2),
                 speedup=round(report.compiled_speedup()["model"], 3),
                 **derived)
    else:
        eager_us = ctx.time(plan.run_model, params, x)
        fn = plan.compile()
        compiled_us = ctx.time(fn, params, x)
        ctx.emit(name, compiled_us, eager_us=round(eager_us, 2),
                 compiled_us=round(compiled_us, 2),
                 speedup=round(eager_us / max(compiled_us, 1e-9), 3),
                 **derived)


def _auto_decisions(ctx, model_name):
    """What does the planner decide unaided, per paper model?"""
    spec, g = ctx.spec, ctx.g
    mm = make_paper_model(model_name, spec)
    plan = build_plan(g, mm.cfg, spec.feature_len, spec.num_classes)
    for d in plan.describe():
        ctx.emit(f"plan/auto/{model_name}/layer{d['layer']}", 0.0,
                 order=d["order"], backend=d["backend"], fused=d["fused"],
                 din=d["din"], dout=d["dout"], agg_bytes=d["agg_bytes"])


_PARTITION_CHILD_FLAG = "--partition-child"


def _partition_child(csv_out: str):
    """Subprocess body: validate every partition scenario on fake devices,
    each through an instrumented (WorkloadReport-validated) run PLUS the
    compiled contract (bitwise eager equality, no retrace).  Rows are
    written to ``csv_out`` so the parent re-emits them through its own
    harness context (they land in the parent's CSV artifact, no stdout
    re-parsing)."""
    from repro.profile.bench import BenchContext, bench_graph, write_csv
    from repro.graph.datasets import make_features, make_synthetic_graph

    spec = bench_graph("reddit", max_vertices=256, max_feature=64)
    g = make_synthetic_graph(spec)
    x = make_features(spec)
    m = make_paper_model("gcn", spec)
    params = m.init(jax.random.PRNGKey(0))
    ref = build_plan(g, m.cfg, spec.feature_len,
                     spec.num_classes).run_model(params, x)
    ctx = BenchContext(bench=None, machine=TPU_V5E, dry=True)
    for kind, shape, names, strategy, reorder in PARTITIONS:
        mesh = make_mesh(shape, names)
        plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                          mesh=mesh, strategy=strategy, reorder=reorder)
        assert plan.partition_kind == kind, (plan.partition_kind, kind)
        name = _partition_name(kind, shape, strategy, reorder)
        with mesh:
            report = plan.instrument(machine=TPU_V5E).run_model(params, x)
            report.validate()
            drift = report.mismatches(plan)
            assert not drift, (kind, shape, strategy, reorder, drift)
            _check_compiled_contract(name, plan, params, x, report.output)
        err = float(np.abs(np.asarray(report.output - ref)).max())
        assert err < 1e-3, (kind, shape, strategy, reorder, err)
        d0 = plan.describe()[0]
        ctx.emit(name, 0.0,
                 order=d0["order"], backend=d0["backend"],
                 partition=d0["partition"], reorder=d0["reorder"],
                 report_phases=len(report.records),
                 collective_bytes=int(sum(r.collective_bytes
                                          for r in report.records)),
                 max_err=f"{err:.2e}")
    write_csv(ctx.rows, csv_out)
    print("PARTITION-CHILD-OK")


def _partitions(ctx, _):
    """Spawn the partition matrix in a subprocess with 8 fake devices and
    re-emit its rows here, so they join the parent's CSV artifact and the
    matrix accounting.  Dry-run only: partition *timing* needs a real
    multi-device mesh (post_run logs that skip reason)."""
    if not ctx.dry:
        return
    import csv as _csv
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        out = Path(td) / "partition_child.csv"
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"   # a virtual-CPU matrix: never the TPU
        env["PYTHONPATH"] = os.pathsep.join(
            [str(Path(__file__).resolve().parents[1] / "src"),
             str(Path(__file__).resolve().parents[1])])
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_plan",
             _PARTITION_CHILD_FLAG, str(out)],
            capture_output=True, text=True, env=env, timeout=900)
        if res.returncode != 0 or "PARTITION-CHILD-OK" not in res.stdout:
            sys.stdout.write(res.stdout)
            raise RuntimeError(
                f"partition dry-run subprocess failed:\n{res.stderr[-3000:]}")
        with out.open(newline="") as f:
            child_rows = list(_csv.DictReader(f))
    for row in child_rows:
        name = row.pop("name")
        us = float(row.pop("us_per_call"))
        ctx.emit(name, us, **row)


SPECS = [
    BenchSpec(name="plan/matrix", graph="reddit", max_vertices=2048,
              max_feature=128, dry_max_vertices=256, machine=TPU_V5E,
              sweep=MATRIX_POINTS,
              setup=_setup, measure=_scenario, dry="run"),
    BenchSpec(name="plan/compiled", graph="reddit", max_vertices=2048,
              max_feature=128, dry_max_vertices=256, machine=TPU_V5E,
              sweep=COMPILED_POINTS, setup=_setup, measure=_compiled,
              dry="run"),
    BenchSpec(name="plan/auto", graph="reddit", max_vertices=2048,
              max_feature=128, dry_max_vertices=256,
              sweep=("gcn", "sage", "gin"), measure=_auto_decisions,
              dry="run"),
    BenchSpec(name="plan/partitions", measure=_partitions, dry="run"),
]


def post_run(rows, dry: bool = False):
    """Matrix accounting + backend coverage report (fails loudly on gaps),
    plus the eager-vs-compiled CSV artifact (``plan/compiled`` rows land
    in ``experiments/bench/bench_plan_compiled*.csv`` with eager_us /
    compiled_us / speedup columns).

    Only names in ``expected_matrix()`` count as validated scenarios (the
    ``plan/auto`` introspection rows are reported but not matrix cells).
    """
    from repro.profile.bench import BENCH_ARTIFACT_DIR, write_csv

    comp_rows = [r for r in rows if r["name"].startswith("plan/compiled/")]
    if comp_rows:
        p = write_csv(comp_rows, BENCH_ARTIFACT_DIR /
                      f"bench_plan_compiled{'.dry' if dry else ''}.csv")
        print(f"# eager-vs-compiled csv artifact: {p}")

    matrix = set(expected_matrix())
    validated = [r["name"] for r in rows if r["name"] in matrix]
    skipped = {}
    if not dry:
        for name in (_partition_name(k, s, st, r)
                     for k, s, _, st, r in PARTITIONS):
            skipped[name] = "partition timing needs a real multi-device mesh"

    plat = platform()
    compiled = [b for b in BACKENDS
                if b == "xla" or not default_interpret()]
    interp = [b for b in BACKENDS if b not in compiled]
    print(f"# backend coverage on platform={plat}: compiled natively: "
          f"{','.join(compiled)}; interpret-mode only (numerics validated, "
          f"perf NOT exercised): {','.join(interp) or 'none'}")
    for name, why in skipped.items():
        print(f"# skipped: {name} ({why})")
    missing = [n for n in expected_matrix()
               if n not in validated and n not in skipped]
    if missing:
        raise RuntimeError(
            "dry-run matrix scenarios silently skipped: " + ", ".join(missing))
    print(f"# matrix: {len(validated)} scenario(s) validated, "
          f"{len(skipped)} skipped with reasons, 0 silent")


def run(dry: bool = False):
    """Direct-invocation entry (``python -m benchmarks.bench_plan
    [--dry-run]``); writes the same CSV artifact benchmarks/run.py does."""
    from repro.profile.bench import BENCH_ARTIFACT_DIR
    rows = run_specs(
        SPECS, dry=dry,
        csv=BENCH_ARTIFACT_DIR / f"bench_plan{'.dry' if dry else ''}.csv")
    post_run(rows, dry=dry)


if __name__ == "__main__":
    if _PARTITION_CHILD_FLAG in sys.argv:
        _partition_child(sys.argv[sys.argv.index(_PARTITION_CHILD_FLAG) + 1])
    else:
        run(dry="--dry-run" in sys.argv)

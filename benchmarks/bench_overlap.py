"""Overlap sweep: overlap x strategy x partition for the distributed halo.

The tentpole measurement for overlapped halo pipelining
(``core.distributed``): every cell builds a distributed plan with an
explicit ``build_plan(overlap=...)`` and validates the whole overlap
contract on 8 fake host devices (subprocess, same rule as bench_plan's
partition matrix):

  * ``overlap="pipelined"`` output is BIT-IDENTICAL (``np.array_equal``)
    to the ``overlap="none"`` plan's output, eager AND compiled -- the two
    schedules share the per-hop partial combine, only the ppermute issue
    order differs, so pipelining may never change a single bit;
  * the compiled contract holds per cell (compiled == eager bitwise, no
    retrace on the second call);
  * the instrumented ``WorkloadReport`` schema-validates and its
    exposed/overlapped collective split agrees with ``describe()``
    (``report.mismatches``);
  * ``overlap="auto"`` resolves to a concrete schedule on the plan (the
    stored decision is never the literal "auto"), and for the all-gather
    strategy it resolves to "none" (one fused collective has no per-hop
    structure to pipeline);
  * the MODELED wall time of the pipelined schedule is <= the
    single-buffered one on every multi-shard ring cell (the overlap model
    guarantees this by construction -- ``min(wire, comp)`` per hop -- so a
    violation means the pricing broke).

Rows carry both the modeled times (``modeled_none_us`` /
``modeled_pipe_us``, the deterministic gate) and the measured compiled
wall time (``measured_us``, informational: 8 fake devices timeshare one
CPU, so measured numbers are correctness-shaped observables, not
accelerator predictions -- the same convention as every other bench).
``post_run`` accounts for every cell in the matrix and hard-fails any
silent skip or modeled-gate violation.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.plan import build_plan
from repro.launch.mesh import make_mesh
from repro.models.gcn import make_paper_model
from repro.profile.bench import BenchSpec, run_specs
from repro.profile.machine import TPU_V5E

#: (kind, mesh shape, mesh axis names) -- 1-D node sharding and a 2-D
#: node x feature mesh, both on the 8 fake devices
PARTITIONS = (
    ("1d", (8,), ("data",)),
    ("2d", (4, 2), ("node", "feat")),
)

#: (strategy, overlap) cells per partition; allgather has no per-hop
#: structure, so only "none" and the auto-resolves-to-none check apply
CELLS = (
    ("ring", "none"),
    ("ring", "pipelined"),
    ("ring", "auto"),
    ("allgather", "none"),
    ("allgather", "auto"),
)


def _cell_name(kind, shape, strategy, overlap):
    return (f"overlap/{kind}/{'x'.join(map(str, shape))}/"
            f"{strategy}/{overlap}")


def expected_matrix():
    """Every cell name the dry run must account for."""
    return [_cell_name(kind, shape, st, ov)
            for kind, shape, _ in PARTITIONS
            for st, ov in CELLS]


def _modeled_times(plan):
    """(t_none_s, t_pipelined_s) summed over the plan's layers from the
    same ``overlap_model`` pricing ``choose_overlap`` applies -- the
    deterministic wall-time gate (measured times on fake devices are
    noise-dominated)."""
    from repro.core.distributed import overlap_model
    from repro.core.scheduler import AGGREGATE_FIRST
    from repro.graph.partition import Partition2D
    part = plan.partition
    if isinstance(part, Partition2D):
        pg, width = part.nodes, part.feature_block
    else:
        pg, width = part, (lambda f: f)
    t_none = t_pipe = 0.0
    for lp in plan.layers:
        flen = width(lp.din if lp.order == AGGREGATE_FIRST else lp.dout)
        m = overlap_model(pg, flen, TPU_V5E, strategy=plan.strategy)
        t_none += m["t_none_s"]
        t_pipe += m["t_none_s"] - m["overlapped_pipelined_s"]
    return t_none, t_pipe


_CHILD_FLAG = "--overlap-child"


def _overlap_child(csv_out: str):
    """Subprocess body (8 fake devices): validate every overlap cell and
    write rows to ``csv_out`` for the parent to re-emit."""
    from repro.graph.datasets import make_features, make_synthetic_graph
    from repro.profile.bench import BenchContext, bench_graph, write_csv

    spec = bench_graph("reddit", max_vertices=256, max_feature=64)
    g = make_synthetic_graph(spec)
    x = make_features(spec)
    m = make_paper_model("gcn", spec)
    params = m.init(jax.random.PRNGKey(0))
    ctx = BenchContext(bench=None, machine=TPU_V5E, dry=True)

    for kind, shape, names in PARTITIONS:
        mesh = make_mesh(shape, names)
        baselines = {}          # strategy -> overlap="none" output
        for strategy, overlap in CELLS:
            name = _cell_name(kind, shape, strategy, overlap)
            plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                              mesh=mesh, strategy=strategy, overlap=overlap)
            assert plan.partition_kind == kind, (plan.partition_kind, kind)
            assert plan.overlap in ("none", "pipelined"), plan.overlap
            if strategy == "allgather":
                # no per-hop structure: auto must price allgather to "none"
                assert plan.overlap == "none", (name, plan.overlap)
            with mesh:
                report = plan.instrument(machine=TPU_V5E).run_model(
                    params, x)
                report.validate()
                drift = report.mismatches(plan)
                assert not drift, (name, drift)
                fn = plan.compile()
                out_c = np.asarray(fn(params, x))
                t0 = time.perf_counter()
                np.asarray(fn(params, x))
                measured_us = (time.perf_counter() - t0) * 1e6
                assert fn.num_traces == 1, (name, fn.num_traces)
            eager = np.asarray(report.output)
            assert np.array_equal(out_c, eager), \
                f"{name}: compiled != eager (the compiled contract is " \
                "bitwise)"
            base = baselines.setdefault(strategy, eager)
            assert np.array_equal(eager, base), \
                f"{name}: overlap={plan.overlap} output differs from the " \
                "overlap='none' plan -- pipelining changed bits"
            t_none, t_pipe = _modeled_times(plan)
            exp = sum(r.exposed_collective_time for r in report.records)
            ovl = sum(r.overlapped_collective_time for r in report.records)
            d0 = plan.describe()[0]
            ctx.emit(name, 0.0,
                     overlap=d0["overlap"], strategy=strategy,
                     partition=d0["partition"],
                     modeled_none_us=round(t_none * 1e6, 3),
                     modeled_pipe_us=round(t_pipe * 1e6, 3),
                     measured_us=round(measured_us, 1),
                     exposed_us=round(exp * 1e6, 3),
                     overlapped_us=round(ovl * 1e6, 3))
    write_csv(ctx.rows, csv_out)
    print("OVERLAP-CHILD-OK")


def _overlap_matrix(ctx, _):
    """Spawn the overlap matrix on 8 fake devices and re-emit its rows
    (dry and full runs alike: the halo paths NEED a multi-shard mesh, and
    fake devices are the only kind this container has)."""
    import csv as _csv
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        out = Path(td) / "overlap_child.csv"
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"   # a virtual-CPU matrix: never the TPU
        env["PYTHONPATH"] = os.pathsep.join(
            [str(Path(__file__).resolve().parents[1] / "src"),
             str(Path(__file__).resolve().parents[1])])
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_overlap",
             _CHILD_FLAG, str(out)],
            capture_output=True, text=True, env=env, timeout=900)
        if res.returncode != 0 or "OVERLAP-CHILD-OK" not in res.stdout:
            sys.stdout.write(res.stdout)
            raise RuntimeError(
                f"overlap subprocess failed:\n{res.stderr[-3000:]}")
        with out.open(newline="") as f:
            child_rows = list(_csv.DictReader(f))
    for row in child_rows:
        name = row.pop("name")
        us = float(row.pop("us_per_call"))
        ctx.emit(name, us, **row)


SPECS = [
    BenchSpec(name="overlap/matrix", measure=_overlap_matrix, dry="run"),
]


def post_run(rows, dry: bool = False):
    """Matrix accounting + the modeled wall-time gate.

    Every expected cell must have emitted a row (a silently skipped
    overlap scenario would merge unvalidated -- scripts/smoke.sh
    hard-fails on the exception this raises), and on every multi-shard
    ring cell the modeled pipelined time must be <= the single-buffered
    one."""
    byname = {r["name"]: r for r in rows}
    missing = [n for n in expected_matrix() if n not in byname]
    if missing:
        raise RuntimeError("overlap matrix cells silently skipped: "
                           + ", ".join(missing))
    bad = []
    for name, r in byname.items():
        if r.get("strategy") != "ring":
            continue
        if float(r["modeled_pipe_us"]) > float(r["modeled_none_us"]):
            bad.append(f"{name}: pipelined {r['modeled_pipe_us']}us > "
                       f"none {r['modeled_none_us']}us")
    if bad:
        raise RuntimeError("overlap model regressed -- pipelined modeled "
                           "time above single-buffered: " + "; ".join(bad))
    print(f"# overlap matrix: {len(expected_matrix())} cell(s) validated "
          "(bitwise + compiled + modeled gate), 0 silent")


def run(dry: bool = False):
    """Direct-invocation entry (``python -m benchmarks.bench_overlap
    [--dry-run]``); writes the same CSV artifact benchmarks/run.py does."""
    from repro.profile.bench import BENCH_ARTIFACT_DIR
    rows = run_specs(
        SPECS, dry=dry,
        csv=BENCH_ARTIFACT_DIR / f"bench_overlap{'.dry' if dry else ''}.csv")
    post_run(rows, dry=dry)


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        _overlap_child(sys.argv[sys.argv.index(_CHILD_FLAG) + 1])
    else:
        run(dry="--dry-run" in sys.argv)

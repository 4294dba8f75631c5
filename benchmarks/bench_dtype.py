"""Dtype sweep: precision as a planned decision, dtype x feature_len.

One matrix cell per (dtype, feature_len): the plan is built through
``build_plan(dtype=...)`` -- the SAME dispatch layer production uses -- and
validated against the two-sided precision contract:

  * **f32 cells** enforce the bitwise side: the explicit ``dtype="f32"``
    plan must BE the no-dtype-argument plan (same cache entry), its
    ``plan.compile()`` output bit-for-bit equal to eager, no retrace.
  * **Reduced cells** (bf16 / int8-agg) are banded against the f32 plan
    through the suite's ONE tolerance table (tests/tolerance.py, loaded by
    path so the bands cannot drift from the tests), and must leave the f32
    plan's output bitwise-unchanged afterwards -- a reduced build/run that
    perturbs the golden path hard-fails the smoke gate.

Under dry-run every cell also runs INSTRUMENTED: the WorkloadReport is
schema-validated (reduced reports must carry observed quant_error; f32
reports must carry none) and cross-checked against ``plan.describe()``
(dtype drift included).

The ``dtype/choose`` spec pins the ``choose_dtype`` decision model: on the
paper-scale workload (V=256, E=1024, F=128) it must pick ``"f32"`` on the
V100 preset (no native bf16 matmul: halving storage doubles GEMM time)
and ``"bf16"`` on TPU_V5E -- the machine-dependent flip that makes dtype a
*planned* decision rather than a global switch.  The ``dtype/halo`` spec
spawns an 8-fake-device subprocess (the dry-run rule) and asserts the
instrumented bf16 distributed plan reports EXACTLY half the f32 plan's
collective halo bytes.

``post_run`` accounts for every expected cell: silently skipped dtype
cells raise.
"""

from __future__ import annotations

import importlib.util
import itertools
import os
import subprocess
import sys
from pathlib import Path

import dataclasses
import jax
import numpy as np

from repro.core.plan import build_plan
from repro.launch.mesh import make_mesh
from repro.models.gcn import make_paper_model
from repro.profile.bench import BenchSpec, run_specs
from repro.profile.machine import (A100, TPU_V5E, V100, choose_dtype,
                                   dtype_model)

DTYPES = ("f32", "bf16", "int8-agg")
FEATURE_LENS = (32, 128)

CELLS = tuple(itertools.product(DTYPES, FEATURE_LENS))

#: (machine preset, expected choose_dtype pick) on the pinned flip workload
FLIP_WORKLOAD = dict(num_vertices=256, num_edges=1024, feature_len=128)
FLIP_EXPECT = ((V100, "f32"), (TPU_V5E, "bf16"), (A100, "bf16"))


def _bands():
    """The tests' tolerance module, loaded by path (tests/ is not a
    package): ONE band table for suite and smoke gate alike."""
    spec = importlib.util.spec_from_file_location(
        "tolerance", Path(__file__).resolve().parents[1] / "tests" /
        "tolerance.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cell_name(dtype, fl):
    return f"dtype/gcn/{dtype}/fl{fl}"


def _flip_name(machine):
    return f"dtype/choose/{machine.name}"


HALO_NAME = "dtype/halo/bf16-half"


def expected_matrix():
    """Every scenario name the dry run must account for."""
    return ([_cell_name(dt, fl) for dt, fl in CELLS]
            + [_flip_name(m) for m, _ in FLIP_EXPECT]
            + [HALO_NAME])


def _check_compiled_bitwise(name, plan, params, x, eager_out):
    fn = plan.compile()
    out_c = fn(params, x)
    fn(params, x)
    if not np.array_equal(np.asarray(out_c), np.asarray(eager_out)):
        raise RuntimeError(f"{name}: plan.compile() differs from eager "
                           "dispatch; the f32 contract is bitwise")
    if fn.num_traces != 1:
        raise RuntimeError(f"{name}: plan.compile() traced "
                           f"{fn.num_traces}x for one signature")


def _cell_inputs(ctx, fl):
    """Per-feature-length model/features on the spec's shared graph."""
    from repro.graph.datasets import make_features

    mspec = dataclasses.replace(ctx.spec, feature_len=fl)
    m = make_paper_model("gcn", mspec)
    params = m.init(jax.random.PRNGKey(0))
    x = make_features(mspec)
    return mspec, m, params, x


def _cell(ctx, point):
    """One (dtype, feature_len) cell of the matrix."""
    dt, fl = point
    tol = ctx.state
    mspec, m, params, x = _cell_inputs(ctx, fl)
    g = ctx.g
    name = _cell_name(dt, fl)

    p32 = build_plan(g, m.cfg, fl, mspec.num_classes)       # no dtype arg
    ref = p32.run_model(params, x)
    plan = build_plan(g, m.cfg, fl, mspec.num_classes, dtype=dt)
    out = plan.run_model(params, x)

    if dt == "f32":
        if plan is not p32:
            raise RuntimeError(
                f"{name}: explicit dtype='f32' built a different plan than "
                "the no-dtype-argument default (cache key drift)")
        _check_compiled_bitwise(name, plan, params, x, out)
        if not np.array_equal(np.asarray(out), np.asarray(ref)):
            raise RuntimeError(f"{name}: f32 output drifted from the "
                               "pre-dtype default path")
    else:
        # compiled replays the reduced schedule within the dtype band, and
        # the reduced output tracks the f32 plan within the band (scale 2:
        # two layers of phase-boundary rounding)
        tol.assert_allclose_dtype(plan.compile()(params, x), out, dtype=dt,
                                  err_msg=f"{name}: compiled vs eager")
        tol.assert_allclose_dtype(out, ref, dtype=dt, scale=2,
                                  err_msg=f"{name}: vs f32 plan")
        again = p32.run_model(params, x)
        if not np.array_equal(np.asarray(again), np.asarray(ref)):
            raise RuntimeError(
                f"{name}: building/running the {dt} plan perturbed the f32 "
                "plan's output -- the bitwise-golden contract is broken")

    derived = dict(dtype=plan.dtype, feature_len=fl,
                   order=plan.describe()[0]["order"])
    if ctx.dry:
        report = plan.instrument(machine=ctx.machine).run_model(params, x)
        report.validate()
        drift = report.mismatches(plan)
        if drift:
            raise RuntimeError(
                f"{name}: describe() disagrees with dispatch: {drift}")
        qerr = max(r.quant_error for r in report.records)
        if dt == "f32" and qerr != 0:
            raise RuntimeError(f"{name}: f32 report observed quantization")
        if dt != "f32" and qerr == 0:
            raise RuntimeError(f"{name}: reduced report observed no "
                               "quantization -- cell silently ran f32")
        ctx.emit(name, 0.0, quant_error=f"{qerr:.2e}",
                 report_phases=len(report.records), **derived)
    else:
        ctx.emit(name, ctx.time(plan.compile(), params, x), **derived)


def _flip(ctx, point):
    """Pin the choose_dtype decision per machine preset on one workload --
    the planner must demonstrably FLIP across presets, not apply a global
    preference."""
    machine, expect = point
    got = choose_dtype(machine=machine, **FLIP_WORKLOAD)
    if got != expect:
        raise RuntimeError(
            f"{_flip_name(machine)}: choose_dtype picked {got!r}, expected "
            f"{expect!r} on {machine.name} for {FLIP_WORKLOAD}")
    model = dtype_model(machine=machine, **FLIP_WORKLOAD)
    ctx.emit(_flip_name(machine), 0.0, picked=got,
             f32_us=round(model["f32"]["total_s"] * 1e6, 3),
             bf16_us=round(model["bf16"]["total_s"] * 1e6, 3),
             f32_tile_rows=model["f32"]["tile_rows"],
             bf16_tile_rows=model["bf16"]["tile_rows"])


_DTYPE_CHILD_FLAG = "--dtype-child"


def _dtype_child(csv_out: str):
    """Subprocess body (8 fake devices): the bf16 distributed plan's
    instrumented collective bytes must be EXACTLY half the f32 plan's on
    the same partition, with the bf16 output banded against the local f32
    reference."""
    from repro.profile.bench import BenchContext, bench_graph, write_csv
    from repro.graph.datasets import make_features, make_synthetic_graph

    tol = _bands()
    spec = bench_graph("reddit", max_vertices=301, max_feature=32)  # ragged
    g = make_synthetic_graph(spec)
    x = make_features(spec)
    m = make_paper_model("gcn", spec)
    params = m.init(jax.random.PRNGKey(0))
    ref = build_plan(g, m.cfg, spec.feature_len,
                     spec.num_classes).run_model(params, x)
    mesh = make_mesh((8,), ("data",))
    kw = dict(mesh=mesh, num_shards=8, strategy="ring")
    d32 = build_plan(g, m.cfg, spec.feature_len, spec.num_classes, **kw)
    dbf = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                     dtype="bf16", **kw)
    with mesh:
        r32 = d32.instrument(machine=TPU_V5E).run_model(params, x).validate()
        rbf = dbf.instrument(machine=TPU_V5E).run_model(params, x).validate()
    drift = rbf.mismatches(dbf)
    assert not drift, drift
    tol.assert_allclose_dtype(rbf.output, ref, dtype="bf16", scale=2,
                              err_msg="sharded bf16 vs local f32")
    c32 = sum(r.collective_bytes for r in r32.records)
    cbf = sum(r.collective_bytes for r in rbf.records)
    if not c32 > 0:
        raise RuntimeError("f32 halo model reported no collective traffic")
    if cbf * 2 != c32:
        raise RuntimeError(
            f"bf16 halo bytes {cbf} are not exactly half of f32's {c32}")
    ctx = BenchContext(bench=None, machine=TPU_V5E, dry=True)
    ctx.emit(HALO_NAME, 0.0, f32_collective_bytes=int(c32),
             bf16_collective_bytes=int(cbf),
             quant_error=f"{max(r.quant_error for r in rbf.records):.2e}")
    write_csv(ctx.rows, csv_out)
    print("DTYPE-CHILD-OK")


def _halo(ctx, _):
    """Spawn the halo-halving check on 8 fake devices (dry-run only: the
    reduced-wire *timing* needs a real multi-device mesh)."""
    if not ctx.dry:
        return
    import csv as _csv
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        out = Path(td) / "dtype_child.csv"
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"   # a virtual-CPU matrix: never the TPU
        env["PYTHONPATH"] = os.pathsep.join(
            [str(Path(__file__).resolve().parents[1] / "src"),
             str(Path(__file__).resolve().parents[1])])
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_dtype",
             _DTYPE_CHILD_FLAG, str(out)],
            capture_output=True, text=True, env=env, timeout=900)
        if res.returncode != 0 or "DTYPE-CHILD-OK" not in res.stdout:
            sys.stdout.write(res.stdout)
            raise RuntimeError(
                f"dtype halo subprocess failed:\n{res.stderr[-3000:]}")
        with out.open(newline="") as f:
            child_rows = list(_csv.DictReader(f))
    for row in child_rows:
        name = row.pop("name")
        us = float(row.pop("us_per_call"))
        ctx.emit(name, us, **row)


SPECS = [
    BenchSpec(name="dtype/matrix", graph="reddit", max_vertices=2048,
              max_feature=128, dry_max_vertices=256, machine=TPU_V5E,
              sweep=CELLS, setup=lambda ctx: _bands(), measure=_cell,
              dry="run"),
    BenchSpec(name="dtype/choose", sweep=FLIP_EXPECT, measure=_flip,
              dry="run"),
    BenchSpec(name="dtype/halo", measure=_halo, dry="run"),
]


def post_run(rows, dry: bool = False):
    """Cell accounting: every expected (dtype, feature_len) cell, flip
    check, and halo check must have emitted a row or carry a skip reason
    -- a silently missing dtype cell fails the smoke gate."""
    matrix = set(expected_matrix())
    validated = [r["name"] for r in rows if r["name"] in matrix]
    skipped = {}
    if not dry:
        skipped[HALO_NAME] = "halo halving needs the fake-device subprocess"
    missing = [n for n in expected_matrix()
               if n not in validated and n not in skipped]
    for name, why in skipped.items():
        print(f"# skipped: {name} ({why})")
    if missing:
        raise RuntimeError(
            "dtype cells silently skipped: " + ", ".join(missing))
    print(f"# dtype matrix: {len(validated)} cell(s) validated, "
          f"{len(skipped)} skipped with reasons, 0 silent")


def run(dry: bool = False):
    """Direct-invocation entry (``python -m benchmarks.bench_dtype
    [--dry-run]``); writes the same CSV artifact benchmarks/run.py does."""
    from repro.profile.bench import BENCH_ARTIFACT_DIR
    rows = run_specs(
        SPECS, dry=dry,
        csv=BENCH_ARTIFACT_DIR / f"bench_dtype{'.dry' if dry else ''}.csv")
    post_run(rows, dry=dry)


if __name__ == "__main__":
    if _DTYPE_CHILD_FLAG in sys.argv:
        _dtype_child(sys.argv[sys.argv.index(_DTYPE_CHILD_FLAG) + 1])
    else:
        run(dry="--dry-run" in sys.argv)

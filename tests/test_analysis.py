"""repro.analysis: static contract verification of plans and source.

Covers the PR 9 tentpole: every rule catches its seeded plant (the
self-test contract), the shipped tree and plan matrix are clean under
``--strict``, suppression pragmas work, and -- in a subprocess on 8
fake devices -- the jaxpr-extracted collective bytes equal BOTH the
analytic ``schedule_wire_bytes`` accounting and the
``WorkloadReport.wire_collective_bytes`` column exactly (f32 and bf16,
1-D and 2-D).
"""

import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.analysis.ast_lint import lint_source, lint_tree
from repro.analysis.jaxpr_lint import lint_plan
from repro.analysis.report import AnalysisReport, Finding
from repro.analysis.selftest import PLANTS, check_suppression
from test_distributed import child_env

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
TESTS = str(Path(__file__).resolve().parent)

ALL_RULES = sorted(PLANTS)


# ---------------------------------------------------------------------------
# Report core
# ---------------------------------------------------------------------------


def test_report_core_roundtrip():
    r = AnalysisReport()
    r.add("no-f64", "error", "plan[x]", "boom", "evidence")
    r.add("tracer-branch", "warning", "f.py:3", "maybe")
    assert not r.ok(strict=True)
    assert r.counts() == {"error": 1, "warning": 1, "info": 0}
    assert "no-f64" in r.to_json() and "boom" in r.to_markdown()
    # strict gate ignores warnings, non-strict does not
    r2 = AnalysisReport([Finding("tracer-branch", "warning", "f.py:3", "m")])
    assert r2.ok(strict=True) and not r2.ok(strict=False)
    with pytest.raises(ValueError):
        r.add("x", "fatal", "y", "z")


# ---------------------------------------------------------------------------
# Self-test: every rule must catch its plant (the gate that keeps the
# gate honest) -- one planted-positive test per rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_detects_its_plant(rule):
    report = PLANTS[rule]()
    assert any(f.rule == rule for f in report.findings), \
        f"rule {rule} missed its seeded violation:\n{report.render()}"


def test_rule_registry_covers_both_front_ends():
    """>= 8 rules total, spanning jaxpr and AST front ends."""
    assert len(ALL_RULES) >= 8
    assert {"no-callbacks", "no-f64", "bf16-f32-accum", "donation",
            "collective-bytes", "dynamic-edge-free"} <= set(ALL_RULES)
    assert {"host-in-trace", "tracer-branch", "broadcast-div",
            "acc-dtype", "grid-arity"} <= set(ALL_RULES)


def test_suppression_pragmas():
    assert check_suppression()
    # file-level pragma form
    src = ("# analysis: allow-file(broadcast-div)\n"
           "def f(h, deg):\n"
           "    return h / deg[:, None]\n")
    assert not lint_source(src).findings
    # an unrelated rule id does NOT suppress
    src = ("def f(h, deg):\n"
           "    return h / deg[:, None]  # analysis: allow(acc-dtype)\n")
    assert lint_source(src).findings


# ---------------------------------------------------------------------------
# The shipped tree and local plan matrix are clean
# ---------------------------------------------------------------------------


def test_shipped_tree_is_clean():
    report = lint_tree(ROOT / "src" / "repro")
    assert report.ok(strict=True), report.render()


@pytest.fixture(scope="module")
def small_setup():
    from repro.config import CORA, reduced_graph
    from repro.graph.datasets import make_synthetic_graph
    from repro.models.gcn import PAPER_MODELS
    spec = reduced_graph(CORA, 64, 16)
    g = make_synthetic_graph(spec)
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(8,))
    return spec, g, cfg


@pytest.mark.parametrize("backend,fused,dtype", [
    ("xla", False, "f32"), ("xla", False, "bf16"),
    ("pallas-tpu", True, "bf16"), ("pallas-tpu", True, "int8-agg"),
])
def test_lint_plan_local_cells_clean(small_setup, backend, fused, dtype):
    from repro.core.plan import build_plan
    spec, g, cfg = small_setup
    plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                      backend=backend, fused=fused, dtype=dtype)
    report = lint_plan(plan, dynamic=(backend == "xla" and not fused
                                      and dtype == "f32"))
    assert report.ok(strict=True), report.render()


def test_lint_plan_donation_positive(small_setup):
    """A plan whose logits CAN alias the donated features must show the
    donation marker in lowered HLO (zero findings); the no-alias shape
    yields an info finding, never an error."""
    from repro.core.plan import build_plan
    from repro.graph.datasets import make_synthetic_graph
    spec, g, cfg = small_setup
    spec_d = dataclasses.replace(spec, feature_len=spec.num_classes)
    g_d = make_synthetic_graph(spec_d)
    plan = build_plan(g_d, cfg, spec_d.feature_len, spec_d.num_classes)
    assert lint_plan(plan, donate=True).ok(strict=True)
    # mismatched shapes: donation silently unusable -> info, not error
    plan2 = build_plan(g, cfg, spec.feature_len, spec.num_classes)
    rep = lint_plan(plan2, donate=True)
    assert rep.ok(strict=True)
    assert any(f.rule == "donation" and f.severity == "info"
               for f in rep.findings)


def test_dynamic_edge_free_catches_baked_plan(small_setup):
    """A plan that bakes edge content (pallas blocked layout) cannot even
    reach dynamic compile; the jaxpr-level rule proves the qualifying
    plan's trace has no template-edge consts."""
    from repro.core.plan import build_plan
    spec, g, cfg = small_setup
    plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                      backend="pallas-tpu")
    with pytest.raises(ValueError, match="edge-content-free"):
        plan._check_dynamic_ok()


def test_pallas_aggregate_without_layout_points_at_the_plans(small_setup):
    """The Pallas tier aggregates over a plan-owned blocked layout alone:
    ``phases.aggregate`` refuses it without one, and both that error and
    the host-in-trace finding a reviewer reads name the trace-pure
    planned entry points."""
    import jax.numpy as jnp

    from repro.core import phases
    _, g, _ = small_setup
    with pytest.raises(ValueError) as ei:
        phases.aggregate(g, jnp.ones((g.num_vertices, 4)), op="sum",
                         backend="pallas-tpu")
    src = ("def f(x):\n"
           "    y = jnp.sum(x)\n"
           "    return float(jnp.max(y))\n")
    hits = [f for f in lint_source(src).findings
            if f.rule == "host-in-trace"]
    assert hits
    for entry in ("seg_agg_planned", "build_plan", "plan_for_conv",
                  "plan_for_phases"):
        assert entry in str(ei.value) and entry in hits[0].detail


# ---------------------------------------------------------------------------
# Acceptance: analyzer-extracted collective bytes == analytic accounting
# == WorkloadReport.wire_collective_bytes, exactly, on 8 fake devices
# ---------------------------------------------------------------------------


def run_sub(body: str):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True,
                         env=child_env(SRC, TESTS),
                         timeout=600)
    assert res.returncode == 0, f"subprocess failed:\n{res.stderr[-3000:]}"
    return res.stdout


@pytest.mark.slow
def test_collective_bytes_match_workload_report_8dev():
    out = run_sub("""
        import dataclasses
        from repro.config import CORA, reduced_graph
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.core.plan import build_plan
        from repro.models.gcn import PAPER_MODELS
        from repro.analysis.jaxpr_lint import (collective_bytes, lint_plan,
                                               plan_expected_collectives)
        spec = reduced_graph(CORA, 64, 16)
        g = make_synthetic_graph(spec); x = make_features(spec)
        cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(8,))
        meshes = {"1d": make_mesh((8,), ("data",)),
                  "2d": make_mesh((4, 2), ("node", "feat"))}
        for kind, mesh in meshes.items():
            for dtype in ("f32", "bf16"):
                for overlap in ("none", "pipelined"):
                    plan = build_plan(g, cfg, spec.feature_len,
                                      spec.num_classes, mesh=mesh,
                                      overlap=overlap, dtype=dtype)
                    params = plan.init(jax.random.PRNGKey(0))
                    jx = jax.make_jaxpr(
                        lambda p, xx: plan.run_model(p, xx))(params, x)
                    got = collective_bytes(jx)
                    exp = plan_expected_collectives(plan)
                    assert got == exp, (kind, dtype, overlap, got, exp)
                    # the full rule registry agrees
                    assert lint_plan(plan).ok(strict=True)
                    # WorkloadReport carries the SAME schedule-exact
                    # accounting, summed over distributed records
                    rep = plan.instrument().run_model(params, x)
                    wire = sum(r.wire_collective_bytes
                               for r in rep.records
                               if r.phase == "distributed")
                    assert wire == float(sum(got.values())), \\
                        (kind, dtype, overlap, wire, got)
                    print("MATCH", kind, dtype, overlap, sum(got.values()))
        print("OK")
    """)
    assert "OK" in out
    assert out.count("MATCH") == 8

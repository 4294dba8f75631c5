"""Compiled plan execution: plan.compile() and eager dispatch against the
float32 oracle across the planner matrix, grad-through-compile, the retrace guard, and
locality reordering as a planned decision (ISSUE 5 acceptance suite)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CORA, reduced_graph
from repro.core.plan import (CompiledPlan, GraphExecutionPlan, build_plan,
                             plan_for_conv, plan_for_phases)
from repro.core.scheduler import AGGREGATE_FIRST, COMBINE_FIRST
from repro.graph.datasets import make_features, make_synthetic_graph
from repro.models.gcn import make_paper_model
from repro.profile import A100, TPU_V5E
from test_distributed import TESTS, child_env
from tolerance import assert_matches_reference

SRC = str(Path(__file__).resolve().parents[1] / "src")

BACKENDS = ("xla", "pallas-tpu")


@pytest.fixture(scope="module")
def data():
    spec = reduced_graph(CORA, 220, 24)
    g = make_synthetic_graph(spec)
    return spec, g, make_features(spec)


def _assert_compiled_contract(plan, params, x, g, cfg):
    """The acceptance contract: eager and compiled both match the float32
    oracle on the natural graph, and the compiled callable traces once."""
    eager = plan.run_model(params, x)
    fn = plan.compile()
    out = fn(params, x)
    fn(params, x)                       # second call: must not retrace
    assert_matches_reference(eager, g, cfg, params, x)
    assert_matches_reference(out, g, cfg, params, x)
    assert fn.num_traces == 1
    return eager


# ---------------------------------------------------------------------------
# Equivalence matrix: compiled == eager across the planner's decisions
# ---------------------------------------------------------------------------


#: backend x fusion at reorder="none", plus the reorder axis on the xla
#: tier (the pallas x degree product is exercised end-to-end by the
#: benchmarks/run.py --dry-run gate; interpret-mode compiles are slow)
_MATRIX = ([(b, f, "none") for b in BACKENDS for f in (False, True)]
           + [("xla", f, "degree") for f in (False, True)])


@pytest.mark.parametrize("backend,fused,reorder", _MATRIX)
def test_compiled_matrix_gcn(data, backend, fused, reorder):
    """plan.compile() output matches the float32 oracle on every
    backend x fusion x reorder cell, with exactly one trace."""
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(0))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      backend=backend, fused=fused, reorder=reorder)
    _assert_compiled_contract(plan, p, x, g, m.cfg)


@pytest.mark.parametrize("model,kw", [
    ("gin", dict(fused=True)),
    ("gin", dict(fused=False)),
    ("gcn", dict(ordering=COMBINE_FIRST)),
    ("gcn", dict(ordering=AGGREGATE_FIRST, reorder="degree")),
    ("sage", dict(fused=True, reorder="degree")),
])
def test_compiled_models_and_orderings(data, model, kw):
    spec, g, x = data
    m = make_paper_model(model, spec)
    p = m.init(jax.random.PRNGKey(1))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes, **kw)
    _assert_compiled_contract(plan, p, x, g, m.cfg)


def test_reorder_matches_unreordered(data):
    """Degree reordering only changes the execution schedule; logits come
    back in the natural vertex order (equal to the unreordered plan up to
    summation-order float noise)."""
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(2))
    base = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    reord = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                       reorder="degree")
    assert reord is not base            # reorder is part of the cache key
    assert reord.reorder == "degree" and base.reorder == "none"
    assert reord.perm is not None
    # the execution graph is renumbered, the describe() row says so
    assert reord.describe()[0]["reorder"] == "degree"
    assert reord.describe()[0]["compiled"] is True
    np.testing.assert_allclose(
        np.asarray(reord.run_model(p, x)), np.asarray(base.run_model(p, x)),
        rtol=1e-4, atol=1e-5)


def test_reorder_auto_resolves_and_caches(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      reorder="auto")
    assert plan.reorder in ("none", "degree")   # resolved, never "auto"
    again = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                       reorder="auto")
    assert again is plan
    with pytest.raises(ValueError, match="reorder"):
        build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                   reorder="hilbert")


def test_run_phases_on_reordered_plan(data):
    """run_phases honors the natural-order contract on reordered plans
    (regression: it used to execute the renumbered graph against
    natural-order rows and return silently corrupted values), and rejects
    per-edge weights whose order the renumbering re-sorts."""
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((spec.feature_len, 8)) * 0.3,
                    jnp.float32)
    base = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    reord = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                       reorder="degree")
    ref = base.run_phases(x, [(w, None)], activation="none")
    out = reord.run_phases(x, [(w, None)], activation="none")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    ew = jnp.ones((g.num_edges,), jnp.float32)
    with pytest.raises(ValueError, match="edge_weight"):
        reord.run_phases(x, [(w, None)], edge_weight=ew, activation="none")


def test_reordered_plan_requires_natural_layout(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(0))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      reorder="degree")
    with pytest.raises(ValueError, match="natural"):
        plan.run_model(p, jnp.zeros((g.num_vertices + 5, spec.feature_len)))


# ---------------------------------------------------------------------------
# Training: grad flows through the compiled callable
# ---------------------------------------------------------------------------


def test_grad_through_compile_training_step(data):
    """One SGD step through plan.compile(): grads match the eager path and
    the step reduces the loss -- compiled execution is trainable."""
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(3))
    labels = jnp.asarray(
        np.random.default_rng(0).integers(0, spec.num_classes,
                                          g.num_vertices))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      backend="xla")
    fn = plan.compile()

    def loss_c(pp):
        ll = jax.nn.log_softmax(fn(pp, x), axis=-1)
        return -jnp.take_along_axis(ll, labels[:, None], axis=-1).mean()

    def loss_e(pp):
        ll = jax.nn.log_softmax(plan.run_model(pp, x), axis=-1)
        return -jnp.take_along_axis(ll, labels[:, None], axis=-1).mean()

    l0, grads = jax.value_and_grad(loss_c)(p)
    grads_e = jax.grad(loss_e)(p)
    for gc, ge in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(grads_e)):
        assert np.isfinite(np.asarray(gc)).all()
        np.testing.assert_allclose(np.asarray(gc), np.asarray(ge),
                                   rtol=1e-4, atol=1e-6)
    p1 = jax.tree_util.tree_map(lambda w, d: w - 0.5 * d, p, grads)
    assert float(loss_c(p1)) < float(l0)


# ---------------------------------------------------------------------------
# Retrace guard + caching + capability
# ---------------------------------------------------------------------------


def test_compile_is_cached_per_plan(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    assert plan.compile() is plan.compile()
    assert plan.compile(layer=0) is plan.compile(layer=0)
    assert plan.compile(layer=0) is not plan.compile()


def test_retrace_guard_fires_on_cache_bust(data):
    """The guard is not vacuous: clearing the underlying jit cache (the
    stand-in for anything that silently busts it) makes the second call
    retrace an already-seen signature, which must raise."""
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(0))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    fn = CompiledPlan(plan)             # fresh, bypasses the plan cache
    fn(p, x)
    if not hasattr(fn._fn, "clear_cache"):
        pytest.skip("jax version without jit clear_cache")
    fn._fn.clear_cache()
    with pytest.raises(RuntimeError, match="retraced"):
        fn(p, x)
    assert fn.num_traces == 2


def test_compile_unsupported_without_layout(data):
    """A hand-built Pallas plan lacking the plan-owned blocked layout is
    reported compiled=False and refused by compile() -- the capability
    field in describe() is observable, not decorative."""
    from dataclasses import replace
    spec, g, x = data
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal((x.shape[1], 8)) * 0.3, jnp.float32)
    good = plan_for_phases(g, [(w, None)], order=COMBINE_FIRST,
                           agg_op="mean", backend="pallas-tpu")
    assert good.compile_supported
    assert good.layers[0].agg_layout is not None
    bad = GraphExecutionPlan(
        g, [replace(good.layers[0], agg_layout=None)], interpret=True)
    assert not bad.compile_supported
    assert bad.describe()[0]["compiled"] is False
    with pytest.raises(ValueError, match="trace-pure"):
        bad.compile()


def test_plan_run_model_compiled_sugar(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(0))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    assert_matches_reference(plan.run_model(p, x, compiled=True), g, m.cfg,
                             p, x)


# ---------------------------------------------------------------------------
# machine= threading through the standalone-plan entry points (satellite)
# ---------------------------------------------------------------------------


def test_plan_for_conv_threads_machine(data):
    """Bugfix: plan_for_conv/plan_for_phases accept machine=, thread it
    into layer planning, and key the cache on it (previously standalone
    convs always planned with preset defaults)."""
    from repro.core.gcn_layers import GCNConv
    spec, g, x = data
    conv = GCNConv(din=spec.feature_len, dout=8, fused=True)
    base = plan_for_conv(conv, g)
    a100 = plan_for_conv(conv, g, machine=A100)
    assert a100 is not base             # machine is part of the cache key
    assert plan_for_conv(conv, g, machine="a100") is a100
    assert a100.machine is A100
    assert a100.instrument().machine is A100
    # the machine actually reaches _plan_layer: fused tile sizing follows
    # the memory hierarchy (A100's per-CTA budget vs v5e's half-VMEM)
    v5e = plan_for_conv(conv, g, machine=TPU_V5E)
    assert a100.layers[0].tile_m != v5e.layers[0].tile_m


def test_plan_for_phases_threads_machine(data):
    spec, g, x = data
    w = jnp.zeros((spec.feature_len, 8), jnp.float32)
    base = plan_for_phases(g, [(w, None)], agg_op="mean")
    a100 = plan_for_phases(g, [(w, None)], agg_op="mean", machine=A100)
    assert a100 is not base
    assert a100.machine is A100


# ---------------------------------------------------------------------------
# Instrumented compiled timing (repro.profile threading)
# ---------------------------------------------------------------------------


def test_instrumented_compiled_report(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(0))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      reorder="degree")
    report = plan.instrument(machine=A100).run_model(p, x, compiled=True)
    report.validate()
    assert report.mismatches(plan) == []
    ct = report.compiled_times
    assert ct is not None and ct["model_s"] > 0
    assert len(ct["layers_s"]) == plan.num_layers
    sp = report.compiled_speedup()
    assert sp["model"] > 0 and len(sp["layers"]) == plan.num_layers
    assert "compiled" in report.to_dict()
    assert "Compiled (plan.compile)" in report.to_markdown()
    # the reorder permute was observed at ingress; a plan that claims a
    # different reorder decision is flagged as drift
    base = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    drift = report.mismatches(base)
    assert drift and "reorder" in drift[0]


# ---------------------------------------------------------------------------
# Distributed plans compile too (8 fake devices, subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_partition_compiled_subprocess():
    """1-D and 2-D partitioned plans (with and without reorder) satisfy the
    compiled contract on an 8-fake-device mesh: eager and compiled match
    the float32 oracle, single trace, and agreement with the unsharded
    plan."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from repro.config import GRAPHS, reduced_graph
        from repro.launch.mesh import make_mesh
        from repro.graph.datasets import make_features, make_synthetic_graph
        from repro.core.plan import build_plan
        from repro.models.gcn import make_paper_model
        from tolerance import assert_matches_reference

        spec = reduced_graph(GRAPHS["reddit"], 256, 64)
        g = make_synthetic_graph(spec); x = make_features(spec)
        m = make_paper_model("gcn", spec)
        p = m.init(jax.random.PRNGKey(0))
        ref = build_plan(g, m.cfg, spec.feature_len,
                         spec.num_classes).run_model(p, x)
        cases = ((( 8,), ("data",), "none"),
                 (( 8,), ("data",), "degree"),
                 ((4, 2), ("node", "feat"), "none"),
                 ((4, 2), ("node", "feat"), "degree"))
        for shape, names, reorder in cases:
            mesh = make_mesh(shape, names)
            plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                              mesh=mesh, reorder=reorder)
            with mesh:
                eager = plan.run_model(p, x)
                fn = plan.compile()
                out = fn(p, x); fn(p, x)
            for got in (eager, out):
                assert_matches_reference(got, g, m.cfg, p, x, scale=10,
                                         err_msg=f"{shape}/{reorder}")
            assert fn.num_traces == 1, (shape, reorder)
            err = np.abs(np.asarray(eager) - np.asarray(ref)).max()
            assert err < 1e-3, (shape, reorder, err)

        # regression: run_phases on a distributed+reordered plan applies
        # ONLY the reorder permute, never the partition padding (V=249 is
        # deliberately not a multiple of the shard count)
        from repro.config import GraphSpec
        sp = GraphSpec("t", 249, 64, 1200, num_classes=5)
        g2 = make_synthetic_graph(sp); x2 = make_features(sp)
        m2 = make_paper_model("gcn", sp)
        w = jnp.asarray(np.random.default_rng(0).standard_normal(
            (64, 8)) * 0.2, jnp.float32)
        mesh = make_mesh((8,), ("data",))
        pr = build_plan(g2, m2.cfg, sp.feature_len, sp.num_classes,
                        mesh=mesh, reorder="degree")
        pb = build_plan(g2, m2.cfg, sp.feature_len, sp.num_classes)
        d = np.abs(np.asarray(
            pr.run_phases(x2, [(w, None)], activation="none")
            - pb.run_phases(x2, [(w, None)], activation="none"))).max()
        assert d < 1e-5, d
        print("OK")
    """)
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=child_env(SRC, TESTS),
        timeout=600)
    assert res.returncode == 0, f"subprocess failed:\n{res.stderr[-3000:]}"
    assert "OK" in res.stdout

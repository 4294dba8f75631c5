"""GraphExecutionPlan: equivalence across backend x ordering x fusion,
plan/BlockedGraph caching, auto-detection, and the no-raw-flags contract."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import CORA, GraphSpec, reduced_graph
from repro.core import backend as backend_mod
from repro.core import phases
from repro.core.backend import default_interpret, resolve_backend
from repro.core.plan import (build_plan, clear_plan_cache, plan_for_conv,
                             plan_for_phases)
from repro.core.scheduler import AGGREGATE_FIRST, COMBINE_FIRST
from repro.graph.datasets import make_features, make_synthetic_graph
from repro.models.gcn import PAPER_MODELS, make_paper_model

# the Pallas tier runs in interpret mode off a TPU
BACKENDS = ("xla", "pallas-tpu")
ORDERINGS = (COMBINE_FIRST, AGGREGATE_FIRST)  # both legal for GCN (mean, 1-mlp)


@pytest.fixture(scope="module")
def data():
    spec = reduced_graph(CORA, 220, 24)
    g = make_synthetic_graph(spec)
    return spec, g, make_features(spec)


def _model_and_ref(name, spec, g, x, key=0):
    m = make_paper_model(name, spec)
    p = m.init(jax.random.PRNGKey(key))
    ref = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                     backend="xla", fused=False).run_model(p, x)
    return m, p, ref


# ---------------------------------------------------------------------------
# The equivalence property: every planned scenario computes the same model
# ---------------------------------------------------------------------------


@given(st.integers(40, 200), st.integers(8, 24))
@settings(max_examples=4, deadline=None)
def test_run_model_equivalence_property(num_vertices, feature_len):
    """plan.run_model is identical (atol 1e-5) across backend x fusion x
    ordering on random graphs -- the planner only changes HOW, never WHAT."""
    spec = GraphSpec("t", num_vertices, feature_len, num_vertices * 4,
                     num_classes=5, seed=num_vertices)
    g = make_synthetic_graph(spec)
    x = make_features(spec)
    m, p, ref = _model_and_ref("gcn", spec, g, x)
    for backend in BACKENDS:
        for fused in (False, True):
            for order in ORDERINGS + (None,):
                plan = build_plan(g, m.cfg, spec.feature_len,
                                  spec.num_classes, backend=backend,
                                  fused=fused, ordering=order)
                out = plan.run_model(p, x)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5,
                    err_msg=f"{backend}/fused={fused}/order={order}")


def test_gin_fused_no_longer_ignored(data):
    """GIN now fuses aggregation with the first MLP matmul (exact)."""
    spec, g, x = data
    m, p, ref = _model_and_ref("gin", spec, g, x, key=1)
    for backend in BACKENDS:
        plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                          backend=backend, fused=True)
        assert plan.layers[0].fused and plan.layers[0].blocked is not None
        out = plan.run_model(p, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5, err_msg=backend)


def test_gin_ordering_pinned_even_when_forced(data):
    spec, g, _ = data
    plan = build_plan(g, PAPER_MODELS["gin"], spec.feature_len,
                      spec.num_classes, ordering=COMBINE_FIRST)
    assert all(lp.order == AGGREGATE_FIRST for lp in plan.layers)


def test_fused_single_matmul_keeps_inline_bias(data):
    """Regression: fusion must fold an inline (W, b) bias into the output,
    not drop it (exact for mean agg / aggregate-first -- see _can_fuse)."""
    spec, g, x = data
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal((x.shape[1], 8)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((8,)) * 2.0, jnp.float32)
    weights = [(w, b)]
    ref = phases.phase_ordered_layer(g, x, weights, order=COMBINE_FIRST,
                                     agg_op="mean", activation="none")
    fused = plan_for_phases(g, weights, order=COMBINE_FIRST, agg_op="mean",
                            fused=True)
    assert fused.layers[0].fused
    out = fused.run_phases(x, weights, activation="none")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_phase_ordered_layer_dispatches_and_chooses(data):
    """order=None lets the planner's cost model decide (F2)."""
    spec, g, x = data
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((x.shape[1], 8)) * 0.3, jnp.float32)
    auto = phases.phase_ordered_layer(g, x, [(w, None)], agg_op="mean",
                                      activation="none")
    # 24 -> 8 shrinks: combine_first must have been selected
    cf = phases.phase_ordered_layer(g, x, [(w, None)], order=COMBINE_FIRST,
                                    agg_op="mean", activation="none")
    np.testing.assert_allclose(np.asarray(auto), np.asarray(cf), rtol=1e-6)
    plan = plan_for_phases(g, [(w, None)], order=None, agg_op="mean")
    assert plan.layers[0].order == COMBINE_FIRST


# ---------------------------------------------------------------------------
# Caching
# ---------------------------------------------------------------------------


def test_plan_and_blocked_caching(data):
    spec, g, x = data
    clear_plan_cache()
    p1 = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                    spec.num_classes, fused=True)
    p2 = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                    spec.num_classes, fused=True)
    assert p1 is p2  # identical build -> cached plan
    assert p1.layers[0].blocked is p2.layers[0].blocked
    # a DIFFERENT plan on the same graph still shares the BlockedGraph
    # (host-side regrouping is done once per (graph, tile_m))
    p3 = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                    spec.num_classes, fused=True, ordering=AGGREGATE_FIRST)
    assert p3 is not p1
    assert p3.layers[0].blocked is p1.layers[0].blocked


def test_conv_apply_uses_cached_plan(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    pl1 = plan_for_conv(m.convs[0], g)
    pl2 = plan_for_conv(m.convs[0], g)
    assert pl1 is pl2


# ---------------------------------------------------------------------------
# Auto-detection + API contract
# ---------------------------------------------------------------------------


def test_interpret_autodetect(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert default_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert default_interpret() is True


def test_backend_auto_resolution():
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("pallas-tpu") == "pallas-tpu"
    plat = jax.default_backend()
    assert resolve_backend("auto") == (
        "pallas-tpu" if plat == "tpu" else "xla")
    assert resolve_backend() == resolve_backend("auto")
    with pytest.raises(ValueError):
        resolve_backend("cuda")


@pytest.mark.parametrize("requested", ["pallas-gpu", "pallas"])
def test_resolve_backend_refuses_removed_tiers(requested):
    """Only the TPU tier and XLA exist: the GPU tier and the "pallas"
    alias that resolved to a platform's tier are unknown backends."""
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend(requested)


@pytest.mark.parametrize("plat,auto", [
    ("cpu", "xla"),
    ("gpu", "xla"),
    ("tpu", "pallas-tpu"),
])
def test_backend_resolution_mocked_platforms(monkeypatch, plat, auto):
    """resolve_backend picks the Pallas tier on a TPU alone (paper F3 per
    platform): a GPU runs XLA's segmented reduction.  Each tier is a
    distinct string so plans record WHICH kernel family ran."""
    monkeypatch.setattr(backend_mod, "platform", lambda: plat)
    assert resolve_backend("auto") == auto
    # explicit tiers are never rewritten, even cross-platform
    assert resolve_backend("pallas-tpu") == "pallas-tpu"
    assert resolve_backend("xla") == "xla"


@pytest.mark.parametrize("plat", ["cpu", "gpu", "tpu"])
def test_interpret_per_tier_mocked_platforms(monkeypatch, plat):
    """The Pallas tier compiles only on a TPU; anywhere else it interprets
    (so a CPU container still validates the kernel numerics), and
    REPRO_PALLAS_INTERPRET overrides either way."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(backend_mod, "platform", lambda: plat)
    assert default_interpret() == (plat != "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret() is False


def test_no_raw_impl_blocked_flags():
    """Acceptance: no public layer API takes raw impl=/blocked= flags."""
    from repro.core.gcn_layers import GCNConv, GINConv
    from repro.models.gcn import GCNModel
    for fn in (GCNConv.apply, GINConv.apply, GCNModel.apply,
               phases.phase_ordered_layer, phases.aggregate):
        params = inspect.signature(fn).parameters
        assert "impl" not in params and "blocked" not in params, fn


def test_describe_reports_decisions(data):
    spec, g, _ = data
    plan = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                      spec.num_classes, fused=True)
    d = plan.describe()
    assert len(d) == PAPER_MODELS["gcn"].num_layers
    for row in d:
        assert {"order", "backend", "fused", "tile_m", "interpret",
                "agg_bytes"} <= set(row)
    # layer 2 shrinks 128->7: the cost model must pick combine_first
    assert d[-1]["order"] == COMBINE_FIRST


def test_partition_2d_structure(data):
    """partition_2d: node axis is the uniform 1-D partition; feature axis is
    a runtime columnwise split (ceil-divided block per feature length)."""
    from repro.graph.partition import partition_1d, partition_2d
    spec, g, _ = data
    p2 = partition_2d(g, 4, 2)
    assert p2.node_shards == 4 and p2.feat_shards == 2
    ref = partition_1d(g, 4, edge_balanced=False)
    assert p2.block_size == ref.block_size
    assert np.array_equal(np.asarray(p2.nodes.vtx_start),
                          np.asarray(ref.vtx_start))
    assert p2.feature_block(24) == 12
    assert p2.feature_block(7) == 4   # ceil(7/2): pad columns are zeros
    with pytest.raises(ValueError):
        partition_2d(g, 0, 2)


def test_plan_2d_mesh_requires_two_axes(data):
    """partition_kind reflects the mesh rank; a local plan reports "none"."""
    spec, g, _ = data
    plan = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                      spec.num_classes)
    assert plan.partition_kind == "none"
    d = plan.describe()[0]
    assert d["partition"] == "none"


def test_build_plan_rejects_traced_graph(data):
    spec, g, x = data

    def f(src):
        g2 = g._replace(src=src)
        return build_plan(g2, PAPER_MODELS["gcn"], spec.feature_len,
                          spec.num_classes)

    with pytest.raises(Exception):
        jax.jit(f)(g.src)

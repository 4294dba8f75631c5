"""Property-based dtype equivalence for planned execution (build_plan dtype=).

The two-sided contract under test:

  * ``dtype="f32"`` (and the default): eager and ``plan.compile()`` both
    match the plain float32 oracle on every (backend, fusion, ordering,
    reorder) combination, and building/running reduced-precision plans in
    between must not perturb the eager f32 output (bitwise).
  * ``"bf16"`` / ``"int8-agg"`` are tolerance-banded equivalent to the f32
    plan through the ONE shared harness (tests/tolerance.py) -- same band
    regardless of which planner axes are in play -- and resolve onto the
    plan (``plan.dtype`` never stays ``"auto"``).

The sharded case (8 fake devices, subprocess per the dry-run rule) drives
the reduced-precision halo exchange with a ragged V and checks the
instrument()-reported bf16 collective bytes are exactly half of f32's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from tolerance import assert_allclose_dtype, assert_matches_reference

from repro.core.plan import build_plan
from repro.graph.structure import graph_from_coo
from repro.models.gcn import PAPER_MODELS

DTYPES = ("f32", "bf16", "int8-agg")


def _case_graph(seed, v, deg, f):
    rng = np.random.default_rng(seed)
    e = max(v, v * deg)
    g = graph_from_coo(rng.integers(0, v, e), rng.integers(0, v, e), v)
    x = jnp.asarray(rng.standard_normal((v, f)), jnp.float32)
    return g, x


@st.composite
def planner_case(draw):
    """One point of the planner decision space x a random graph shape."""
    return dict(
        seed=draw(st.integers(0, 2 ** 16)),
        v=draw(st.integers(40, 160)),
        deg=draw(st.integers(2, 5)),
        f=draw(st.sampled_from([8, 24, 48])),
        backend=draw(st.sampled_from(["xla", "pallas-tpu"])),
        ordering=draw(st.sampled_from(["combine_first", "aggregate_first",
                                       None])),
        fused=draw(st.sampled_from([False, True, None])),
        reorder=draw(st.sampled_from(["none", "degree"])),
    )


def _plans_for(case):
    g, x = _case_graph(case["seed"], case["v"], case["deg"], case["f"])
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
    kw = dict(backend=case["backend"], ordering=case["ordering"],
              fused=case["fused"], reorder=case["reorder"])
    plans = {dt: build_plan(g, cfg, case["f"], 7, dtype=dt, **kw)
             for dt in DTYPES}
    params = plans["f32"].init(jax.random.PRNGKey(0))
    return g, x, plans, params, cfg


@given(planner_case())
@settings(max_examples=5, deadline=None)
def test_dtype_equivalence_across_planner_axes(case):
    """eager and compiled within the dtype band on every planner combo
    (f32: both against the float32 oracle); f32 eager is not perturbed by
    reduced runs in between."""
    g, x, plans, params, cfg = _plans_for(case)

    ref = plans["f32"].run_model(params, x)
    assert_matches_reference(ref, g, cfg, params, x, err_msg=str(case))
    assert_matches_reference(plans["f32"].compile()(params, x), g, cfg,
                             params, x, err_msg=str(case))

    for dt in ("bf16", "int8-agg"):
        p = plans[dt]
        assert p.dtype == dt                      # resolved, stored
        assert p.describe()[0]["dtype"] == dt
        out = p.run_model(params, x)
        # compiled replays the same reduced path within the band (bf16 is
        # a pure cast schedule, int8 rounding may fuse differently)
        assert_allclose_dtype(p.compile()(params, x), out, dtype=dt,
                              err_msg=f"compiled {dt}: {case}")
        # reduced output tracks the f32 plan within the dtype's band
        # (scale 2: two layers of rounding at the phase boundaries)
        assert_allclose_dtype(out, ref, dtype=dt, scale=2,
                              err_msg=f"{dt} vs f32: {case}")

    # the reduced builds/runs above must not have perturbed f32
    assert_allclose_dtype(plans["f32"].run_model(params, x), ref,
                          bitwise=True, err_msg=f"f32 perturbed: {case}")


def test_auto_dtype_resolves_and_caches_distinctly():
    """"auto" resolves against the machine before the plan is stored: the
    plan never carries "auto", and the cache keys the RESOLVED request --
    one graph can hold f32 and bf16 plans side by side."""
    from repro.profile.machine import TPU_V5E, V100, choose_dtype
    g, x = _case_graph(7, 96, 3, 24)
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
    pa = build_plan(g, cfg, 24, 7, dtype="auto", machine=TPU_V5E)
    assert pa.dtype in ("f32", "bf16") and pa.dtype != "auto"
    p32 = build_plan(g, cfg, 24, 7, dtype="f32", machine=TPU_V5E)
    pbf = build_plan(g, cfg, 24, 7, dtype="bf16", machine=TPU_V5E)
    assert p32 is not pbf
    assert build_plan(g, cfg, 24, 7, machine=TPU_V5E) is p32
    # the decision function itself flips across presets at the paper's
    # GCN-scale widths (the bench_dtype matrix pins the exact workload)
    assert choose_dtype(256, 1024, 128, machine=V100) == "f32"
    assert choose_dtype(256, 1024, 128, machine=TPU_V5E) == "bf16"
    with pytest.raises(ValueError):
        build_plan(g, cfg, 24, 7, dtype="f16")


def test_int8_agg_quantizes_only_aggregation():
    """int8-agg: combine stays f32 (records + describe agree), and the
    instrument report carries the quantization error it observed."""
    g, x = _case_graph(3, 80, 3, 24)
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
    p = build_plan(g, cfg, 24, 7, dtype="int8-agg")
    params = p.init(jax.random.PRNGKey(0))
    rep = p.instrument().run_model(params, x).validate()
    assert not rep.mismatches(p)
    by_phase = {r.phase: r for r in rep.records}
    assert by_phase["combine"].dtype == "f32"
    assert by_phase["aggregate"].dtype == "int8-agg"
    assert max(r.quant_error for r in rep.records) > 0
    # int8-agg keeps f32 storage at the output (only the agg operand is
    # fake-quantized); bf16 rounds the phase outputs down
    assert p.run_model(params, x).dtype == jnp.float32
    pb = build_plan(g, cfg, 24, 7, dtype="bf16")
    assert pb.run_model(params, x).dtype == jnp.bfloat16


@pytest.mark.slow
def test_sharded_bf16_halo_halves_collective_bytes():
    """8 fake devices, ragged V: the bf16 distributed plan matches the
    local f32 reference within band, and instrument() reports EXACTLY half
    the f32 plan's collective (halo) bytes -- the wire slab is the thing
    the reduced dtype shrinks."""
    from test_distributed import run_sub
    out = run_sub("""
        import dataclasses
        from repro.config import CORA, reduced_graph
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.core.plan import build_plan
        from repro.models.gcn import PAPER_MODELS
        spec = reduced_graph(CORA, 301, 32)       # 301 % 8 != 0: ragged
        g = make_synthetic_graph(spec); x = make_features(spec)
        cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
        mesh = make_mesh((8,), ("data",))
        local = build_plan(g, cfg, spec.feature_len, spec.num_classes)
        params = local.init(jax.random.PRNGKey(0))
        ref = local.run_model(params, x)
        kw = dict(mesh=mesh, num_shards=8, strategy="ring")
        d32 = build_plan(g, cfg, spec.feature_len, spec.num_classes, **kw)
        dbf = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                         dtype="bf16", **kw)
        with mesh:
            o32 = d32.run_model(params, x)
            obf = dbf.run_model(params, x)
        assert_allclose_dtype(o32, ref, scale=100)
        assert_allclose_dtype(obf, ref, dtype="bf16", scale=2)
        with mesh:
            r32 = d32.instrument().run_model(params, x).validate()
            rbf = dbf.instrument().run_model(params, x).validate()
        assert not rbf.mismatches(dbf)
        c32 = sum(r.collective_bytes for r in r32.records)
        cbf = sum(r.collective_bytes for r in rbf.records)
        assert c32 > 0, "halo model reported no collective traffic"
        assert cbf * 2 == c32, (cbf, c32)
        assert max(r.quant_error for r in rbf.records) > 0
        assert all(r.quant_error == 0 for r in r32.records)
        print("DTYPE-OK")
    """)
    assert "DTYPE-OK" in out

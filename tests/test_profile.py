"""repro.profile: Machine presets, InstrumentedPlan/WorkloadReport, the
BenchSpec harness, and the describe()-vs-dispatch consistency guard."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CORA, reduced_graph
from repro.core import characterize
from repro.core.backend import default_machine
from repro.core.dataflow import suggest_tile_m
from repro.core.hlo_cost import analyze_hlo
from repro.core.plan import build_plan, plan_for_phases
from repro.core.scheduler import (AGGREGATE_FIRST, COMBINE_FIRST,
                                  choose_ordering, ordering_cost,
                                  ordering_time)
from repro.graph.datasets import make_features, make_synthetic_graph
from repro.models.gcn import make_paper_model
from repro.profile import (A100, H100, MACHINES, TPU_V5E, TPU_V5P, V100,
                           BenchSpec, Machine, WorkloadReportError,
                           get_machine, machine_for_backend, run_specs)
from repro.profile.bench import csv_columns, write_csv

GOLDEN = Path(__file__).parent / "golden" / "workload_report.schema.json"


@pytest.fixture(scope="module")
def data():
    spec = reduced_graph(CORA, 220, 24)
    g = make_synthetic_graph(spec)
    return spec, g, make_features(spec)


def _gcn(spec, g, x, **plan_kw):
    m = make_paper_model("gcn", spec)
    p = m.init(jax.random.PRNGKey(0))
    plan = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                      **plan_kw)
    return m, p, plan


# ---------------------------------------------------------------------------
# Machine presets
# ---------------------------------------------------------------------------


def test_machine_presets_and_registry():
    assert set(MACHINES) == {"tpu-v5e", "tpu-v5p", "a100", "h100", "v100"}
    # the paper's classification threshold: V100 fp32 balance ~17.4 F/B
    assert V100.balance == pytest.approx(15.7e12 / 900e9)
    assert TPU_V5E.balance == pytest.approx(197e12 / 819e9)
    # v5p: fatter chip, but HBM grows faster than peak -> lower balance
    assert TPU_V5P.balance < TPU_V5E.balance
    assert get_machine("tpu-v5p") is TPU_V5P
    assert V100.classify(5.0) == "memory"
    assert V100.classify(50.0) == "compute"
    # the same AI=50 GEMM is memory-bound on v5e: the hardware-adaptation
    # finding the repo reports alongside the paper numbers
    assert TPU_V5E.classify(50.0) == "memory"
    assert get_machine("a100") is A100
    assert get_machine(A100) is A100
    assert get_machine("h100") is H100
    # H100 is still memory-hungrier than its FLOP growth: balance rises
    assert H100.balance > A100.balance
    with pytest.raises(ValueError):
        get_machine("h200")


def test_machine_for_backend_mapping():
    # off a TPU both tiers model the repo's target chip
    assert machine_for_backend("pallas-tpu") is TPU_V5E
    assert machine_for_backend("xla") is TPU_V5E
    # default_machine resolves the tier first (CPU container: auto -> xla)
    assert default_machine("auto") is TPU_V5E
    assert default_machine("pallas-tpu") is TPU_V5E


def test_tpu_machine_keyed_by_device_kind():
    """On a real TPU the preset comes from the chip's device_kind; a kind
    with no preset is an error, never a silent v5e."""
    from repro.profile.machine import TPU_V5P, tpu_machine
    assert tpu_machine("TPU v5 lite") is TPU_V5E
    assert tpu_machine("TPU v5") is TPU_V5P
    with pytest.raises(ValueError, match="no Machine preset"):
        tpu_machine("TPU v99")


def test_deprecated_characterize_shims_removed():
    """The PR 4 'one release' constant shims are gone: Machine presets are
    the only copy of the hardware numbers."""
    for name in ("VMEM_BYTES", "MACHINE_BALANCE", "GPU_SMEM_PER_SM",
                 "GPU_TARGET_CTAS_PER_SM", "GPU_WARP_ROWS", "V100_BALANCE",
                 "PEAK_FLOPS_BF16", "HBM_BW", "MXU_DIM"):
        assert not hasattr(characterize, name), name


def test_suggest_tile_m_is_machine_parameterized():
    """The fused tile is sized from the Machine, not from constants: the
    default is the platform's preset (TPU_V5E off a TPU), every tile is a
    multiple of the machine's row alignment, and a larger VMEM can only
    grow the tile."""
    default = suggest_tile_m(128, 128, 8.0)
    assert default == suggest_tile_m(128, 128, 8.0, machine=TPU_V5E)
    assert suggest_tile_m(128, 128, 8.0, backend="xla") == default
    for m in (TPU_V5E, TPU_V5P):
        tile = suggest_tile_m(602, 128, 50.0, machine=m)
        assert tile >= m.row_align and tile % m.row_align == 0
    # the budget follows the machine's VMEM, not a hardcoded constant
    big = Machine(name="tpu-big", kind="tpu", peak_flops=197e12,
                  hbm_bw=819e9, interconnect_bw=50e9, interconnect_links=4,
                  on_chip_bytes=4 * TPU_V5E.on_chip_bytes)
    assert suggest_tile_m(602, 512, 50.0, machine=big) >= \
        suggest_tile_m(602, 512, 50.0, machine=TPU_V5E)


def test_choose_ordering_machine_agrees_with_bytes(data):
    """A Machine only re-prices the margin; the legal decision (driven by
    the memory-bound aggregation term) is identical across presets."""
    _, g, _ = data
    for in_len, out_len in ((602, 128), (128, 602), (64, 64)):
        base = choose_ordering(g, in_len, out_len)
        for m in (TPU_V5E, A100, V100):
            assert choose_ordering(g, in_len, out_len, machine=m) == base
    # ordering_time itself is finite, positive, and orders correctly
    cf = ordering_cost(g, 602, 128, COMBINE_FIRST)
    af = ordering_cost(g, 602, 128, AGGREGATE_FIRST)
    assert 0 < ordering_time(cf, V100) < ordering_time(af, V100)


# ---------------------------------------------------------------------------
# The one-call characterization path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("machine", [A100, TPU_V5E], ids=lambda m: m.name)
def test_one_call_report(data, machine):
    """build_plan(...).instrument(machine=...).run_model(...) yields a
    validated WorkloadReport whose markdown reproduces a paper-style
    per-phase breakdown -- on >= 2 Machine presets (acceptance)."""
    spec, g, x = data
    m, p, plan = _gcn(spec, g, x)
    report = plan.instrument(machine=machine).run_model(p, x).validate()
    # the forward result rides along and matches the uninstrumented plan
    ref = plan.run_model(p, x)
    np.testing.assert_allclose(np.asarray(report.output), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # per-phase records: one aggregate + one combine per layer (unfused)
    assert len(report.records) == 2 * plan.num_layers
    for r in report.records:
        assert r.wall_time_s > 0
        assert r.bound == machine.classify(r.arithmetic_intensity)
    md = report.to_markdown()
    assert machine.name in md
    assert "| layer | phase | order | backend |" in md
    assert "aggregate" in md and "combine" in md
    assert md.count("\n| ") >= 2 * plan.num_layers + 1  # rows + totals
    assert f"balance {machine.balance:.1f}" in md


def test_report_json_schema_golden(data):
    """Golden-file schema: key sets of every report section are pinned."""
    spec, g, x = data
    _, p, plan = _gcn(spec, g, x)
    d = json.loads(plan.instrument(machine=V100).run_model(p, x).to_json())
    golden = json.loads(GOLDEN.read_text())
    assert d["schema"] == golden["schema"]
    assert d["version"] == golden["version"]
    assert sorted(d) == golden["top"]
    assert sorted(d["machine"]) == golden["machine"]
    assert sorted(d["plan"]) == golden["plan"]
    assert sorted(d["totals"]) == golden["totals"]
    for rec in d["phases"]:
        assert sorted(rec) == golden["phase_record"]
    for lay in d["plan"]["layers"]:
        assert sorted(lay) == golden["layer"]


def test_report_validate_catches_violations(data):
    spec, g, x = data
    _, p, plan = _gcn(spec, g, x)
    report = plan.instrument().run_model(p, x)
    report.validate()  # clean passes
    empty = type(report)(machine=report.machine,
                         plan_summary=report.plan_summary, records=[])
    with pytest.raises(WorkloadReportError, match="empty phase records"):
        empty.validate()
    bad = type(report)(machine=report.machine,
                       plan_summary=report.plan_summary,
                       records=[report.records[0].__class__(
                           layer=0, phase="warp", order="combine_first",
                           backend="xla", fused=False, feature_len=8,
                           flops=1.0, bytes=1.0, collective_bytes=0.0,
                           wall_time_s=0.0, bound="memory")])
    with pytest.raises(WorkloadReportError, match="unknown phase"):
        bad.validate()
    # deserialized artifacts are validated in dict form, where the
    # totals-vs-phases cross-check is meaningful (files can be edited)
    from repro.profile import validate_report_dict
    d = json.loads(report.to_json())
    assert validate_report_dict(d) == []
    d["totals"]["flops"] += 1e6
    assert any("totals.flops" in p for p in validate_report_dict(d))


def test_report_phase_costs_match_hlo(data):
    """Invariant: the report's combine-phase FLOPs sum EXACTLY to the dot
    FLOPs hlo_cost extracts from the compiled model, and analytic totals
    never exceed the compiled program's (the analytic model is a lower
    bound; XLA's CPU scatter lowering adds platform noise on top)."""
    spec, g, x = data
    _, p, plan = _gcn(spec, g, x, backend="xla", fused=False)
    report = plan.instrument(machine=TPU_V5E).run_model(p, x)
    hc = analyze_hlo(jax.jit(
        lambda pp, xx: plan.run_model(pp, xx)).lower(p, x).compile()
        .as_text())
    comb_flops = sum(r.flops for r in report.records
                     if r.phase == "combine")
    assert comb_flops == pytest.approx(hc.dot_flops, rel=1e-6)
    tot = report.totals()
    assert 0 < tot["flops"] <= hc.flops
    assert 0 < tot["bytes"] <= hc.bytes_accessed


# ---------------------------------------------------------------------------
# describe() vs dispatch consistency (regression guard)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", ["gcn", "sage", "gin"])
def test_describe_matches_dispatch(data, model):
    """plan.describe() must agree with the decisions actually dispatched
    (ordering / backend / fusion per layer) across the planner matrix."""
    spec, g, x = data
    m = make_paper_model(model, spec)
    p = m.init(jax.random.PRNGKey(1))
    orderings = (None,) if model == "gin" else (None, COMBINE_FIRST,
                                                AGGREGATE_FIRST)
    for backend in ("xla", "pallas-tpu"):
        for fused in (False, True):
            for order in orderings:
                plan = build_plan(g, m.cfg, spec.feature_len,
                                  spec.num_classes, backend=backend,
                                  fused=fused, ordering=order)
                report = plan.instrument().run_model(p, x).validate()
                assert report.mismatches(plan) == [], \
                    (model, backend, fused, order)


def test_runtime_fusion_fallback_is_reported(data):
    """The drift guard is not vacuous: run_phases with an inline bias that
    fusion cannot absorb (sum + combine_first) legitimately falls back at
    call time, and mismatches() reports exactly that."""
    spec, g, x = data
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((x.shape[1], 8)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    plan = plan_for_phases(g, [(w, b)], order=COMBINE_FIRST, agg_op="sum",
                           fused=True)
    assert plan.layers[0].fused  # planned fused...
    report = plan.instrument().run_phases(x, [(w, b)], activation="none")
    drift = report.mismatches(plan)
    assert drift and "fused" in drift[0]  # ...but dispatch fell back


def test_unresolved_backend_alias_is_reported(data):
    """The backend drift check observes call-time resolution: a plan that
    regressed to storing the unresolved 'auto' (instead of a resolved
    tier) must be flagged -- proves the guard is not vacuous."""
    from dataclasses import replace

    from repro.core.plan import GraphExecutionPlan
    spec, g, x = data
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal((x.shape[1], 8)) * 0.3, jnp.float32)
    good = plan_for_phases(g, [(w, None)], order=COMBINE_FIRST,
                           agg_op="mean", backend="pallas-tpu")
    bad_lp = replace(good.layers[0], backend="auto")  # unresolved
    bad = GraphExecutionPlan(g, [bad_lp], interpret=True)
    report = bad.instrument(machine=TPU_V5E).run_phases(
        x, [(w, None)], activation="none")
    drift = report.mismatches(bad)
    assert drift and "backend" in drift[0]


def test_distributed_record_carries_collective_bytes(data):
    """The probe prices distributed layers with the halo model's collective
    bytes (the full multi-device matrix runs in bench_plan's dry-run
    subprocess; here the cost hookup is checked without a mesh)."""
    import types

    from repro.core.distributed import halo_bytes
    from repro.graph.partition import partition_1d
    from repro.profile.instrument import _Probe
    spec, g, x = data
    pg = partition_1d(g, 4, edge_balanced=False)
    hb = halo_bytes(pg, 8)["min_halo_bytes"]
    assert hb > 0  # the fixture graph has cut edges
    fake_plan = types.SimpleNamespace(g=g, partition_kind="1d", partition=pg)
    probe = _Probe(fake_plan, TPU_V5E)
    assert probe._halo_bytes(8) == float(hb)
    lp = types.SimpleNamespace(index=0, order=COMBINE_FIRST, backend="xla",
                               include_self=True, dims=(24, 8))
    probe.run("distributed", lambda: jnp.zeros(()), lp=lp, feature_len=8)
    (rec,) = probe.records
    assert rec.phase == "distributed" and rec.collective_bytes == float(hb)


# ---------------------------------------------------------------------------
# Machine plumbing through build_plan
# ---------------------------------------------------------------------------


def test_build_plan_machine_in_cache_key(data):
    spec, g, x = data
    m = make_paper_model("gcn", spec)
    p0 = build_plan(g, m.cfg, spec.feature_len, spec.num_classes)
    pa = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                    machine=A100)
    pa2 = build_plan(g, m.cfg, spec.feature_len, spec.num_classes,
                     machine="a100")
    assert pa is not p0
    assert pa2 is pa            # name resolves to the same preset -> cached
    assert pa.machine is A100
    # instrument() defaults to the plan's machine
    assert pa.instrument().machine is A100


# ---------------------------------------------------------------------------
# BenchSpec harness
# ---------------------------------------------------------------------------


def test_bench_harness_csv_and_dry(tmp_path):
    calls = []

    def measure(ctx, point):
        t = ctx.time(lambda: jnp.ones(4))
        calls.append((point, ctx.dry, t))
        row = {"sweep": point} if point == "a" else {"other": point}
        ctx.emit(f"t/{point}", t, **row)

    spec = BenchSpec(name="t", sweep=("a", "b"), measure=measure, dry="run")
    csv_path = tmp_path / "t.csv"
    rows = run_specs([spec], dry=True, csv=csv_path)
    assert [c[0] for c in calls] == ["a", "b"]
    assert all(dry and t == 0.0 for _, dry, t in calls)  # timing disabled
    assert len(rows) == 2
    # CSV artifact: header row, stable column order, empty cells for holes
    lines = csv_path.read_text().strip().splitlines()
    assert lines[0] == "name,us_per_call,other,sweep"
    assert lines[1] == "t/a,0.0,,a"
    assert lines[2] == "t/b,0.0,b,"
    assert csv_columns(rows) == ["name", "us_per_call", "other", "sweep"]
    # dry="skip" specs are skipped under dry-run, run otherwise
    skip_spec = BenchSpec(name="s", measure=measure, dry="skip")
    n_before = len(calls)
    run_specs([skip_spec], dry=True)
    assert len(calls) == n_before


def test_bench_write_csv_empty(tmp_path):
    assert write_csv([], tmp_path / "none.csv") is None
    assert not (tmp_path / "none.csv").exists()

"""Serving: prefill/decode consistency, engine continuous batching, and
the bucketed GraphServeEngine (smallest-fit selection, padded-vs-eager
bit-identity, slot reuse, zero-retrace warm-up, latency percentiles,
plan-cache eviction, serving report schema)."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CORA, reduced_graph
from repro.configs import (gemma2_9b, granite_3_8b, jamba_1_5_large,
                           kimi_k2, mamba2_2_7b, seamless_m4t_medium)
from repro.core.plan import build_plan, clear_plan_cache, plan_cache_stats
from repro.core.scheduler import AGGREGATE_FIRST
from repro.graph.datasets import make_features, make_synthetic_graph
from repro.kernels.ref import gcn_forward_ref
from repro.models import encdec
from repro.models.gcn import PAPER_MODELS
from repro.models.transformer import (init_lm, lm_decode_step, lm_forward,
                                      lm_prefill)
from repro.serve import (Bucket, GraphRequest, GraphServeEngine,
                         default_buckets)
from repro.serve.engine import Request, ServeEngine
from tolerance import assert_allclose_dtype

GOLDEN = Path(__file__).parent / "golden" / "workload_report.schema.json"


def _fp32(mod, cap=8.0):
    cfg = dataclasses.replace(mod.reduced(), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=cap))
    return cfg


@pytest.mark.parametrize("mod", [granite_3_8b, gemma2_9b, kimi_k2,
                                 jamba_1_5_large, mamba2_2_7b])
def test_decode_matches_full_forward(mod):
    cfg = _fp32(mod)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _ = lm_forward(params, cfg, toks)
    lg, caches, length = lm_prefill(params, cfg, toks[:, :S - 1],
                                    cache_size=S + 4)
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(full[:, -2]), rtol=1e-3, atol=1e-3)
    lg2, caches, length = lm_decode_step(params, cfg, toks[:, S - 1:S],
                                         caches, length)
    np.testing.assert_allclose(np.asarray(lg2[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-3, atol=1e-3)


def test_decode_multi_step_consistency():
    cfg = _fp32(granite_3_8b)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0,
                              cfg.vocab_size)
    full, _ = lm_forward(params, cfg, toks)
    lg, caches, length = lm_prefill(params, cfg, toks[:, :16],
                                    cache_size=32)
    for t in range(16, 24):
        lg, caches, length = lm_decode_step(params, cfg, toks[:, t:t + 1],
                                            caches, length)
        np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                   np.asarray(full[0, t]), rtol=1e-3,
                                   atol=1e-3)


def test_encdec_decode_consistency():
    cfg = _fp32(seamless_m4t_medium)
    p = encdec.init_encdec(cfg, jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                              cfg.vocab_size)
    memory = encdec.encode(p, cfg, frames)
    full, _ = encdec.decode_stack(p, cfg, toks, memory)
    lg, caches, mem, length = encdec.encdec_prefill(p, cfg, frames,
                                                    toks[:, :11],
                                                    cache_size=16)
    lg2, caches, length = encdec.encdec_decode_step(p, cfg, toks[:, 11:12],
                                                    caches, mem, length)
    np.testing.assert_allclose(np.asarray(lg2[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-3, atol=1e-3)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = _fp32(granite_3_8b)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_greedy_matches_naive(engine_setup):
    cfg, params = engine_setup
    eng = ServeEngine(cfg, params, max_batch=2, cache_size=48)
    reqs = [Request(rid=i, prompt=np.arange(4 + i) % cfg.vocab_size,
                    max_tokens=6) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 3
    for r in done:
        toks = list(r.prompt)
        for _ in range(r.max_tokens):
            logits, _ = lm_forward(params, cfg,
                                   jnp.asarray([toks], jnp.int32))
            toks.append(int(np.asarray(logits)[0, -1].argmax()))
        assert toks[len(r.prompt):] == r.output[:r.max_tokens]


def test_engine_continuous_batching_slot_reuse(engine_setup):
    cfg, params = engine_setup
    eng = ServeEngine(cfg, params, max_batch=2, cache_size=64)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=np.arange(3) % cfg.vocab_size,
                           max_tokens=3 + i))
    done = eng.run()
    assert len(done) == 5
    assert {r.rid for r in done} == set(range(5))
    # slots were reused: max concurrent = 2 but 5 requests served
    assert eng.stats()["decode_steps"] < sum(3 + i for i in range(5))


def test_engine_eos_stop(engine_setup):
    cfg, params = engine_setup
    eng = ServeEngine(cfg, params, max_batch=1, cache_size=64)
    # find the greedy first token, then use it as EOS: generation stops at 1
    eng.submit(Request(rid=0, prompt=np.arange(4), max_tokens=32))
    done = eng.run()
    first = done[0].output[0]
    eng2 = ServeEngine(cfg, params, max_batch=1, cache_size=64)
    eng2.submit(Request(rid=1, prompt=np.arange(4), max_tokens=32,
                        eos_id=first))
    done2 = eng2.run()
    assert len(done2[0].output) == 1


# --------------------------------------------------------------------------
# GraphServeEngine: GCN node prediction through bucketed compiled plans
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def graph_setup():
    spec = reduced_graph(CORA, max_vertices=220, max_feature=24)
    return spec, make_synthetic_graph(spec), make_features(spec)


def _graph_engine(graph_setup, **kw):
    spec, g, x = graph_setup
    kw.setdefault("fanouts", (3, 3))
    kw.setdefault("max_batch", 4)
    eng = GraphServeEngine(g, PAPER_MODELS["gcn"], None, x,
                           spec.num_classes, **kw)
    eng.params = eng.init_params(jax.random.PRNGKey(0))
    return eng


@pytest.fixture(scope="module")
def drained_engine(graph_setup):
    """The acceptance drain: 200 requests through <= 4 buckets."""
    spec, g, x = graph_setup
    eng = _graph_engine(
        graph_setup, max_batch=8,
        buckets=default_buckets((3, 3), seed_levels=(4, 16),
                                max_inputs=g.num_vertices))
    traces = eng.warmup()
    rng = np.random.default_rng(7)
    for i in range(200):
        seeds = rng.choice(g.num_vertices,
                           size=int(rng.integers(1, 17)), replace=False)
        eng.submit(GraphRequest(rid=i, seeds=seeds))
    done = eng.run()
    return eng, traces, done


def test_bucket_fits_rule():
    b = Bucket(num_seeds=4, num_inputs=10, num_edges=20)
    assert b.fits(4, 10, 20)          # exact fit: no pad edges needed
    assert b.fits(4, 9, 19)           # pad edges -> last row is the sink
    assert not b.fits(4, 10, 19)      # pad edges but no free sink row
    assert not b.fits(5, 9, 19)       # too many seeds
    assert not b.fits(4, 9, 21)       # too many edges


def test_default_buckets_worst_case_fit():
    f1, f2 = 3, 3
    buckets = default_buckets((f1, f2), seed_levels=(2, 4))
    assert len(buckets) == 2
    for s, b in zip((2, 4), sorted(buckets, key=lambda b: b.num_seeds)):
        frontier = s * (1 + f1) * (1 + f2)
        edges = s * f1 + s * (1 + f1) * f2
        assert b.fits(s, frontier, edges)   # worst case fits by design


def test_select_bucket_smallest_fitting(graph_setup):
    eng = _graph_engine(graph_setup,
                        buckets=[(8, 80, 160), (2, 20, 30), (4, 40, 80)])
    assert eng.select_bucket(1, 10, 10) == Bucket(2, 20, 30)
    # full frontier with pad edges pending: the sink row rule kicks in
    assert eng.select_bucket(2, 20, 29) == Bucket(4, 40, 80)
    assert eng.select_bucket(3, 10, 10) == Bucket(4, 40, 80)
    assert eng.select_bucket(8, 80, 160) == Bucket(8, 80, 160)
    assert eng.select_bucket(9, 10, 10) is None


def _block_reference(eng, prep):
    """Seed logits of the float32 oracle on the unpadded union block."""
    g = prep.graph
    out = gcn_forward_ref(g.src, g.dst, g.num_vertices, eng.cfg, eng.params,
                          eng.features[prep.frontier])
    return np.asarray(out)[prep.seed_pos]


def test_graph_padded_bit_identical_to_eager(graph_setup):
    """The padded compiled bucket call and the unpadded eager forward both
    match the float32 oracle on the real block: pad rows and sink edges
    never reach a real row."""
    spec, g, _ = graph_setup
    eng = _graph_engine(graph_setup)
    eng.warmup()
    rng = np.random.default_rng(3)
    for s in (1, 4, 13):
        prep = eng.prepare(rng.choice(g.num_vertices, size=s, replace=False))
        assert prep.bucket is not None
        compiled = eng.run_prepared(prep)
        assert compiled.shape == (s, spec.num_classes)
        ref = _block_reference(eng, prep)
        assert_allclose_dtype(compiled, ref)
        assert_allclose_dtype(eng.run_eager(prep), ref)


def test_graph_bucket_donation_no_retrace_and_exact(graph_setup):
    """Bucket callables compile with donate=True by default -- each call
    pads a FRESH feature buffer, so donation must neither retrace nor
    move the padded result off the float32 oracle."""
    spec, g, _ = graph_setup
    eng = _graph_engine(graph_setup)
    assert eng.donate is True                       # the default
    eng.warmup()
    assert all(fn.donate for fn in eng._fns.values())
    rng = np.random.default_rng(11)
    for s in (2, 4, 2, 9, 4):                       # sustained bucket reuse
        prep = eng.prepare(rng.choice(g.num_vertices, size=s,
                                      replace=False))
        assert prep.bucket is not None
        compiled = eng.run_prepared(prep)
        assert_allclose_dtype(compiled, _block_reference(eng, prep))
    assert eng.retraces() == 0                      # one trace per bucket
    # opting out still works (callers that reuse x across calls)
    eng2 = _graph_engine(graph_setup, donate=False)
    eng2.warmup()
    assert all(not fn.donate for fn in eng2._fns.values())


def test_graph_slot_reuse(graph_setup):
    spec, g, _ = graph_setup
    eng = _graph_engine(graph_setup, max_batch=2)
    eng.warmup()
    for i in range(7):
        eng.submit(GraphRequest(rid=i, seeds=np.array([i, i + 1], np.int32)))
    done = eng.run()
    assert {r.rid for r in done} == set(range(7))
    s = eng.stats()
    assert s["served"] == 7 and s["queued"] == 0 and s["active"] == 0
    # 2 slots served 7 requests: every request got a slot, steps batched
    assert s["slot_assignments"] == 7
    assert s["steps"] < s["served"]
    for r in done:
        assert r.logits.shape == (2, spec.num_classes)
        assert np.isfinite(r.logits).all()


def test_graph_warmup_once_and_zero_retraces(drained_engine):
    eng, traces, done = drained_engine
    assert len(eng.buckets) <= 4
    assert traces == {eng._bucket_name(b): 1 for b in eng.buckets}
    assert eng.warmup() == traces          # idempotent: no second trace
    s = eng.stats()
    assert s["served"] == len(done) == 200
    assert s["retraces"] == 0 and s["bucket_misses"] == 0
    assert s["bucket_hits"] == 200
    assert all(b["compiled"] == 1 for b in s["buckets"])


def test_graph_latency_percentiles_monotone(drained_engine):
    eng, _, _ = drained_engine
    s = eng.stats()
    assert 0 < s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    assert s["throughput_rps"] > 0


def test_graph_bucket_miss_eager_path_and_cache_sweep(graph_setup):
    spec, g, _ = graph_setup
    # one bucket too small for any 2-seed request: every request misses,
    # is served eagerly, and the transient plans trip the watermark sweep
    eng = _graph_engine(graph_setup, buckets=[(1, 2, 1)], max_batch=2,
                        plan_cache_watermark=2)
    eng.warmup()
    for i in range(6):
        eng.submit(GraphRequest(rid=i,
                                seeds=np.array([i, i + 1], np.int32)))
    done = eng.run()
    s = eng.stats()
    assert s["bucket_misses"] == 6 and s["bucket_hits"] == 0
    assert all(r.bucket is None for r in done)
    for r in done:
        assert r.logits.shape == (2, spec.num_classes)
    assert s["cache_sweeps"] >= 2          # warmup pin + watermark sweeps
    assert s["plan_cache"]["size"] <= 1 + 2 * eng.max_batch
    assert s["plan_cache"]["evictions"] >= 1


def test_plan_cache_stats_and_eviction(graph_setup):
    spec, g, x = graph_setup
    clear_plan_cache()
    assert plan_cache_stats() == {"size": 0, "limit": 64, "blocked_size": 0,
                                  "reorder_size": 0, "hits": 0, "misses": 0,
                                  "evictions": 0}
    p1 = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                    spec.num_classes, backend="xla", fused=False)
    assert plan_cache_stats()["misses"] == 1
    assert build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                      spec.num_classes, backend="xla", fused=False) is p1
    assert plan_cache_stats()["hits"] == 1
    build_plan(g, PAPER_MODELS["gcn"], spec.feature_len, spec.num_classes,
               backend="xla", fused=False, ordering=AGGREGATE_FIRST)
    assert plan_cache_stats()["size"] == 2
    clear_plan_cache(keep=[p1])            # explicit eviction policy
    s = plan_cache_stats()
    assert s["size"] == 1 and s["evictions"] >= 1
    assert build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                      spec.num_classes, backend="xla", fused=False) is p1
    clear_plan_cache()                     # full wipe resets the counters
    assert plan_cache_stats()["size"] == 0
    assert plan_cache_stats()["hits"] == 0


def test_plan_cache_eviction_accounting(graph_setup):
    """``clear_plan_cache(keep=...)`` counts EVERY dropped cache line --
    plan entries plus the blocked/reorder layouts swept with them -- and
    the hit/miss counters survive the eviction cycle."""
    spec, g, x = graph_setup
    clear_plan_cache()
    p_keep = build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                        spec.num_classes, backend="xla", fused=False)
    # a second graph seeds blocked (fused pallas) and reorder (degree)
    # cache lines -- all swept together with its plan entries
    spec2 = dataclasses.replace(spec, seed=spec.seed + 1)
    g2 = make_synthetic_graph(spec2)
    build_plan(g2, PAPER_MODELS["gcn"], spec.feature_len, spec.num_classes,
               backend="pallas-tpu", fused=True)
    build_plan(g2, PAPER_MODELS["gcn"], spec.feature_len, spec.num_classes,
               backend="xla", fused=False, reorder="degree")
    s0 = plan_cache_stats()
    assert s0["blocked_size"] >= 1 and s0["reorder_size"] >= 1
    dropped = clear_plan_cache(keep=[p_keep])
    s1 = plan_cache_stats()
    assert dropped == s0["size"] - 1
    # every dropped line counted, plan entries AND swept layouts
    assert s1["evictions"] == \
        dropped + s0["blocked_size"] + s0["reorder_size"]
    assert s1["size"] == 1
    assert s1["blocked_size"] == 0 and s1["reorder_size"] == 0
    # hit/miss counters accumulate ACROSS the sweep: the kept plan is
    # still a cache hit afterwards
    assert s1["hits"] == s0["hits"] and s1["misses"] == s0["misses"]
    assert build_plan(g, PAPER_MODELS["gcn"], spec.feature_len,
                      spec.num_classes, backend="xla", fused=False) is p_keep
    assert plan_cache_stats()["hits"] == s0["hits"] + 1
    clear_plan_cache()


def test_graph_workload_report_golden_schema(drained_engine):
    eng, _, _ = drained_engine
    report = eng.workload_report()         # .validate() runs inside
    d = json.loads(report.to_json())
    golden = json.loads(GOLDEN.read_text())
    assert sorted(d) == golden["top_serving"]
    assert sorted(d["serving"]) == golden["serving"]
    for b in d["serving"]["buckets"]:
        assert sorted(b) == golden["serving_bucket"]
    assert d["serving"]["requests"] == 200
    assert d["serving"]["bucket_misses"] == 0
    assert d["serving"]["retraces"] == 0
    assert "Serving: 200 requests" in report.to_markdown()

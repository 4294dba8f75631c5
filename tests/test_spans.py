"""The span and counter registry (``repro.profile.spans``) and what the
planned forward and the serving engine record in it: spans at layer
boundaries, named scopes in the traced program, layout counters."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CORA, reduced_graph
from repro.core.plan import build_plan
from repro.graph.datasets import make_features, make_synthetic_graph
from repro.graph.structure import graph_from_coo
from repro.kernels.ops import gather_tail
from repro.kernels.ref import gcn_forward_ref
from repro.models.gcn import PAPER_MODELS
from repro.profile import spans as reg
from repro.serve import GraphRequest, GraphServeEngine
from tolerance import assert_allclose_dtype


@pytest.fixture(autouse=True)
def clean_registry():
    reg.reset()
    yield
    reg.reset()


def _small_graph(v=200, e=800, seed=0):
    rng = np.random.default_rng(seed)
    return graph_from_coo(rng.integers(0, v, e), rng.integers(0, v, e), v)


# --------------------------------------------------------------------------
# the registry
# --------------------------------------------------------------------------


def test_nested_spans_record_parent_and_times():
    with reg.span("outer") as outer:
        with reg.span("inner", rid=7) as inner:
            pass
        with reg.span("inner", rid=7):
            pass
    got = reg.spans()
    assert [s.name for s in got] == ["inner", "inner", "outer"]
    assert got[0].id == inner.id and got[2].id == outer.id
    assert outer.parent is None
    assert all(s.parent == outer.id for s in got[:2])
    assert all(s.attrs == {"rid": 7} for s in got[:2])
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert reg.spans("inner")[1].seconds >= 0


def test_span_records_when_its_body_raises():
    with pytest.raises(ValueError):
        with reg.span("fails"):
            raise ValueError("boom")
    assert [s.name for s in reg.spans()] == ["fails"]
    with reg.span("after") as after:
        pass
    assert after.parent is None          # the failed span left the stack


def test_parent_is_per_thread():
    import threading
    seen = {}

    def work():
        with reg.span("other") as sp:
            seen["parent"] = sp.parent
    with reg.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert seen["parent"] is None


def test_ring_is_bounded():
    assert reg.RING >= 65536
    for i in range(reg.RING + 10):
        reg.record("r", float(i), float(i) + 1.0)
    assert len(reg.spans()) == reg.RING
    got = reg.spans("r")                # collections may hold a few slots
    assert got[0].t0 >= 10.0 and got[-1].t0 == reg.RING + 9.0


def test_since_filter_keeps_spans_that_start_later():
    for t in (1.0, 2.0, 3.0):
        reg.record("s", t, t + 0.5, rid=int(t))
    assert [s.attrs["rid"] for s in reg.spans("s", since=2.0)] == [2, 3]
    assert reg.spans("s", since=9.0) == []
    assert reg.spans("nothing") == []


def test_counters_and_gauges():
    reg.count("c")
    reg.count("c", 4)
    reg.gauge("g", 3.5)
    reg.gauge("g", 2.0)
    assert reg.counters() == {"c": 5, "g": 2.0}
    snap = reg.counters()
    snap["c"] = 0                        # a copy
    assert reg.counters()["c"] == 5
    reg.reset()
    assert reg.counters() == {} and reg.spans() == []


def test_gc_is_recorded_under_the_open_span():
    with reg.span("work") as work:
        gc.collect(1)
    got = reg.spans("host.gc")
    assert got and all(s.parent == work.id for s in got)
    assert any(s.attrs["gen"] == 1 for s in got)
    assert all(work.t0 <= s.t0 <= s.t1 <= work.t1 for s in got)


def test_lazy_exports_from_the_package():
    import repro.profile as prof
    assert prof.span is reg.span and prof.counters is reg.counters
    assert prof.spans is reg             # the reader is the submodule's


# --------------------------------------------------------------------------
# the planned forward
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gin_plan():
    g = _small_graph()
    plan = build_plan(g, PAPER_MODELS["gin"], 40, 3, backend="pallas-tpu")
    params = plan.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (g.num_vertices, 40))
    return plan, params, x


def test_one_call_span_with_one_guard_child_per_call(gin_plan):
    plan, params, x = gin_plan
    fn = plan.compile()
    fn(params, x).block_until_ready()
    reg.reset()
    for _ in range(3):
        fn(params, x).block_until_ready()
    calls, guards = reg.spans("plan.call"), reg.spans("plan.guard")
    assert len(calls) == len(guards) == 3
    for c, gd in zip(calls, guards):
        assert gd.parent == c.id
        assert c.t0 <= gd.t0 <= gd.t1 <= c.t1
    assert fn.num_traces == 1


def test_op_scopes_name_gather_pad_and_combine(gin_plan):
    """Gather, kernel and combine are ops of their scopes.  The ``pad``
    scope pads only the plan's ids and mask, which are constants of the
    executable, so all it leaves are constants: the rows are gathered
    straight into the padded slots and no pad of them is left to run."""
    plan, params, x = gin_plan
    scopes = plan.compile().op_scopes(params, x)
    paths = set(scopes.values())
    for want in ("l0.aggregate/gather", "l0.aggregate/seg_agg", "l0.combine",
                 "l1.aggregate/gather", "l1.aggregate/seg_agg", "l1.combine"):
        assert want in paths, sorted(paths)
    padded = [op for op, p in scopes.items() if p.endswith("/pad")]
    assert all(op.startswith("constant") for op in padded), padded
    assert all("(" not in p for p in paths)


def test_hlo_op_scopes_parses_metadata():
    from repro.core.plan import hlo_op_scopes
    text = "\n".join([
        '  %fusion.1 = f32[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(fwd)/l0.aggregate/gather/jit(_take)/gather"'
        ' source_file="x.py" source_line=3}',
        '  ROOT %pad.6 = f32[2,8]{1,0} pad(%a, %b), padding=0_0x0_4, '
        'metadata={op_type="pad" op_name="jit(fwd)/l0.aggregate/pad/pad"}',
        '  %add.2 = f32[2]{0} add(%x, %y), metadata={op_name="jit(fwd)/add"}',
        '  %p = f32[8,4]{1,0} parameter(0)'])
    assert hlo_op_scopes(text) == {"fusion.1": "l0.aggregate/gather",
                                   "pad.6": "l0.aggregate/pad"}


@pytest.mark.parametrize("fused", [False, True], ids=["seg_agg", "fused"])
def test_layout_counts_match_the_traced_kernel_operands(fused):
    """``describe()``'s counts are the shapes the traced forward gathers
    and pads, layer by layer, and the trace publishes them as gauges."""
    g = _small_graph(v=300, e=1500, seed=2)
    plan = build_plan(g, PAPER_MODELS["gcn"], 24, 5, backend="pallas-tpu",
                      fused=fused)
    params = plan.init(jax.random.PRNGKey(0))
    x = jnp.ones((g.num_vertices, 24), jnp.float32)
    hlo = plan.compile().lower(params, x).as_text()
    for lp, d in zip(plan.layers, plan.describe()):
        assert d["agg_edges"] == g.num_edges
        bg = lp.blocked if fused else lp.agg_layout
        emax_p = d["agg_kernel_slots"] // bg.nblocks
        assert d["agg_gather_rows"] == bg.nblocks * (
            emax_p + gather_tail(bg.nblocks, emax_p))
        width = lp.din if fused or lp.order == "aggregate_first" \
            else lp.dout
        assert d["agg_gather_bytes"] == d["agg_gather_rows"] * width * 4
        assert f"tensor<{bg.nblocks}x1x{emax_p}xf32>" in hlo   # mask
        assert f"tensor<{d['agg_gather_rows']}x{width}xf32>" in hlo
    counts = reg.counters()
    for i, d in enumerate(plan.describe()):
        for k in ("edges", "gather_rows", "kernel_slots", "gather_bytes"):
            assert counts[f"agg.{k}.l{i}"] == d[f"agg_{k}"]


def test_xla_layers_count_no_layout():
    g = _small_graph()
    plan = build_plan(g, PAPER_MODELS["gcn"], 24, 5, backend="xla")
    for d in plan.describe():
        assert d["agg_edges"] == d["agg_kernel_slots"] == 0


# --------------------------------------------------------------------------
# the serving engine
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    spec = reduced_graph(CORA, max_vertices=220, max_feature=24)
    g, x = make_synthetic_graph(spec), make_features(spec)
    eng = GraphServeEngine(g, PAPER_MODELS["gcn"], None, x,
                           spec.num_classes, fanouts=(3, 3), max_batch=2)
    eng.params = eng.init_params(jax.random.PRNGKey(0))
    eng.warmup()
    reg.reset()
    rng = np.random.default_rng(5)
    for i in range(5):
        eng.submit(GraphRequest(rid=i, seeds=rng.choice(
            g.num_vertices, size=3, replace=False)))
    done = eng.run()
    return eng, done, reg.spans(), reg.counters()


SERVE_CHILDREN = {"serve.admit": ("serve.sample", "serve.union"),
                  "serve.dispatch": ("serve.pad", "serve.transfer",
                                     "serve.execute", "serve.readback")}


def test_served_request_records_its_spans(served):
    eng, done, spans, _ = served
    assert {r.rid for r in done} == set(range(5))
    for rid in range(5):
        mine = [s for s in spans if s.attrs.get("rid") == rid]
        names = [s.name for s in mine]
        for top in ("serve.queue", "serve.admit", "serve.dispatch"):
            assert names.count(top) == 1, names
        by_name = {s.name: s for s in mine}
        for top, children in SERVE_CHILDREN.items():
            for child in children:
                assert names.count(child) == 1, names
                assert by_name[child].parent == by_name[top].id
        q, a, d = (by_name[n] for n in ("serve.queue", "serve.admit",
                                        "serve.dispatch"))
        assert q.t1 <= a.t0 <= a.t1 <= d.t0


def test_served_request_clock_is_the_spans_clock(served):
    eng, done, spans, _ = served
    for r in done:
        q = next(s for s in spans if s.name == "serve.queue"
                 and s.attrs["rid"] == r.rid)
        d = next(s for s in spans if s.name == "serve.dispatch"
                 and s.attrs["rid"] == r.rid)
        assert r.enqueue_t == q.t0 and r.finish_t == d.t1
    lat = sorted(r.finish_t - r.enqueue_t for r in done)
    assert eng.stats()["p50_ms"] == pytest.approx(1e3 * np.median(lat))


def test_served_logits_unchanged(served):
    eng, done, _, _ = served
    for r in done:
        prep = r.prep
        ref = gcn_forward_ref(prep.graph.src, prep.graph.dst,
                              prep.graph.num_vertices, eng.cfg, eng.params,
                              eng.features[prep.frontier])
        assert_allclose_dtype(r.logits, np.asarray(ref)[prep.seed_pos])
        # the bucket call on the padded block, made directly
        _, fn = eng._bucket_plan(r.bucket)
        x, src, dst, deg = (jnp.asarray(a)
                            for a in eng._pad_into(prep, r.bucket))
        g = prep.graph._replace(src=src, dst=dst, in_deg=deg, out_deg=deg,
                                num_vertices=r.bucket.num_inputs)
        direct = np.asarray(fn(eng.params, x, g))[prep.seed_pos]
        np.testing.assert_array_equal(r.logits, direct)


def test_serving_counters(served):
    eng, done, _, counters = served
    pad = sum(r.bucket.num_inputs - r.frontier_size for r in done)
    assert counters["serve.pad_rows"] == pad
    h2d = sum(r.bucket.num_inputs * (eng.in_dim * 4 + 4)
              + 2 * r.bucket.num_edges * 4 for r in done)
    assert counters["serve.h2d_bytes"] == h2d

"""Overlapped halo pipelining: pricing, plan threading, and the bitwise
schedule-equivalence regression (multi-device parts run in SUBPROCESSES
with 8 fake CPU devices, same rule as tests/test_distributed.py)."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.config import CORA, reduced_graph
from repro.core.distributed import (OVERLAP_SAVING_THRESHOLD, choose_overlap,
                                    overlap_model)
from repro.core.plan import build_plan
from repro.graph.datasets import make_features, make_synthetic_graph
from repro.graph.partition import partition_1d
from repro.launch.mesh import make_mesh
from repro.models.gcn import PAPER_MODELS
from repro.profile.machine import TPU_V5E, TPU_V5P

from test_distributed import run_sub


@pytest.fixture(scope="module")
def pg249():
    """8-way 1-D partition of a V=249 graph -- 249 % 8 != 0, so every
    shard's last rows are padding."""
    spec = reduced_graph(CORA, 249, 32)
    g = make_synthetic_graph(spec)
    return spec, g, partition_1d(g, 8, edge_balanced=False)


# ---------------------------------------------------------------------------
# pricing: overlap_model / choose_overlap
# ---------------------------------------------------------------------------


def test_overlap_model_per_hop_terms(pg249):
    """The model prices ONE link per hop: wire time is hop_time(per-hop
    slab bytes), exposure is hops * wire single-buffered and
    hops * max(0, wire - comp) pipelined."""
    _, _, pg = pg249
    m = overlap_model(pg, 64, TPU_V5E)
    assert m["strategy"] == "ring" and m["hops"] == 7
    assert m["bytes_per_hop"] == pg.block_size * 64 * 4
    assert m["t_wire_hop_s"] == pytest.approx(
        TPU_V5E.hop_time(m["bytes_per_hop"]))
    assert m["exposed_none_s"] == pytest.approx(7 * m["t_wire_hop_s"])
    hidden = min(m["t_wire_hop_s"], m["t_comp_hop_s"])
    assert m["overlapped_pipelined_s"] == pytest.approx(7 * hidden)
    assert m["exposed_pipelined_s"] == pytest.approx(
        m["exposed_none_s"] - m["overlapped_pipelined_s"])
    assert m["t_none_s"] == pytest.approx(
        7 * m["t_comp_hop_s"] + m["exposed_none_s"])
    # the all-gather strategy is one fused collective: nothing to pipeline
    ag = overlap_model(pg, 64, TPU_V5E, strategy="allgather")
    assert ag["overlapped_pipelined_s"] == 0.0


def test_choose_overlap_flips_with_interconnect_speed(pg249):
    """Satellite: the pricing decision is a genuine function of the
    Machine's link speed -- slower links expose more wire time per hop, so
    hiding it behind the hop's combine work clears the saving threshold;
    fast-enough links make pipelining pointless."""
    _, _, pg = pg249
    lens = [64, 16]
    assert choose_overlap(pg, lens, TPU_V5E) == "pipelined"
    # v5p's 2x-fatter ICI links shrink the wire term below the threshold:
    # the SAME workload flips to single-buffered on the faster machine
    assert choose_overlap(pg, lens, TPU_V5P) == "none"
    fast = dataclasses.replace(TPU_V5E, interconnect_bw=1e18,
                               link_latency_s=0.0)
    assert choose_overlap(pg, lens, fast) == "none"
    # threshold semantics: the v5e saving actually clears the 2% bar
    tot_none = sum(overlap_model(pg, f, TPU_V5E)["t_none_s"] for f in lens)
    tot_hidden = sum(overlap_model(pg, f, TPU_V5E)["overlapped_pipelined_s"]
                     for f in lens)
    assert tot_hidden >= OVERLAP_SAVING_THRESHOLD * tot_none
    # no per-hop structure / nothing moving => never pipeline
    assert choose_overlap(pg, lens, TPU_V5E, strategy="allgather") == "none"
    pg1 = partition_1d(pg249[1], 1, edge_balanced=False)
    assert choose_overlap(pg1, lens, TPU_V5E) == "none"
    # int shorthand == one-element sequence
    assert choose_overlap(pg, 64, TPU_V5E) == \
        choose_overlap(pg, [64], TPU_V5E)


# ---------------------------------------------------------------------------
# plan threading: validation, describe(), cache key
# ---------------------------------------------------------------------------


def test_build_plan_overlap_validation(pg249):
    spec, g, _ = pg249
    cfg = PAPER_MODELS["gcn"]
    with pytest.raises(ValueError, match="overlap"):
        build_plan(g, cfg, spec.feature_len, spec.num_classes,
                   overlap="sometimes")
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="requires strategy='ring'"):
        build_plan(g, cfg, spec.feature_len, spec.num_classes, mesh=mesh,
                   strategy="allgather", overlap="pipelined")
    # a LOCAL plan has no collective to overlap: the knob resolves to none
    local = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                       overlap="pipelined")
    assert local.overlap == "none"


def test_overlap_in_describe_and_cache_key(pg249):
    spec, g, _ = pg249
    cfg = PAPER_MODELS["gcn"]
    mesh = make_mesh((1,), ("data",))
    kw = dict(mesh=mesh, num_shards=1, strategy="ring")
    p_none = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                        overlap="none", **kw)
    p_pipe = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                        overlap="pipelined", **kw)
    assert p_none is not p_pipe              # overlap is in the cache key
    assert p_none is build_plan(g, cfg, spec.feature_len, spec.num_classes,
                                overlap="none", **kw)   # cache hit
    assert p_pipe.overlap == "pipelined"
    for d in p_pipe.describe():
        assert d["overlap"] == "pipelined"
    for d in p_none.describe():
        assert d["overlap"] == "none"
    # "auto" stores the RESOLVED schedule, never the literal request
    p_auto = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                        overlap="auto", **kw)
    assert p_auto.overlap in ("none", "pipelined")


# ---------------------------------------------------------------------------
# the bitwise regression: V % shards != 0, eager AND compiled, 1-D and 2-D
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["xla", "pallas-tpu"])
def test_overlapped_halo_bitwise_with_ragged_padding(backend):
    """Satellite regression: with V=249 on 8 shards every device block
    ends in padding rows; the pipelined schedule must produce the SAME
    BITS as the single-buffered one (pad rows never enter a hop's partial
    combine -- their mask zeroes them in _hop_partial), eager and
    compiled, 1-D and 2-D, on either hop kernel (``segment_sum``, or
    ``seg_agg`` on the ``pallas-tpu`` tier), and the instrumented report
    must carry the matching exposed/overlapped split."""
    out = run_sub(f"""
        backend = {backend!r}
        """ + """
        import dataclasses
        from repro.config import CORA, reduced_graph
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.core.plan import build_plan
        from repro.models.gcn import PAPER_MODELS
        from repro.profile.machine import TPU_V5E
        spec = reduced_graph(CORA, 249, 32)       # 249 % 8 == 1
        g = make_synthetic_graph(spec); x = make_features(spec)
        cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
        local = build_plan(g, cfg, spec.feature_len, spec.num_classes)
        params = local.init(jax.random.PRNGKey(0))
        ref = np.asarray(local.run_model(params, x))
        meshes = {"1d": make_mesh((8,), ("data",)),
                  "2d": make_mesh((4, 2), ("node", "feat"))}
        for kind, mesh in meshes.items():
            outs = {}
            for ov in ("none", "pipelined"):
                plan = build_plan(g, cfg, spec.feature_len,
                                  spec.num_classes, mesh=mesh,
                                  strategy="ring", overlap=ov,
                                  backend=backend)
                assert plan.overlap == ov
                with mesh:
                    rep = plan.instrument(machine=TPU_V5E).run_model(
                        params, x)
                    rep.validate()
                    assert not rep.mismatches(plan), (kind, ov)
                    fn = plan.compile()
                    comp = np.asarray(fn(params, x))
                    fn(params, x)
                    assert fn.num_traces == 1, (kind, ov)
                eager = np.asarray(rep.output)
                for got in (comp, eager):
                    assert_matches_reference(got, g, cfg, params, x,
                                             scale=10,
                                             err_msg=f"{kind}/{ov}")
                outs[ov] = eager
                exp = sum(r.exposed_collective_time for r in rep.records)
                hid = sum(r.overlapped_collective_time
                          for r in rep.records)
                assert exp > 0, (kind, ov)
                assert (hid > 0) == (ov == "pipelined"), (kind, ov)
                # correctness vs the unsharded reference: pad rows never
                # contaminate real rows (float tolerance: different
                # reduction grouping than the local plan is expected)
                err = np.abs(outs[ov] - ref).max()
                assert err < 1e-3, (kind, ov, err)
            assert np.array_equal(outs["none"], outs["pipelined"]), kind
        print("OK")
    """)
    assert "OK" in out

"""Multi-device tests: run in SUBPROCESSES with 8 fake CPU devices so the
main pytest process keeps its single real device (per the dry-run rule)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")
TESTS = str(Path(__file__).resolve().parent)  # tolerance.py for subprocesses


def child_env(*pythonpath: str) -> dict:
    """Environment of a virtual-device child: CPU only, so a child started
    after the parent has touched JAX can never try to load the TPU."""
    return {"PYTHONPATH": os.pathsep.join(pythonpath),
            "PATH": os.environ.get("PATH", ""),
            "HOME": os.environ.get("HOME", ""),
            "JAX_PLATFORMS": "cpu"}


def run_sub(body: str):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from tolerance import assert_allclose_dtype, assert_matches_reference
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True,
                         env=child_env(SRC, TESTS),
                         timeout=600)
    assert res.returncode == 0, f"subprocess failed:\n{res.stderr[-3000:]}"
    return res.stdout


@pytest.mark.slow
def test_distributed_aggregation_strategies():
    out = run_sub("""
        from repro.config import CORA, reduced_graph
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.graph.partition import partition_1d
        from repro.core.distributed import (aggregate_allgather,
            aggregate_ring, pad_features)
        from repro.core.phases import aggregate
        mesh = make_mesh((8,), ("data",))
        spec = reduced_graph(CORA, 300, 32)
        g = make_synthetic_graph(spec); x = make_features(spec)
        pg = partition_1d(g, 8, edge_balanced=False)
        xp = pad_features(x, pg.block_size, 8)
        ref = aggregate(g, x, op="sum", include_self=False)
        with mesh:
            a1 = aggregate_allgather(pg, xp, mesh)[:g.num_vertices]
            a2 = aggregate_ring(pg, xp, mesh)[:g.num_vertices]
        assert_allclose_dtype(a1, ref, scale=10)
        assert_allclose_dtype(a2, ref, scale=10)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_distributed_phase_ordering_halo_reduction():
    """Cluster Table 4: combine-first shrinks halo bytes by in/out ratio."""
    out = run_sub("""
        from repro.config import GraphSpec
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.graph.partition import partition_1d
        from repro.core.distributed import (distributed_gcn_layer,
            pad_features, halo_bytes)
        from repro.core.phases import phase_ordered_layer
        spec = GraphSpec("t", 256, 64, 2048)
        g = make_synthetic_graph(spec); x = make_features(spec)
        pg = partition_1d(g, 8, edge_balanced=False)
        xp = pad_features(x, pg.block_size, 8)
        w = jnp.asarray(np.random.default_rng(0).standard_normal(
            (64, 16)) * 0.2, jnp.float32)
        b = jnp.zeros(16)
        mesh = make_mesh((8,), ("data",))
        ref = phase_ordered_layer(g, x, [(w, b)], order="combine_first",
                                  agg_op="mean", activation="none")
        with mesh:
            for order in ("combine_first", "aggregate_first"):
                for strat in ("ring", "allgather"):
                    o = distributed_gcn_layer(pg, xp, w, b, g.in_deg, mesh,
                        order=order, strategy=strat)[:g.num_vertices]
                    assert_allclose_dtype(o, ref, scale=100,
                                          err_msg=f"{order}/{strat}")
        hb_in = halo_bytes(pg, 64)["min_halo_bytes"]
        hb_out = halo_bytes(pg, 16)["min_halo_bytes"]
        assert hb_in / hb_out == 4.0   # in_len/out_len = 64/16
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_distributed_plan_matches_local():
    """A mesh-built GraphExecutionPlan runs the whole model sharded and
    matches the local (single-device) plan output."""
    out = run_sub("""
        from repro.config import CORA, reduced_graph
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.core.plan import build_plan
        from repro.models.gcn import PAPER_MODELS
        import dataclasses
        spec = reduced_graph(CORA, 300, 32)
        g = make_synthetic_graph(spec); x = make_features(spec)
        cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
        mesh = make_mesh((8,), ("data",))
        local = build_plan(g, cfg, spec.feature_len, spec.num_classes)
        dist = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                          mesh=mesh, num_shards=8, strategy="ring")
        assert dist.distributed and not local.distributed
        params = local.init(jax.random.PRNGKey(0))
        ref = local.run_model(params, x)
        with mesh:
            out = dist.run_model(params, x)
        assert out.shape == ref.shape
        assert_allclose_dtype(out, ref, scale=100)
        # ordering decisions stay cost-model driven in the sharded plan:
        # both layers shrink (32->16->7) => combine-first halo everywhere
        assert [lp.order for lp in dist.layers] == ["combine_first"] * 2
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_distributed_2d_plan_matches_local():
    """A 2-D (node x feature) mesh plan equals the unsharded reference for
    both orderings and both halo strategies, and its per-device halo bytes
    shrink by the feature-shard count Q vs the 1-D partition."""
    out = run_sub("""
        import dataclasses
        from repro.config import CORA, reduced_graph
        from repro.graph.datasets import make_synthetic_graph, make_features
        from repro.graph.partition import partition_1d, partition_2d
        from repro.core.distributed import (distributed_gcn_layer_2d,
            halo_bytes, halo_bytes_2d, pad_features_2d)
        from repro.core.plan import build_plan
        from repro.models.gcn import PAPER_MODELS
        spec = reduced_graph(CORA, 300, 32)
        g = make_synthetic_graph(spec); x = make_features(spec)
        cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
        local = build_plan(g, cfg, spec.feature_len, spec.num_classes)
        params = local.init(jax.random.PRNGKey(0))
        ref = local.run_model(params, x)
        # ordering=None resolves to one of the two explicit orders (covered
        # below); the (2, 4) shape and cost-model ordering are exercised by
        # the dry-run partition matrix (benchmarks/bench_plan.py) on every
        # smoke run -- keep this sweep inside run_sub's 600 s budget
        combos = [((4, 2), "ring"), ((4, 2), "allgather")]
        for shape, strat in combos:
            mesh = make_mesh(shape, ("node", "feat"))
            for order in ("combine_first", "aggregate_first"):
                plan = build_plan(g, cfg, spec.feature_len,
                                  spec.num_classes, mesh=mesh,
                                  strategy=strat, ordering=order)
                assert plan.partition_kind == "2d"
                with mesh:
                    out = plan.run_model(params, x)
                assert out.shape == ref.shape
                assert_allclose_dtype(out, ref, scale=100,
                                      err_msg=f"{shape}/{strat}/{order}")
        # bare-layer entry: padded layout in, padded layout out
        p2 = partition_2d(g, 4, 2)
        mesh = make_mesh((4, 2), ("node", "feat"))
        w = jnp.asarray(np.random.default_rng(0).standard_normal(
            (32, 16)) * 0.2, jnp.float32)
        b = jnp.zeros(16)
        from repro.core.phases import phase_ordered_layer
        lref = phase_ordered_layer(g, x, [(w, b)], order="combine_first",
                                   agg_op="mean", activation="none")
        with mesh:
            lo = distributed_gcn_layer_2d(p2, pad_features_2d(x, p2), w, b,
                g.in_deg, mesh, order="combine_first")
        assert_allclose_dtype(lo[:g.num_vertices, :16], lref, scale=100)
        # Q-fold halo saving on top of Table 4's in/out ratio
        pg = partition_1d(g, 4, edge_balanced=False)
        assert halo_bytes_2d(p2, 32)["min_halo_bytes"] * 2 == \
            halo_bytes(pg, 32)["min_halo_bytes"]
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_allreduce_matches_mean():
    out = run_sub("""
        from jax.sharding import Mesh
        from repro.optim.compression import (make_compressed_allreduce,
            init_residuals)
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)}
        res = init_residuals(g)
        ar = make_compressed_allreduce(mesh, "data")
        with mesh:
            out, res2 = ar(g, res)
        # every shard held the same replica here, so mean == input (up to
        # int8 quantization); residual carries the quantization error
        err = np.abs(np.asarray(out["w"] - g["w"])).max()
        scale = np.abs(np.asarray(g["w"])).max() / 127
        assert err <= scale * 1.01 + 1e-6
        recon = np.asarray(out["w"]) + np.asarray(res2["w"])
        assert np.abs(recon - np.asarray(g["w"])).max() < 1e-5
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_ctx_parallel_attention_sharded():
    out = run_sub("""
        from repro.launch.sharding import sharding_rules, DEFAULT_RULES
        from repro.nn.attention import flash_attention_xla, direct_attention
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((2, 14, 512, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 2, 512, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 2, 512, 32)), jnp.float32)
        rules = dict(DEFAULT_RULES)
        rules.update({"heads": None, "kv_heads": None, "seq": ("model",),
                      "seq_q": ("model",), "mlp": None, "vocab": None})
        with mesh, sharding_rules(mesh, rules):
            f = lambda q, k, v: flash_attention_xla(
                q, k, v, causal=True, q_chunk=64, kv_chunk=64)
            o1 = jax.jit(f)(q, k, v)
            g1 = jax.jit(jax.grad(
                lambda q, k, v: f(q, k, v).sum() * 0.01,
                argnums=(0, 1, 2)))(q, k, v)
        o2 = direct_attention(q, k, v, causal=True, window=0, cap=0.0)
        g2 = jax.grad(lambda q, k, v: direct_attention(
            q, k, v, causal=True, window=0, cap=0.0).sum() * 0.01,
            argnums=(0, 1, 2))(q, k, v)
        assert np.abs(np.asarray(o1 - o2)).max() < 1e-4
        for a, b in zip(g1, g2):
            assert np.abs(np.asarray(a - b)).max() < 1e-5
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_lm_train_step_matches_single_device():
    """pjit train step on a 4x2 mesh == single-device step (same math)."""
    out = run_sub("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import granite_3_8b
        from repro.config import OptimizerConfig
        from repro.launch.sharding import sharding_rules, rules_for
        from repro.launch.specs import param_pspecs, state_pspecs
        from repro.launch.steps import make_train_step
        from repro.models.transformer import init_lm
        from repro.optim.optimizer import make_train_state
        cfg = dataclasses.replace(granite_3_8b.reduced(), dtype="float32")
        opt = OptimizerConfig(warmup_steps=1, total_steps=10)
        params = init_lm(cfg, jax.random.PRNGKey(0))
        state = make_train_state(params, opt)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        step = make_train_step(cfg, opt)
        s_ref, m_ref = jax.jit(step)(state, batch)
        mesh = make_mesh((4, 2), ("data", "model"))
        with mesh, sharding_rules(mesh, rules_for(cfg, mesh)):
            st_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 state_pspecs(jax.eval_shape(
                                     lambda: state), mesh),
                                 is_leaf=lambda x: isinstance(x, P))
            bt_sh = {"tokens": NamedSharding(mesh, P("data", None)),
                     "labels": NamedSharding(mesh, P("data", None))}
            jstep = jax.jit(step, in_shardings=(st_sh, bt_sh))
            s_sh, m_sh = jstep(jax.device_put(state, st_sh),
                               {k: jax.device_put(v, bt_sh[k])
                                for k, v in batch.items()})
        l1 = float(np.asarray(m_ref["loss"]))
        l2 = float(np.asarray(m_sh["loss"]))
        assert abs(l1 - l2) < 1e-3, (l1, l2)
        d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         s_ref.params, jax.device_get(s_sh.params))
        assert max(jax.tree.leaves(d)) < 1e-3
        print("OK")
    """)
    assert "OK" in out

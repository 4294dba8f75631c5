import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Distributed GCN training demo: a mesh-built GraphExecutionPlan
(shard_map vertex partitioning) + int8 error-feedback gradient compression
(DESIGN.md §6).

8 placeholder devices on CPU (the same code drives a real (data,) mesh):
  * ``build_plan(..., mesh=mesh, num_shards=8)`` owns the 1-D partition,
    the per-layer phase ordering (cost model prices the halo: combine-first
    moves 16-wide projected rows, not 64-wide inputs -- the Table 4
    collective saving), and the ring-halo aggregation strategy,
  * per-shard gradients reduced with int8 error feedback (4x wire bytes
    reduction vs fp32; unbiased over time).

  PYTHONPATH=src python examples/distributed_gcn.py
"""

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import CORA, reduced_graph  # noqa: E402
from repro.core.distributed import halo_bytes, halo_bytes_2d  # noqa: E402
from repro.core.plan import build_plan  # noqa: E402
from repro.graph.datasets import (make_features, make_labels,  # noqa: E402
                                  make_synthetic_graph)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.gcn import PAPER_MODELS  # noqa: E402
from repro.optim.compression import (compression_wire_bytes,  # noqa: E402
                                     init_residuals,
                                     make_compressed_allreduce)


def main():
    spec = reduced_graph(CORA, max_vertices=512, max_feature=64)
    g = make_synthetic_graph(spec)
    x = make_features(spec)
    y = make_labels(spec)
    x = x.at[:, :spec.num_classes].add(
        4.0 * jax.nn.one_hot(y, spec.num_classes))

    mesh = make_mesh((8,), ("data",))
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(16,))
    plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                      mesh=mesh, num_shards=8, strategy="ring")
    pg = plan.partition
    hb_in = halo_bytes(pg, spec.feature_len)["min_halo_bytes"]
    hb_out = halo_bytes(pg, 16)["min_halo_bytes"]
    print(f"partition: 8 shards x {pg.block_size} vertices, "
          f"halo {hb_in:,} B (agg-first) vs {hb_out:,} B (combine-first) "
          f"-> {hb_in / hb_out:.1f}x collective saving")
    for d in plan.describe():
        print(f"  layer{d['layer']}: {d['din']}->{d['dout']} "
              f"order={d['order']} (planned)")

    params = plan.init(jax.random.PRNGKey(0))

    def loss_fn(p):
        logits = plan.run_model(p, x)
        ll = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(ll, y[:, None], axis=-1)[:, 0]
        return nll.mean()

    allreduce = make_compressed_allreduce(mesh, "data")
    residuals = init_residuals(params)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    wire = compression_wire_bytes(
        sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params)), dp=8)
    print(f"grad wire bytes/step: fp32 {wire['fp32_bytes']:,.0f} -> "
          f"int8+EF {wire['int8_ef_bytes']:,.0f} "
          f"({wire['reduction_vs_fp32']:.0f}x)")

    lr = 0.25
    with mesh:
        for step in range(30):
            loss, grads = grad_fn(params)
            grads, residuals = allreduce(grads, residuals)  # int8 EF wire
            params = jax.tree.map(lambda p_, g_: p_ - lr * g_, params,
                                  grads)
            if step % 5 == 0:
                print(f" step {step:2d}  loss {float(loss):.4f}")

        logits = plan.run_model(params, x)
    acc = float((jnp.argmax(logits, -1) == y).mean())
    print(f"final accuracy {acc:.3f} (chance {1 / spec.num_classes:.3f})")

    # --- the same model on a 2-D (node x feature) mesh -------------------
    # The multi-host shape: node axis across hosts (halo bytes / Q), the
    # feature axis across intra-host links (the combine reduce-scatter stays
    # local).
    mesh2 = make_mesh((4, 2), ("node", "feat"))
    plan2 = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                       mesh=mesh2, strategy="ring")
    hb1 = halo_bytes(plan.partition, 16)["min_halo_bytes"]
    hb2 = halo_bytes_2d(plan2.partition, 16)["min_halo_bytes"]
    print(f"2-D partition {plan2.partition_kind}: 4 node x 2 feat shards, "
          f"per-device halo {hb2:,} B vs {hb1:,} B 1-D "
          f"(columns ride {plan2.partition.feature_block(16)} wide)")
    with mesh2:
        logits2 = plan2.run_model(params, x)
    drift = float(jnp.abs(logits2 - logits).max())
    print(f"2-D forward matches 1-D-trained logits (max |diff| {drift:.2e})")


if __name__ == "__main__":
    main()

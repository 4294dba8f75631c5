"""Traffic kind ``mesh_forward``: full-graph forwards, back to back, on a
mesh of the cell's chips.

Set-up builds the configuration's plan on a one-axis mesh of the first
``chips`` devices (``build_plan`` with the file's ``plan`` options: mesh
shape and axis, halo strategy and overlap) and compiles it through
``plan.compile()``.  The features are padded to the partition's rows and
placed on the mesh, sharded by destination block, and the parameters are
replicated on it; then the forward is called twice.  The window calls
that same callable on those resident inputs, one forward after another,
each ended by ``block_until_ready``, so that no feature row moves but
through the plan's halo.  ``forward_ms`` is the window's length over the
forwards completed in it.

``correct`` compares the logits of every vertex from the window's first
and last forwards with the plain float32 reference, computed on the first
chip once the program's state is freed.  The control (read only when
asked) is the program's own bfloat16 plan on the same mesh.

Traffic parameters: none.
"""

from __future__ import annotations

import numpy as np

from chipbench.harness import (Check, Context, Outcome, free_program,
                               model_config, program_graph)
from chipbench.inputs import device_inputs
from chipbench.reference import forward_device, logit_err


def _plan(cfg, g, mesh, dtype):
    from repro.core.plan import build_plan
    gs, p = cfg["graph"], cfg["plan"]
    return build_plan(g, model_config(cfg), gs["feature_len"],
                      gs["num_classes"], backend=p["backend"], dtype=dtype,
                      mesh=mesh, axis=p["axis"], strategy=p["strategy"],
                      overlap=p["overlap"])


def _place(mesh, axis, rows, params, x):
    """``(params, x)`` on the mesh: the parameters replicated, the
    features padded to ``rows`` and sharded by row block over ``axis``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    xs = jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))
    return (jax.device_put(params, NamedSharding(mesh, PartitionSpec())),
            jax.device_put(xs, NamedSharding(mesh, PartitionSpec(axis,
                                                                 None))))


def run(ctx: Context) -> Outcome:
    import jax
    from repro.launch.mesh import make_mesh
    cfg = ctx.config
    gs, p = cfg["graph"], cfg["plan"]
    devices = jax.devices()[:ctx.cell["chips"]]
    mesh = make_mesh(p["mesh"], (p["axis"],), devices=devices)
    g, src, dst = program_graph(cfg)
    plan = _plan(cfg, g, mesh, p["dtype"])
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0))
    params, x = device_inputs(ctx.seed, gs["num_vertices"],
                              gs["feature_len"], shapes)
    pg = plan.partition
    rows = pg.block_size * pg.num_shards
    args = _place(mesh, p["axis"], rows, params, x)
    fn = plan.compile()
    for _ in range(2):
        fn(*args).block_until_ready()
    desc = plan.describe()
    counters = {"backend": [d["backend"] for d in desc],
                "order": [lp.order for lp in plan.layers],
                "partition": plan.partition_kind,
                "strategy": plan.strategy, "overlap": plan.overlap,
                "shard_rows": pg.block_size,
                "hop_slots": int(pg.bucket_src.shape[-1]),
                "dtype": plan.dtype, "traces": fn.num_traces}
    n, first = 0, None
    with ctx.window() as w:
        while w.is_open():
            with ctx.span("bench.forward"):
                out = fn(*args)
                out.block_until_ready()
            n += 1
            if first is None:
                first = out
    forward_ms = w.elapsed * 1e3 / n
    counters["traces_after_window"] = fn.num_traces
    ctx.read_memory(devices)
    outs = [np.asarray(first), np.asarray(out)]
    del fn, plan, first, out, args
    free_program()

    model = cfg["model"]
    ref = forward_device(src, dst, gs["num_vertices"], model, params, x)
    err = max(logit_err(o, ref) for o in outs)
    control = {}
    if ctx.control:
        plan = _plan(cfg, g, mesh, "bf16")
        args = _place(mesh, p["axis"], rows, params, x)
        low = np.asarray(plan.compile()(*args))
        control["logit_err"] = logit_err(low, ref)
        del plan, args
        free_program()
    return Outcome(metrics={"forward_ms": forward_ms}, attempted=n,
                   failed=0,
                   checks=[Check("logit_err", err, ctx.limit("logit_err"))],
                   counters=counters, control=control)

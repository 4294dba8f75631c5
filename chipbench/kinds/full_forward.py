"""Traffic kind ``full_forward``: full-graph forwards, back to back.

Set-up builds the configuration's plan (``build_plan`` with the file's
``plan`` options), compiles it through ``plan.compile()`` and calls it
twice.  The window then calls that same callable on the whole graph, one
forward after another, each ended by ``block_until_ready``.
``forward_ms`` is the window's length over the forwards completed in it.

``correct`` compares the logits of every vertex from the window's first
and last forwards with the plain float32 reference, computed on the chip
in edge chunks once the program's state is freed.  The control (read only
when asked) is the program's own bfloat16 path, ``dtype="bf16"``.

Traffic parameters: none.
"""

from __future__ import annotations

import numpy as np

from chipbench.harness import (Check, Context, Outcome, free_program,
                               model_config, program_graph)
from chipbench.inputs import device_inputs
from chipbench.reference import forward_device, logit_err


def _plan(cfg, g, dtype):
    from repro.core.plan import build_plan
    gs, p = cfg["graph"], cfg["plan"]
    return build_plan(g, model_config(cfg), gs["feature_len"],
                      gs["num_classes"], backend=p["backend"], dtype=dtype)


def run(ctx: Context) -> Outcome:
    import jax
    cfg = ctx.config
    gs = cfg["graph"]
    g, src, dst = program_graph(cfg)
    plan = _plan(cfg, g, cfg["plan"]["dtype"])
    shapes = jax.eval_shape(plan.init, jax.random.PRNGKey(0))
    params, x = device_inputs(ctx.seed, gs["num_vertices"],
                              gs["feature_len"], shapes)
    fn = plan.compile()
    for _ in range(2):
        fn(params, x).block_until_ready()
    desc = plan.describe()
    counters = {"backend": [d["backend"] for d in desc],
                "order": [lp.order for lp in plan.layers],
                "fused": [d["fused"] for d in desc],
                "interpret": plan.interpret, "dtype": plan.dtype,
                "traces": fn.num_traces}
    n, first = 0, None
    with ctx.window() as w:
        while w.is_open():
            with ctx.span("bench.forward"):
                out = fn(params, x)
                out.block_until_ready()
            n += 1
            if first is None:
                first = out
    forward_ms = w.elapsed * 1e3 / n
    counters["traces_after_window"] = fn.num_traces
    ctx.read_memory(jax.devices()[:1])
    outs = [np.asarray(first), np.asarray(out)]
    del fn, plan, first, out
    free_program()

    model = cfg["model"]
    ref = forward_device(src, dst, gs["num_vertices"], model, params, x)
    err = max(logit_err(o, ref) for o in outs)
    control = {}
    if ctx.control:
        plan = _plan(cfg, g, "bf16")
        low = np.asarray(plan.compile()(params, x))
        control["logit_err"] = logit_err(low, ref)
        del plan
        free_program()
    return Outcome(metrics={"forward_ms": forward_ms}, attempted=n,
                   failed=0,
                   checks=[Check("logit_err", err, ctx.limit("logit_err"))],
                   counters=counters, control=control)

"""Traffic kind ``open_loop``: node-prediction requests to
``GraphServeEngine`` on a fixed Poisson schedule.

Traffic parameters:

* ``rate_rps``: mean arrival rate; the window holds ``round(rate ·
  seconds)`` requests, due over ``[0, seconds]``;
* ``seeds_per_request``: ``[lo, hi]``, seed vertices per request, uniform;
* ``mix_seed``: the stream that draws the arrival times and the request
  sizes.  A run's ``--seed`` picks only the seed vertices: latency at this
  load swings with the order of arrivals far more than between two runs
  of one order, so every seed offers the same schedule;
* ``drain_s``: how long after the last arrival a request may still finish;
  one that has not is failed;
* ``check_requests``: how many finished requests ``correct`` compares.

Each request is submitted when it falls due (the loop sleeps only when
nothing is queued or in flight) and the engine is ticked; a request's
latency runs from its due time to the return of the ``tick()`` that hands
back its logits.  ``loadgen.late`` records how late each submit was.

``correct``: a sample of finished requests drawn from the seed, the one
with the most edges among them.  Each one's sampled block is checked
against the benchmark's own edge list (every edge a graph edge or a
self-loop, every seed where the block says) and its seed logits are
compared with the plain reference computed over that block in float64.
The control is that reference with bfloat16 operands and float32 sums.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.harness import (Check, Context, Outcome, model_config,
                               program_graph)
from chipbench.inputs import device_inputs
from chipbench.reference import forward_host, logit_err


def schedule(traffic, seconds: float, seed: int, num_vertices: int):
    """``(due seconds, seed-vertex arrays)`` of one run's requests."""
    n = max(1, round(traffic["rate_rps"] * seconds))
    mix = np.random.default_rng(traffic["mix_seed"])
    gaps = mix.exponential(1.0, n)
    due = np.cumsum(gaps * (seconds / gaps.sum()))
    lo, hi = traffic["seeds_per_request"]
    sizes = mix.integers(lo, hi + 1, n)
    rng = np.random.default_rng(seed)
    seeds = [rng.choice(num_vertices, size=int(s), replace=False)
             for s in sizes]
    return due, seeds


def percentile_ms(samples_s, q: float) -> float:
    """Linear-interpolated percentile in ms; no samples is an error."""
    if len(samples_s) == 0:
        raise ValueError("no latency samples")
    return float(np.percentile(np.asarray(samples_s, np.float64) * 1e3, q))


def _serve(ctx: Context, eng, due, seed_sets, drain_s: float):
    """The measured window; returns ``(finished requests, latencies)``."""
    from repro.serve import GraphRequest
    n = len(due)
    finish = {}
    done = []
    late = ctx.spans.setdefault("loadgen.late", [])
    with ctx.window() as w:
        t0, i = w.start, 0
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                eng.submit(GraphRequest(rid=i, seeds=seed_sets[i]))
                late.append(now - due[i])
                i += 1
            if eng.outstanding:
                for req in eng.tick():
                    finish[req.rid] = time.perf_counter() - t0
                    done.append(req)
            elif i < n:
                time.sleep(max(0.0, due[i] - now))
            else:
                break
            if now > due[-1] + drain_s:
                break
    lat = [finish[r.rid] - due[r.rid] for r in done]
    return done, lat


def _block_errors(req, keys, num_vertices: int) -> int:
    """How many ways the request's sampled block departs from the graph:
    edges that are neither graph edges nor self-loops, plus seeds not at
    their rows."""
    prep = req.prep
    fr = np.asarray(prep.frontier, np.int64)
    s = fr[np.asarray(prep.graph.src)]
    d = fr[np.asarray(prep.graph.dst)]
    k = d * num_vertices + s
    pos = np.minimum(np.searchsorted(keys, k), len(keys) - 1)
    bad = int(((keys[pos] != k) & (s != d)).sum())
    seeds = np.asarray(req.seeds, np.int64)
    return bad + int((fr[np.asarray(prep.seed_pos)] != seeds).sum())


def run(ctx: Context) -> Outcome:
    import jax
    from repro.serve import GraphRequest, GraphServeEngine
    from repro.serve.graph_engine import default_buckets
    cfg, traffic = ctx.config, ctx.traffic
    gs, sv = cfg["graph"], cfg["serve"]
    v = gs["num_vertices"]
    g, src, dst = program_graph(cfg)
    due, seed_sets = schedule(traffic, ctx.seconds, ctx.seed, v)
    probe = GraphServeEngine(g, model_config(cfg), None,
                             np.zeros((1, gs["feature_len"]), np.float32),
                             gs["num_classes"], fanouts=tuple(sv["fanouts"]))
    # the parameter layout is the program's; build_plan cannot run under
    # eval_shape, so the probe's tiny init is run and only its shapes kept
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        probe.init_params(jax.random.PRNGKey(0)))
    params, x = device_inputs(ctx.seed, v, gs["feature_len"], shapes)
    x_host = np.asarray(x)
    del probe, x
    eng = GraphServeEngine(
        g, model_config(cfg), params, x_host, gs["num_classes"],
        buckets=default_buckets(tuple(sv["fanouts"]), sv["seed_levels"],
                                max_inputs=v),
        fanouts=tuple(sv["fanouts"]), max_batch=sv["max_batch"],
        seed=ctx.seed)
    eng.warmup()
    warm = np.random.default_rng(ctx.seed)
    for rid, s in enumerate(sv["seed_levels"]):
        eng.submit(GraphRequest(rid=-1 - rid,
                                seeds=warm.choice(v, s, replace=False)))
    eng.run()
    ctx.wrap(eng, "prepare", "bench.admit")
    ctx.wrap(eng, "run_prepared", "bench.dispatch")
    done, lat = _serve(ctx, eng, due, seed_sets, traffic["drain_s"])
    ctx.read_memory(jax.devices()[:1])
    stats = eng.stats()
    counters = {k: stats[k] for k in ("bucket_misses", "retraces",
                                      "bucket_hits")}
    counters["frontier_size_mean"] = float(np.mean(
        [r.frontier_size for r in done])) if done else 0.0
    counters["edge_count_mean"] = float(np.mean(
        [r.edge_count for r in done])) if done else 0.0
    failed = len(due) - len(done)
    metrics = {}
    if lat:
        metrics = {"serve_p50_ms": percentile_ms(lat, 50),
                   "serve_p95_ms": percentile_ms(lat, 95)}

    # correctness, after the window: a seeded sample with the largest block
    pick = np.random.default_rng([ctx.seed, 1])
    sample = []
    if done:
        largest = max(done, key=lambda r: r.edge_count)
        rest = [r for r in done if r is not largest]
        k = min(len(rest), traffic["check_requests"] - 1)
        sample = [largest] + [rest[j] for j in
                              pick.choice(len(rest), k, replace=False)]
    keys = np.sort(np.asarray(dst, np.int64) * v + src)
    params_h = jax.tree_util.tree_map(np.asarray, params)
    model = cfg["model"]
    err = ctl = 0.0
    bad = 0
    for req in sample:
        bad += _block_errors(req, keys, v)
        blk = req.prep.graph
        feats = x_host[req.prep.frontier]
        args = (np.asarray(blk.src), np.asarray(blk.dst), blk.num_vertices,
                model, params_h, feats)
        ref = forward_host(*args)[req.prep.seed_pos]
        err = max(err, logit_err(req.logits, ref))
        if ctx.control:
            low = forward_host(*args, bf16=True)[req.prep.seed_pos]
            ctl = max(ctl, logit_err(low, ref))
    counters["checked_requests"] = len(sample)
    checks = [Check("logit_err", err, ctx.limit("logit_err")),
              Check("bad_block", float(bad), ctx.limit("bad_block")),
              Check("unanswered", float(failed), ctx.limit("unanswered"))]
    return Outcome(metrics=metrics, attempted=len(due), failed=failed,
                   checks=checks, counters=counters,
                   control={"logit_err": ctl} if ctx.control else {})

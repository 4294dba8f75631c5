"""Chip smoke: the planned GCN forward and graph serving, end to end on a TPU.

One process drives the system's main path through the entry points a user
calls (``build_plan`` -> ``plan.compile()``, ``GraphServeEngine``) at the
full published Pubmed size -- 19,717 vertices, 44,338 edges, 500 features
(paper Table 2, generated from its statistics by ``graph/datasets.py``) --
with random weights from a fixed seed.  Every output is compared with the
plain float32 oracle (``repro.kernels.ref.gcn_forward_ref``, matmuls at
"highest") within the bands of ``tests/tolerance.py``.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the partitioned layers only

Phases on one chip:

  (a) GCN, ``backend="auto"`` (must resolve to ``pallas-tpu``), the same
      with ``fused=True``, and ``backend="xla"``;
  (b) GIN, aggregate-first at F=500, ``backend="auto"``;
  (c) ``GraphServeEngine``: warm-up, then 16 requests served to completion
      with zero bucket misses and zero retraces.

With ``--chips 4`` it runs only the partitioned GCN layers and what they are
compared with: a ``(4,)`` ``"data"`` mesh (ring halo, ``overlap`` "none" and
"pipelined") and a ``(2, 2)`` ``("node", "feat")`` mesh, each against the same
model planned on one chip, with a check that every device holds its own
shard.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  No failure is
caught: any of them exits non-zero.  Without a TPU the script exits non-zero
before printing a result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
NUM_REQUESTS = 16
#: f32 band slack: two layers whose sums run in another order than the
#: oracle's (the same slack the suite gives sharded and compiled plans)
F32_SCALE = 10


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _band_check(name: str, out, ref, dtype: str = "f32",
                scale: float = F32_SCALE) -> dict:
    """Assert ``out`` within the dtype band of ``ref``; return the largest
    absolute error and the largest share of the band any element uses
    (<= 1 passes)."""
    import numpy as np
    from tolerance import DTYPE_BANDS, assert_allclose_dtype
    a = np.asarray(out, np.float32)
    r = np.asarray(ref, np.float32)
    if a.shape != r.shape or not np.isfinite(a).all():
        raise AssertionError(f"{name}: shape {a.shape} vs {r.shape}, "
                             f"finite={bool(np.isfinite(a).all())}")
    rtol, atol = (t * scale for t in DTYPE_BANDS[dtype])
    assert_allclose_dtype(a, r, dtype, scale=scale, err_msg=name)
    err = np.abs(a - r)
    return {"max_abs_err": float(err.max()),
            "band_use": float((err / (atol + rtol * np.abs(r))).max())}


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")}


def _forward(name: str, plan, params, x, ref) -> dict:
    """Compile the plan's forward through ``plan.compile()``, run it, and
    hold it to the oracle.  Returns the phase's record."""
    fn = plan.compile()
    t0 = time.perf_counter()
    compiled = fn.lower(params, x).compile()
    compile_s = time.perf_counter() - t0
    out = fn(params, x)
    d0 = plan.describe()[0]
    return {
        "phase": name, "backend": d0["backend"], "fused": d0["fused"],
        "order": [lp.order for lp in plan.layers],
        "tile_m": d0["tile_m"], "interpret": plan.interpret,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "compile_s": compile_s, "num_traces": fn.num_traces,
        **_band_check(name, out, ref), "memory": _memory(compiled),
    }


def _graph(spec):
    from repro.graph.datasets import make_features, make_synthetic_graph
    return make_synthetic_graph(spec), make_features(spec)


def phase_gcn(spec, g, x):
    """(a) GCN: backend auto, auto + fused, and xla."""
    import jax
    from repro.core.plan import build_plan
    from repro.kernels.ref import gcn_forward_ref
    from repro.models.gcn import PAPER_MODELS
    cfg = PAPER_MODELS["gcn"]
    out = []
    ref = None
    for name, kw in (("gcn/auto", {}), ("gcn/auto/fused", {"fused": True}),
                     ("gcn/xla", {"backend": "xla"})):
        plan = build_plan(g, cfg, spec.feature_len, spec.num_classes, **kw)
        params = plan.init(jax.random.PRNGKey(SEED))
        if ref is None:
            ref = gcn_forward_ref(g.src, g.dst, g.num_vertices, cfg, params,
                                  x)
        out.append(_forward(name, plan, params, x, ref))
    return out


def phase_gin(spec, g, x):
    """(b) GIN: aggregate-first at the input width, backend auto."""
    import jax
    from repro.core.plan import build_plan
    from repro.kernels.ref import gcn_forward_ref
    from repro.models.gcn import PAPER_MODELS
    cfg = PAPER_MODELS["gin"]
    plan = build_plan(g, cfg, spec.feature_len, spec.num_classes)
    params = plan.init(jax.random.PRNGKey(SEED))
    ref = gcn_forward_ref(g.src, g.dst, g.num_vertices, cfg, params, x)
    return [_forward("gin/auto", plan, params, x, ref)]


def phase_serve(spec, g, x, num_requests: int = NUM_REQUESTS):
    """(c) GraphServeEngine: warm every bucket, serve requests to
    completion, hold each request's logits to the oracle on its block."""
    import jax
    import numpy as np
    from repro.kernels.ref import gcn_forward_ref
    from repro.models.gcn import PAPER_MODELS
    from repro.serve import GraphRequest, GraphServeEngine
    cfg = PAPER_MODELS["gcn"]
    eng = GraphServeEngine(g, cfg, None, x, spec.num_classes, seed=SEED)
    eng.params = eng.init_params(jax.random.PRNGKey(SEED))
    t0 = time.perf_counter()
    traces = eng.warmup()
    warmup_s = time.perf_counter() - t0
    rng = np.random.default_rng(SEED)
    for rid in range(num_requests):
        seeds = rng.choice(g.num_vertices, size=int(rng.integers(1, 17)),
                           replace=False)
        eng.submit(GraphRequest(rid=rid, seeds=seeds))
    done = eng.run()
    stats = eng.stats()
    if len(done) != num_requests or stats["bucket_misses"] or \
            stats["retraces"]:
        raise AssertionError(f"serve: {len(done)}/{num_requests} done, "
                             f"{stats['bucket_misses']} bucket misses, "
                             f"{stats['retraces']} retraces")
    worst = {"max_abs_err": 0.0, "band_use": 0.0}
    for req in done:
        blk = req.prep.graph
        ref = gcn_forward_ref(blk.src, blk.dst, blk.num_vertices, cfg,
                              eng.params, eng.features[req.prep.frontier])
        err = _band_check(f"serve/request {req.rid}", req.logits,
                          np.asarray(ref)[req.prep.seed_pos])
        worst = {k: max(worst[k], err[k]) for k in worst}
    return [{"phase": "serve", "requests": len(done),
             "bucket_misses": stats["bucket_misses"],
             "retraces": stats["retraces"],
             "buckets_compiled": sum(traces.values()),
             "warmup_s": warmup_s, **worst}]


def phase_mesh(spec, g, x, devices):
    """--chips 4: the 1-D ring (none, pipelined) and the 2-D partition
    against the same model planned on one chip."""
    import jax
    import numpy as np
    from repro.core.distributed import pad_features, pad_features_2d
    from repro.core.plan import build_plan
    from repro.kernels.ref import gcn_forward_ref
    from repro.launch.mesh import make_mesh
    from repro.models.gcn import PAPER_MODELS
    cfg = PAPER_MODELS["gcn"]
    local = build_plan(g, cfg, spec.feature_len, spec.num_classes)
    params = local.init(jax.random.PRNGKey(SEED))
    ref = gcn_forward_ref(g.src, g.dst, g.num_vertices, cfg, params, x)
    one_chip = _forward("mesh/one-chip", local, params, x, ref)
    local_out = np.asarray(local.compile()(params, x))
    records = [one_chip]
    n = len(devices)
    cases = (("ring/none", (n,), ("data",), "none"),
             ("ring/pipelined", (n,), ("data",), "pipelined"),
             ("2d", (n // 2, 2), ("node", "feat"), "none"))
    for name, shape, names, overlap in cases:
        mesh = make_mesh(shape, names, devices=devices)
        plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                          mesh=mesh, strategy="ring", overlap=overlap)
        with mesh:
            rec = _forward(f"mesh/{name}", plan, params, x, ref)
            fn = plan.compile()
            rec["vs_one_chip"] = _band_check(
                f"mesh/{name} vs one chip", fn(params, x), local_out)
            # one planned layer in the partition layout: every device must
            # hold its own block of the output, not a copy on device 0
            h = pad_features_2d(x, plan.partition) if len(names) == 2 \
                else pad_features(x, plan.partition.block_size,
                                  plan.partition.num_shards)
            h1 = plan.compile(layer=0)(params["conv0"], h)
        held = sorted(s.device.id for s in h1.addressable_shards)
        shard_shapes = {tuple(s.data.shape) for s in h1.addressable_shards}
        want = (h1.shape[0] // shape[0],
                h1.shape[1] // (shape[1] if len(shape) == 2 else 1))
        if held != sorted(d.id for d in devices) or shard_shapes != {want}:
            raise AssertionError(f"mesh/{name}: shards {shard_shapes} on "
                                 f"devices {held}; want {want} on each of "
                                 f"{sorted(d.id for d in devices)}")
        text = fn.lower(params, x).compile().as_text()
        rec.update(overlap=plan.overlap, partition=plan.partition_kind,
                   shard_devices=held, shard_shape=list(want),
                   collective_permute="collective-permute" in text,
                   reduce_scatter="reduce-scatter" in text)
        records.append(rec)
    return records


def _require_tpu(chips: int):
    """The device check: a TPU, compiled kernels, enough chips."""
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        raise SystemExit("REPRO_PALLAS_INTERPRET is set: it would force the "
                         "Pallas kernels into interpret mode; unset it")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX runs on "
                         f"{devices[0].platform!r} ({len(devices)} "
                         "device(s)); the chip smoke needs a TPU")
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices, "
                         f"found {len(devices)}")
    return devices[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the partitioned layers on four chips")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    from repro.config import PUBMED
    from repro.launch.cache import enable_compile_cache
    devices = _require_tpu(args.chips)
    _emit({"compile_cache": enable_compile_cache()})
    spec = PUBMED
    g, x = _graph(spec)
    if args.chips == 4:
        records = phase_mesh(spec, g, x, devices)
    else:
        records = phase_gcn(spec, g, x) + phase_gin(spec, g, x) \
            + phase_serve(spec, g, x)
    for rec in records:
        if rec.get("backend") == "pallas-tpu" and (
                rec["interpret"] or not rec["tpu_custom_call"]):
            raise AssertionError(f"{rec['phase']}: the Pallas layer did not "
                                 f"compile for the chip: {rec}")
        _emit(rec)
    if args.chips == 1 and records[0]["backend"] != "pallas-tpu":
        raise AssertionError("backend='auto' did not resolve to pallas-tpu")
    d = devices[0]
    _emit({"ok": True, "device": {"platform": d.platform,
                                  "kind": d.device_kind,
                                  "count": len(devices)}})


if __name__ == "__main__":
    main()

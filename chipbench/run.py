"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics, read from a profiler trace
of the window.  Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.  The numbers compared for
``correct`` are the last lines on standard error and the last key of the
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
WORK_DIR = "chipbench/.work"           # traces, git-ignored
CACHE_DIR = "chipbench/.jax_cache"     # JAX's compile cache, git-ignored


class NoDevice(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices a cell runs on, or ``NoDevice``."""
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        raise NoDevice("REPRO_PALLAS_INTERPRET is set: it would force the "
                       "Pallas kernels into interpret mode")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however short
    its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _per_layer(manifest, cell, ctx, outcome) -> dict:
    from chipbench.manifest import load_module
    from chipbench.metrics_api import Readings
    peaks = json.loads((manifest.root / "chipbench" /
                        "peaks.json").read_text())
    readings = Readings(ctx=ctx, outcome=outcome, peaks=peaks)
    out = {}
    for name in cell["per_layer"]:
        value = load_module(manifest.metric_file(name), name).read(readings)
        if value is not None:
            out[name] = {"value": float(value),
                         "unit": manifest.per_layer[name]["unit"]}
    return out


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, devices=None, control: bool = False,
             t0: float = T0):
    """Run one cell; returns ``(result dict, outcome, context)``.  ``devices``
    None skips the look for a chip (the tests drive a run on the CPU)."""
    from chipbench.harness import Context
    from chipbench.manifest import Manifest, load_module
    manifest = Manifest.load(root)
    cell = manifest.cell(workload)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  work_dir=root / WORK_DIR, t0=t0, control=control)
    kind = cell["traffic_data"]["kind"]
    outcome = load_module(manifest.kind_file(kind), kind).run(ctx)
    import jax
    used = devices if devices is not None else jax.devices()[:cell["chips"]]
    d = used[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(used),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    if trace:
        metrics = _per_layer(manifest, cell, ctx, outcome)
        device.update(busy_s=ctx.summary.busy_s,
                      window_s=ctx.summary.window_s)
    else:
        metrics = {"setup_s": ctx.setup_end - t0}
        metrics.update(outcome.metrics)
        metrics = {k: {"value": float(metrics[k]),
                       "unit": manifest.end_to_end[k]["unit"]}
                   for k in cell["end_to_end"] if k in metrics}
    result = {"correct": all(c.ok for c in outcome.checks),
              "attempted": outcome.attempted, "failed": outcome.failed,
              "metrics": metrics, "device": device}
    if trace:
        s = ctx.summary
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in list(s.op_s.items())[:10]],
            "idle_gaps": [[k, v] for k, v in s.gaps[:10]]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    outcome.counters["compile_events_in_window"] = ctx.compiles
    return result, outcome, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = ROOT
    # run as a script, this file's directory heads sys.path; its modules
    # (trace, inputs, ...) would shadow any top-level module of that name
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != root / "chipbench"]
    sys.path[:0] = [str(root), str(root / "src")]
    # libtpu logs under /tmp unless told otherwise; a run writes only
    # inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench.manifest import Manifest
    cell = Manifest.load(root).cell(args.workload)
    try:
        devices = require_tpu(cell["chips"])
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(root)
    result, outcome, ctx = run_cell(root, args.workload, args.seed,
                                    args.seconds, bool(args.trace),
                                    devices=devices)
    for k, v in sorted(outcome.counters.items()):
        print(f"counter {k}={v}", file=sys.stderr)
    if ctx.summary is not None:
        for k, v in sorted(ctx.summary.idle_by_span.items()):
            print(f"counter idle_s.{k}={v}", file=sys.stderr)
    for c in outcome.checks:
        print(f"check {c.name}={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Static contract verification gate (scripts/smoke.sh leg 4).

Runs ``repro.analysis`` over the FULL static plan matrix -- backend x
fusion x partition x dtype x overlap, local plans plus 1-D and 2-D
shard_map plans on 8 fake CPU devices -- and the AST lint over
``src/repro/``, without executing a single plan.  Rule catalog:
``docs/analysis.md``.

  python scripts/analyze.py --strict     # exit 1 on any error finding
  python scripts/analyze.py --selftest   # every rule must catch its plant
  python scripts/analyze.py --json       # machine-readable report
  python scripts/analyze.py --markdown   # rendered report

``--strict`` is the CI gate: zero error-severity findings on the
shipped tree.  ``--selftest`` seeds one known violation per rule
(``repro.analysis.selftest``) and fails if ANY rule misses its plant --
the gate that keeps the gate honest.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

# 8 fake devices BEFORE jax import: the distributed matrix cells trace
# shard_map programs over a (8,) / (4, 2) mesh
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

LOCAL_BACKENDS = ("xla", "pallas-tpu")
DTYPES = ("f32", "bf16", "int8-agg")
OVERLAPS = ("none", "pipelined")


def _build_matrix():
    """Yield (label, plan, lint kwargs) for every static matrix cell."""
    import dataclasses

    import jax

    from repro.config import CORA, reduced_graph
    from repro.core.plan import build_plan
    from repro.graph.datasets import make_synthetic_graph
    from repro.launch.mesh import make_mesh
    from repro.models.gcn import PAPER_MODELS

    spec = reduced_graph(CORA, 64, 16)
    g = make_synthetic_graph(spec)
    cfg = dataclasses.replace(PAPER_MODELS["gcn"], hidden_dims=(8,))

    # -- local: backend x fusion x dtype; the xla/unfused/f32 cell also
    #    proves the dynamic bucket path edge-content-free
    for backend in LOCAL_BACKENDS:
        for fused in (False, True):
            for dtype in DTYPES:
                plan = build_plan(g, cfg, spec.feature_len,
                                  spec.num_classes, backend=backend,
                                  fused=fused, dtype=dtype)
                dyn = backend == "xla" and not fused and dtype == "f32"
                yield plan, {"dynamic": dyn}

    # -- donation: a cell whose output CAN alias the donated features
    #    (feature_len == num_classes), so the marker must appear
    spec_d = dataclasses.replace(spec, feature_len=spec.num_classes)
    g_d = make_synthetic_graph(spec_d)
    plan = build_plan(g_d, cfg, spec_d.feature_len, spec_d.num_classes)
    yield plan, {"donate": True}

    # -- reorder cell: the permuted ingress/egress must stay trace-pure
    plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                      reorder="degree")
    yield plan, {}

    # -- dedup cells: a fanout-regular block (every dst draws two hub
    #    in-neighbors -> guaranteed matched pairs) where the XLA cell's
    #    trace must show the SHORTENED two-level fold (dedup-accounting)
    #    and the Pallas cell must still pass the general rules
    import numpy as np

    from repro.graph.structure import graph_from_coo
    rng = np.random.default_rng(0)
    hub_pairs = np.array([(a, b) for a in range(4) for b in range(a + 1, 4)])
    sel = hub_pairs[rng.integers(0, len(hub_pairs), spec.num_vertices)]
    g_dd = graph_from_coo(sel.reshape(-1),
                          np.repeat(np.arange(spec.num_vertices), 2),
                          spec.num_vertices)
    for backend in ("xla", "pallas-tpu"):
        plan = build_plan(g_dd, cfg, spec.feature_len, spec.num_classes,
                          backend=backend, dedup="pairs")
        yield plan, {}

    # -- 1-D halo: strategy x overlap x dtype on an (8,) mesh
    mesh = make_mesh((8,), ("data",))
    for overlap in OVERLAPS:
        for dtype in DTYPES:
            plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                              mesh=mesh, overlap=overlap, dtype=dtype)
            yield plan, {}
    plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                      mesh=mesh, strategy="allgather")
    yield plan, {}

    # -- 2-D node x feature partition on a (4, 2) mesh
    mesh2 = make_mesh((4, 2), ("node", "feat"))
    for overlap in OVERLAPS:
        for dtype in DTYPES:
            plan = build_plan(g, cfg, spec.feature_len, spec.num_classes,
                              mesh=mesh2, overlap=overlap, dtype=dtype)
            yield plan, {}


def run_matrix(verbose: bool = False):
    """Lint every matrix cell + the shipped source tree; returns the
    merged AnalysisReport and the number of plan cells."""
    from repro.analysis.ast_lint import lint_tree
    from repro.analysis.jaxpr_lint import lint_plan, plan_label
    from repro.analysis.report import AnalysisReport

    report = AnalysisReport()
    cells = 0
    for plan, kwargs in _build_matrix():
        cells += 1
        if verbose:
            print(f"  lint {plan_label(plan)} {kwargs or ''}")
        report.merge(lint_plan(plan, **kwargs))
    lint_tree(ROOT / "src" / "repro", report)
    return report, cells


def run_selftest() -> int:
    from repro.analysis.selftest import run_selftest as _selftest
    detected, _ = _selftest()
    missed = sorted(r for r, ok in detected.items() if not ok)
    for rule in sorted(detected):
        print(f"  {rule:20s} {'DETECTED' if detected[rule] else 'MISSED'}")
    if missed:
        print(f"analyze --selftest: FAILED ({len(missed)} rule(s) missed "
              f"their plant: {', '.join(missed)})")
        return 1
    print(f"analyze --selftest: OK ({len(detected)} rules caught their "
          "plants; suppression pragma honored)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any error-severity finding")
    ap.add_argument("--selftest", action="store_true",
                    help="seed one violation per rule; fail on any miss")
    ap.add_argument("--json", action="store_true", help="JSON report")
    ap.add_argument("--markdown", action="store_true",
                    help="markdown report")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        return run_selftest()

    report, cells = run_matrix(verbose=args.verbose)
    if args.json:
        print(report.to_json())
    elif args.markdown:
        print(report.to_markdown())
    elif report.findings:
        print(report.render())
    counts = report.counts()
    ok = report.ok(strict=True)
    status = "OK" if ok else "FAILED"
    print(f"analyze: {status} ({cells} plan cells, {counts['error']} "
          f"error(s), {counts['warning']} warning(s), "
          f"{counts['info']} info)")
    if args.strict and not ok:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

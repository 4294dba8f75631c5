"""Seeded-violation self-test: prove every rule still catches its plant.

``scripts/analyze.py --selftest`` (and ``tests/test_analysis.py``) run
one KNOWN violation per rule through the real detection path --
:func:`~repro.analysis.jaxpr_lint.lint_callable` for traced rules,
:func:`~repro.analysis.ast_lint.lint_source` for source rules -- and
fail if any rule misses.  A linter whose rules silently rot is worse
than no linter: this is the gate that keeps the gate honest.

Each ``plant_*`` function returns the :class:`AnalysisReport` its
seeded violation produced; :func:`run_selftest` maps rule id ->
detected and also checks the suppression pragma path (a planted
violation carrying ``# analysis: allow(...)`` must NOT fire).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

from repro.analysis.ast_lint import lint_source
from repro.analysis.jaxpr_lint import (check_collective_bytes,
                                       check_dedup_fold, check_donation,
                                       check_dynamic_consts, lint_callable)
from repro.analysis.report import AnalysisReport


# -- traced plants ----------------------------------------------------------


def plant_no_callbacks() -> AnalysisReport:
    """A pure_callback smuggled into a traced function."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def fn(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    return lint_callable(fn, jnp.ones((4,)), where="plant:no-callbacks")


def plant_no_f64() -> AnalysisReport:
    """An f64 upcast traced while x64 is temporarily enabled."""
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    try:
        return lint_callable(lambda x: x.astype(jnp.float64) + 1.0,
                             jnp.ones((4,), jnp.float32),
                             where="plant:no-f64")
    finally:
        jax.config.update("jax_enable_x64", False)


def plant_bf16_accum() -> AnalysisReport:
    """A bf16 dot WITHOUT the f32 preferred_element_type accumulator."""
    import jax.numpy as jnp
    a = jnp.ones((4, 4), jnp.bfloat16)
    return lint_callable(lambda p, q: jnp.dot(p, q), a, a,
                         where="plant:bf16-f32-accum")


def plant_donation() -> AnalysisReport:
    """A donate=True claim over a lowering that donated nothing."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((8, 8))
    text = jax.jit(lambda v: v * 2.0).trace(x).lower().as_text()
    report = AnalysisReport()
    check_donation(text, True, "plant:donation", report,
                   alias_possible=True)
    return report


def plant_collective_bytes() -> AnalysisReport:
    """A traced ppermute whose bytes contradict the claimed schedule.

    Runs on ONE device (degenerate 1-ring): the extractor still walks
    the shard_map jaxpr and totals the send, so claiming a 2-send
    schedule must produce a finding.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))

    def body(v):
        return jax.lax.ppermute(v, "x", [(0, 0)])

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                       check_vma=False)
    x = jnp.ones((4, 8), jnp.float32)
    closed = jax.make_jaxpr(fn)(x)
    report = AnalysisReport()
    one_send = 4 * 8 * 4  # what the trace actually ships
    check_collective_bytes(closed, {"ppermute": 2 * one_send},
                           "plant:collective-bytes", report)
    return report


def plant_dynamic_edge_free() -> AnalysisReport:
    """A 'dynamic' trace that closes over the template graph's edges."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graph.structure import Graph
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([1, 2, 3, 0], np.int32)
    in_deg = np.ones(4, np.float32)
    g = Graph(src=src, dst=dst, in_deg=in_deg, out_deg=in_deg,
              num_vertices=4)
    baked = jnp.asarray(g.src)  # the violation: template edges as consts

    def fn(x, src_arg, dst_arg):
        return x + jnp.take(x, baked, axis=0).sum()

    closed = jax.make_jaxpr(fn)(jnp.ones((4,)), jnp.asarray(src),
                                jnp.asarray(dst))
    report = AnalysisReport()
    check_dynamic_consts(closed, g, "plant:dynamic-edge-free", report)
    return report


def plant_dedup_accounting() -> AnalysisReport:
    """A dedup='pairs' pricing claim whose trace still runs the NAIVE
    fold: the layout prices the shortened (num_pairs=1, num_edges2=4)
    two-level aggregation, but the traced program segment-sums all 6
    original edges -- the priced FLOP saving is bookkeeping, not work."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graph.dedup import build_dedup_layout
    src = np.array([3, 4, 4, 3, 2, 3], np.int32)
    dst = np.array([0, 0, 1, 1, 2, 2], np.int32)
    lay = build_dedup_layout(src, dst, 6)   # pair (3,4): dsts 0,1 share it
    assert lay.num_pairs == 1 and lay.num_edges2 == 4
    s, d = jnp.asarray(src), jnp.asarray(dst)

    def fn(x):
        return jax.ops.segment_sum(jnp.take(x, s, axis=0), d,
                                   num_segments=6)

    closed = jax.make_jaxpr(fn)(jnp.ones((6, 8)))
    report = AnalysisReport()
    check_dedup_fold(closed, lay, "plant:dedup-accounting", report)
    return report


# -- source plants ----------------------------------------------------------

_SRC_PLANTS = {
    "host-in-trace": (
        "def f(x):\n"
        "    y = jnp.sum(x)\n"
        "    return float(jnp.max(y))\n"),
    "tracer-branch": (
        "def f(x):\n"
        "    s = jnp.sum(x)\n"
        "    if s > 0:\n"
        "        return s\n"
        "    return -s\n"),
    "broadcast-div": (
        "def f(h, deg):\n"
        "    return h / deg[:, None]\n"),
    "acc-dtype": (
        "def k(tile_m, f_in):\n"
        "    return pl.pallas_call(\n"
        "        kern, scratch_shapes=[pltpu.VMEM((tile_m, f_in),\n"
        "                                         jnp.float32)])\n"),
    "grid-arity": (
        "out = pl.pallas_call(\n"
        "    kern, grid=(4, 4),\n"
        "    in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))])\n"),
}


def _plant_source(rule: str) -> Callable[[], AnalysisReport]:
    def run() -> AnalysisReport:
        return lint_source(_SRC_PLANTS[rule], filename=f"plant:{rule}")
    run.__doc__ = f"Throwaway source seeding one {rule} violation."
    return run


#: rule id -> plant callable; every registered rule must appear here
PLANTS: Dict[str, Callable[[], AnalysisReport]] = {
    "no-callbacks": plant_no_callbacks,
    "no-f64": plant_no_f64,
    "bf16-f32-accum": plant_bf16_accum,
    "donation": plant_donation,
    "collective-bytes": plant_collective_bytes,
    "dynamic-edge-free": plant_dynamic_edge_free,
    "dedup-accounting": plant_dedup_accounting,
    **{rule: _plant_source(rule) for rule in _SRC_PLANTS},
}


def check_suppression() -> bool:
    """The pragma path: an allowed plant must NOT fire."""
    src = ("def f(h, deg):\n"
           "    return h / deg[:, None]  # analysis: allow(broadcast-div)\n")
    return not lint_source(src, filename="plant:suppressed").findings


def run_selftest() -> Tuple[Dict[str, bool], AnalysisReport]:
    """Run every plant; returns (rule -> detected, merged report).

    Detected means the plant produced at least one finding FOR ITS OWN
    rule.  The merged report also carries a synthetic
    ``selftest-suppression`` error if the pragma path stopped working.
    """
    merged = AnalysisReport()
    detected: Dict[str, bool] = {}
    for rule, plant in sorted(PLANTS.items()):
        rep = plant()
        detected[rule] = any(f.rule == rule for f in rep.findings)
        merged.merge(rep)
    if not check_suppression():
        merged.add("selftest-suppression", "error", "plant:suppressed",
                   "suppression pragma no longer suppresses findings")
        detected["selftest-suppression"] = False
    return detected, merged

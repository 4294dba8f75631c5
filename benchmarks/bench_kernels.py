"""Beyond-paper: Pallas kernel benchmarks (interpret-mode correctness +
modeled TPU utilization) and the fused-dataflow guideline (paper §5.1-3).

Interpret-mode timing is meaningless for TPU perf; what we measure:
  * XLA path wall-clock for fused vs unfused dataflow (the HBM-traffic
    effect is visible even on CPU),
  * analytic VMEM footprint + MXU-alignment of the kernel tilings against
    the spec's Machine (``ctx.machine.on_chip_bytes``),
  * numerics of the Pallas kernels at benchmark shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import plan_for_phases
from repro.kernels import ops
from repro.kernels.ref import seg_agg_ref
from repro.profile.bench import BenchSpec, run_specs


def _fused_dataflow(ctx, _):
    """Fused vs unfused dataflow (XLA backend), both as planner scenarios."""
    g, x = ctx.g, ctx.x
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128)) * 0.05
    weights = [(w, None)]
    fused_plan = plan_for_phases(g, weights, order="combine_first",
                                 agg_op="mean", backend="xla", fused=True)
    unfused_plan = plan_for_phases(g, weights, order="combine_first",
                                   agg_op="mean", backend="xla")
    fused = jax.jit(lambda xx: fused_plan.run_phases(
        xx, weights, activation="none"))
    unfused = jax.jit(lambda xx: unfused_plan.run_phases(
        xx, weights, activation="none"))
    t_f = ctx.time(fused, x)
    t_u = ctx.time(unfused, x)
    err = float(jnp.abs(fused(x) - unfused(x)).max())
    ctx.emit("kernels/fused_dataflow", t_f,
             unfused_us=round(t_u, 1),
             speedup=round(t_u / max(t_f, 1e-9), 2),
             max_err=f"{err:.1e}", tile_m=fused_plan.layers[0].tile_m)


def _vmem_budgets(ctx, shape):
    """VMEM budget of one kernel tiling (structural roofline input)."""
    fi, fo, tm, te = shape
    vmem_total = ctx.machine.on_chip_bytes
    vmem = (fi * fo + tm * fi + tm * fo + te * fi) * 4
    ctx.emit(f"kernels/fused_vmem_f{fi}", 0.0,
             vmem_bytes=vmem, vmem_frac=round(vmem / vmem_total, 3),
             mxu_aligned=bool(fo % ctx.machine.matrix_tile == 0
                              and tm % ctx.machine.row_align == 0))


def _pallas_numerics(ctx, _):
    """Pallas numerics at benchmark shapes (interpret mode)."""
    rng = np.random.default_rng(0)
    nb, emax, f, tm = 2, 512, 128, 128
    rows = jnp.asarray(rng.standard_normal((nb, emax, f)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, tm, (nb, emax))), jnp.int32)
    mask = jnp.ones((nb, emax), jnp.float32)
    # the rows are already grouped: slot j of block b gathers row b*emax+j
    src = jnp.arange(nb * emax, dtype=jnp.int32).reshape(nb, emax)
    tile_e = ops.kernel_tile_e(tm, f, 4)
    seg3, mask3, src = ops.kernel_edges(seg, mask, tile_e, src)
    out = ops.gather_seg_agg(rows.reshape(-1, f), src, seg3, mask3,
                             tile_m=tm, tile_e=tile_e)
    gseg = (seg + jnp.arange(nb)[:, None] * tm).reshape(-1)
    ref = seg_agg_ref(rows.reshape(-1, f), gseg, mask.reshape(-1), nb * tm)
    ctx.emit("kernels/seg_agg_numerics", 0.0,
             max_err=f"{float(jnp.abs(out - ref).max()):.1e}",
             mxu_reduction=True)


SPECS = [
    BenchSpec(name="kernels/dataflow", graph="reddit", max_vertices=4096,
              max_feature=256, measure=_fused_dataflow),
    BenchSpec(name="kernels/vmem",
              sweep=((602, 128, 128, 512), (256, 128, 256, 512)),
              measure=_vmem_budgets),
    BenchSpec(name="kernels/numerics", measure=_pallas_numerics),
]


def run():
    from repro.profile.bench import BENCH_ARTIFACT_DIR
    run_specs(SPECS, csv=BENCH_ARTIFACT_DIR / "bench_kernels.csv")


if __name__ == "__main__":
    run()

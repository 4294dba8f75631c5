"""Model FLOPs of the forwards completed in the traced window, over the
window's length and the bf16 peak of all the cell's chips, in percent: the
whole step's share of the mesh's peak, host gaps and halo included."""

from chipbench import work


def read(r):
    s = r.summary
    if s is None or s.window_s <= 0 or r.window_count <= 0:
        return None
    flops = work.forward_flops(r.config) * r.window_count
    peak = r.ctx.cell["chips"] * r.peak()["flops_per_s"]
    return 100.0 * flops / s.window_s / peak

"""Edge slots the aggregation kernels read over the edges they aggregate,
summed over the layers: the blocked layout's padding (rows padded to a
block's largest in-degree, then to the kernel's edge tile).  The
program's gauges ``agg.kernel_slots.l<i>`` and ``agg.edges.l<i>``."""

from chipbench.registry import layer_sum


def read(r):
    slots, edges = layer_sum(r, "kernel_slots"), layer_sum(r, "edges")
    if slots is None or not edges:
        return None
    return slots / edges

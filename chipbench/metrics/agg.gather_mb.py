"""Bytes one forward's pre-gather writes (one feature row per edge slot
of the blocked layout, ahead of the aggregation kernels), summed over the
layers, in MB: the program's gauges ``agg.gather_bytes.l<i>``."""

from chipbench.registry import layer_sum


def read(r):
    b = layer_sum(r, "gather_bytes")
    return b / 1e6 if b else None

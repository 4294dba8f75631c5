"""The least time of the aggregation work (adds, and input rows, output
rows and edge indices moved once, per layer), over the device time of the
``seg_agg`` and ``fused_agg_combine`` kernels, in percent.  The
pre-gather before the kernel is not in their time."""


def read(r):
    t = r.kernel_s()
    if t is None or r.window_count <= 0:
        return None
    return 100.0 * r.least_agg_s() * r.window_count / t

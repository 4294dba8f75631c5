"""The least time of the forwards' compulsory work on all the cell's chips
(the larger of model FLOPs over their summed peak and compulsory bytes
over their summed HBM bandwidth), over the per-device busy time in the
traced window, in percent."""


def read(r):
    busy = r.busy_s()
    if busy is None or r.window_count <= 0:
        return None
    least = r.least_forward_s() / r.ctx.cell["chips"]
    return 100.0 * least * r.window_count / busy

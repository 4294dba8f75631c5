"""The least time of the forwards' compulsory work (the larger of model
FLOPs over the peak and compulsory bytes over HBM bandwidth), over the
device's busy time in the traced window, in percent."""


def read(r):
    busy = r.busy_s()
    if busy is None or r.window_count <= 0:
        return None
    return 100.0 * r.least_forward_s() * r.window_count / busy

"""Device time of the ``collective-permute`` ops (the ring's sends and the
waits for them) per device and per forward in the traced window, in ms:
the halo's wire time that no other op on the device hid."""

PREFIX = "collective-permute"


def read(r):
    s = r.summary
    if s is None or r.window_count <= 0:
        return None
    t = sum(sec for name, sec in s.op_s.items() if name.startswith(PREFIX))
    if t <= 0:
        return None
    return 1e3 * t / r.ctx.cell["chips"] / r.window_count

"""Device busy time per forward in the traced window, in ms."""


def read(r):
    busy = r.busy_s()
    if busy is None or r.window_count <= 0:
        return None
    return 1e3 * busy / r.window_count

"""Share of the traced window in which no operation ran on the device, in
percent, in a cell of full-graph forwards."""


def read(r):
    return r.idle_pct()

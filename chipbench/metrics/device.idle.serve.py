"""Share of the traced window in which no operation ran on the device, in
percent, in a serving cell."""


def read(r):
    return r.idle_pct()

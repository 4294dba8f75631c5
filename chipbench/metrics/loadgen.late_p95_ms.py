"""95th percentile of how late the load generator submitted each request
after its due time, in ms (a starved generator reads high here)."""

import numpy as np


def read(r):
    t = r.span("loadgen.late")
    return float(np.percentile(np.asarray(t) * 1e3, 95)) if t else None

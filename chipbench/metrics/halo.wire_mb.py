"""Bytes one device sends over the halo collectives in one forward, summed
over the layers, in MB: the program's gauges ``halo.wire_bytes.l<i>``
(set when the forward is traced)."""

from chipbench.registry import registry


def read(r):
    reg = registry()
    if reg is None:
        return None
    values = reg.counters()
    keys = [f"halo.wire_bytes.l{i}"
            for i in range(r.config["model"]["num_layers"])]
    if not all(k in values for k in keys):
        return None
    return sum(values[k] for k in keys) / 1e6

"""Model FLOPs and compulsory HBM bytes of a configuration, from its sizes.

Counted from V, E, F, the layer widths and the dtype alone -- never from
tiles, padding or the pre-gathered layout -- so that a change to how the
program lays the work out cannot change the yardstick.

* Model FLOPs: ``2·V·a·b`` per ``a×b`` weight matrix, plus one add per
  edge per aggregated feature.  The aggregated width is the one the
  model's maths fixes: a GCN layer may aggregate before or after its one
  linear map, so it counts at the narrower side; GIN sums before its MLP,
  so it counts at the layer's input width.  Bias, ReLU and the mean's
  divide are not counted.
* Compulsory bytes of a forward: every input feature row, weight, bias and
  edge index (source and destination) read once, every output logit
  written once.
* Aggregation work of one layer: its adds, and its input rows read once,
  its output rows written once and its edge indices read once.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def layers(cfg: Dict) -> List[Dict]:
    """Per layer: the widths of its maps (``mlp``) and its aggregated
    width (``agg``)."""
    g, m = cfg["graph"], cfg["model"]
    hid = m["hidden_dims"]
    out = []
    din = g["feature_len"]
    for i in range(m["num_layers"]):
        dout = hid[0] if i < m["num_layers"] - 1 else g["num_classes"]
        mlp = [din, dout] if m["conv"] == "gcn" else [din, hid[-1], dout]
        agg = min(din, dout) if m["conv"] == "gcn" else din
        out.append({"mlp": mlp, "agg": agg})
        din = dout
    return out


def _itemsize(cfg: Dict) -> int:
    return {"f32": 4, "bf16": 2}[cfg["plan"]["dtype"]]


def forward_flops(cfg: Dict) -> float:
    v, e = cfg["graph"]["num_vertices"], cfg["graph"]["num_edges"]
    total = 0
    for lay in layers(cfg):
        total += sum(2 * v * a * b for a, b in zip(lay["mlp"], lay["mlp"][1:]))
        total += e * lay["agg"]
    return float(total)


def forward_bytes(cfg: Dict) -> float:
    g = cfg["graph"]
    v, e = g["num_vertices"], g["num_edges"]
    params = sum(a * b + b for lay in layers(cfg)
                 for a, b in zip(lay["mlp"], lay["mlp"][1:]))
    values = v * g["feature_len"] + params + v * g["num_classes"]
    return float(values * _itemsize(cfg) + 2 * e * 4)


def agg_flops(cfg: Dict) -> float:
    e = cfg["graph"]["num_edges"]
    return float(sum(e * lay["agg"] for lay in layers(cfg)))


def agg_bytes(cfg: Dict) -> float:
    v, e = cfg["graph"]["num_vertices"], cfg["graph"]["num_edges"]
    return float(sum(2 * v * lay["agg"] * _itemsize(cfg) + 2 * e * 4
                     for lay in layers(cfg)))


def least_time(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                             "memory")

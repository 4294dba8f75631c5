"""The plain reference of a GCN-family forward (paper Eq. 1, Table 1).

Every layer reduces over ``N(v) ∪ {v}``: the sum of the in-neighbours'
rows plus the vertex's own row, divided by ``in-degree + 1`` for the mean
aggregator.  Then its combination: ``{"lin": {w, b}}`` is one affine map
(GCN), ``{"mlp1": ..., "mlp2": ...}`` an MLP with ReLU between its maps
(GIN).  ReLU joins the layers.  This is the repository's
``kernels/ref.gcn_forward_ref`` written again here, importing nothing of
the program: it takes the edge list, the features and the weights that
the benchmark made.

Two forms of the same equations:

* ``forward_device``: float32 on the chip, matmuls at ``HIGHEST``, the
  edges in fixed chunks so that it fits beside a graph of Reddit's size
  (one compile per layer width);
* ``forward_host``: numpy, float64 for the reference, or with every
  operand rounded to bfloat16 and float32 sums for the control (a served
  block is small).
"""

from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np


def _mlp(layer_params: Dict) -> List[Dict]:
    if "lin" in layer_params:
        return [layer_params["lin"]]
    return [layer_params[f"mlp{j + 1}"] for j in range(len(layer_params))]


def _check_model(model: Dict) -> None:
    if model["aggregator"] not in ("mean", "sum"):
        raise ValueError(f"no reference for aggregator "
                         f"{model['aggregator']!r}")


#: edges per chunk of the on-chip reference: a chunk's gathered rows at
#: width 602 take 2.4 GB beside the 0.56 GB features of Reddit
CHUNK = 1 << 20


def forward_device(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                   model: Dict, params, x):
    """Float32 logits of the whole graph, computed on the default device."""
    import jax
    import jax.numpy as jnp
    _check_model(model)
    v = int(num_vertices)
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    e = len(src)
    pad = -e % CHUNK
    src_c = np.concatenate([src, np.zeros(pad, np.int32)]).reshape(-1, CHUNK)
    dst_c = np.concatenate([dst, np.full(pad, v, np.int32)]).reshape(-1,
                                                                     CHUNK)
    inv_deg = 1.0 / (np.bincount(dst, minlength=v) + 1.0)
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def agg_chunk(acc, h, s, d):
        # pad edges land in segment v, which is dropped
        return acc + jax.ops.segment_sum(h[s], d, num_segments=v + 1,
                                         indices_are_sorted=True)[:v]

    @functools.partial(jax.jit, static_argnums=3)
    def combine(agg, mlp, inv_deg, last):
        h = agg * inv_deg[:, None] if model["aggregator"] == "mean" else agg
        for j, lin in enumerate(mlp):
            h = jnp.matmul(h, lin["w"], precision=hi) + lin["b"]
            if j < len(mlp) - 1:
                h = jax.nn.relu(h)
        return h if last else jax.nn.relu(h)

    h = jnp.asarray(x, jnp.float32)
    inv_deg = jnp.asarray(inv_deg, jnp.float32)
    for i in range(model["num_layers"]):
        acc = h
        for s, d in zip(src_c, dst_c):
            acc = agg_chunk(acc, h, jnp.asarray(s), jnp.asarray(d))
        h = combine(acc, _mlp(params[f"conv{i}"]), inv_deg,
                    i == model["num_layers"] - 1)
    return np.asarray(h)


def forward_host(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                 model: Dict, params, x, *, bf16: bool = False) -> np.ndarray:
    """Logits of a small graph in numpy: float64, or (``bf16=True``)
    bfloat16 operands with float32 sums -- the control's precision."""
    _check_model(model)
    if bf16:
        import ml_dtypes

        def rnd(a):
            return np.asarray(a, np.float32).astype(
                ml_dtypes.bfloat16).astype(np.float32)
        acc_t = np.float32
    else:
        def rnd(a):
            return np.asarray(a, np.float64)
        acc_t = np.float64
    v = int(num_vertices)
    order = np.argsort(dst, kind="stable")
    src, dst = np.asarray(src)[order], np.asarray(dst)[order]
    deg = np.bincount(dst, minlength=v)
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    has = deg > 0
    h = np.asarray(x, acc_t)
    for i in range(model["num_layers"]):
        rows = rnd(h)
        agg = rows.astype(acc_t).copy()
        if len(src):
            sums = np.add.reduceat(rows[src], starts[has], axis=0)
            agg[has] += sums.astype(acc_t)
        if model["aggregator"] == "mean":
            agg /= (deg + 1.0).astype(acc_t)[:, None]
        mlp = _mlp(params[f"conv{i}"])
        h = agg
        for j, lin in enumerate(mlp):
            h = (rnd(h) @ rnd(lin["w"])).astype(acc_t) \
                + np.asarray(lin["b"], acc_t)
            if j < len(mlp) - 1:
                h = np.maximum(h, 0)
        if i < model["num_layers"] - 1:
            h = np.maximum(h, 0)
    return h


def logit_err(out, ref) -> float:
    """The gap compared against a limit: the largest absolute difference
    of any logit, over the largest absolute reference logit (the largest
    float when the logits are missing, misshapen or not finite)."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape or not np.isfinite(out).all():
        return float(np.finfo(np.float64).max)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))

"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition, written with plain jnp ops and
no tiling -- tests sweep shapes/dtypes and assert_allclose kernels against
these.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def seg_agg_ref(rows: jnp.ndarray, seg_ids: jnp.ndarray, mask: jnp.ndarray,
                num_segments: int) -> jnp.ndarray:
    """Segmented row sum: out[s] = sum_{e: seg_ids[e]==s} rows[e] * mask[e].

    rows: (E, F); seg_ids: (E,) int32 in [0, num_segments); mask: (E,).
    """
    w = rows * mask[:, None].astype(rows.dtype)
    return jax.ops.segment_sum(w, seg_ids, num_segments=num_segments)


def fused_agg_combine_ref(rows: jnp.ndarray, seg_ids: jnp.ndarray,
                          mask: jnp.ndarray, w: jnp.ndarray,
                          num_segments: int) -> jnp.ndarray:
    """out[s] = (sum_{e in seg s} rows[e]) @ w  -- aggregation fused into GEMM."""
    agg = seg_agg_ref(rows, seg_ids, mask, num_segments)
    return agg.astype(w.dtype) @ w


def mha_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
            causal: bool = True, sliding_window: int = 0,
            logit_softcap: float = 0.0, scale: Optional[float] = None,
            kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Reference attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0 (GQA).
    ``kv_len``: optional (B,) valid KV length (decode with padded cache).
    Positions: query i sits at absolute position Sk - Sq + i (decode-style
    right alignment), matching the serving engine's cache layout.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * scale
    if logit_softcap > 0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    sk = k.shape[2]
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)
    # (B, Sq): last q row sits at position kv_len - 1
    qpos = jnp.arange(sq)[None, :] + (kv_len[:, None] - sq)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((b, sq, sk), bool)
    if causal:
        mask &= kpos[:, None, :] <= qpos[:, :, None]
    if sliding_window > 0:
        mask &= kpos[:, None, :] > qpos[:, :, None] - sliding_window
    mask &= (kpos < kv_len[:, None])[:, None, :]
    mask = mask[:, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def gcn_forward_ref(src, dst, num_vertices: int, cfg, params,
                    x: jnp.ndarray) -> jnp.ndarray:
    """Plain float32 forward of a planned GCN-family model (the oracle a
    ``GraphExecutionPlan`` is compared against on any device).

    Every layer reduces over ``{N(v)} ∪ {v}`` with one dense segment sum
    (``mean`` divides by in-degree + 1), then applies its combination:
    ``{"lin": ...}`` is one affine map (GCN/SAGE), ``{"mlp1": ...,
    "mlp2": ...}`` an MLP with ReLU between its matmuls (GIN).  ReLU joins
    the layers.  Matmuls run at ``"highest"`` precision, so on a TPU the
    result is f32-exact rather than one bf16 MXU pass.

    src/dst: the (E,) edge list; ``params``: ``plan.init`` layout
    (``{"conv<i>": ...}``); ``cfg``: the ``GCNModelConfig`` (its
    ``aggregator`` and ``num_layers``).
    """
    if cfg.aggregator not in ("mean", "sum"):
        raise ValueError(f"no reference for aggregator {cfg.aggregator!r}")
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(x, jnp.float32)
        deg = jax.ops.segment_sum(jnp.ones(dst.shape, jnp.float32), dst,
                                  num_segments=num_vertices)
        for i in range(cfg.num_layers):
            p = params[f"conv{i}"]
            agg = jax.ops.segment_sum(h[src], dst,
                                      num_segments=num_vertices) + h
            if cfg.aggregator == "mean":
                agg = agg / (deg + 1.0)[:, None]  # analysis: allow(broadcast-div)
            mlp = [p["lin"]] if "lin" in p else \
                [p[f"mlp{j + 1}"] for j in range(len(p))]
            h = agg
            for j, lin in enumerate(mlp):
                h = h @ lin["w"].astype(jnp.float32) + lin["b"]
                if j < len(mlp) - 1:
                    h = jax.nn.relu(h)
            if i < cfg.num_layers - 1:
                h = jax.nn.relu(h)
    return h

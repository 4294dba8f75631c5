"""Token-choice top-k Mixture-of-Experts layer (kimi-k2 / arctic / jamba).

The dispatch pipeline is deliberately built as the paper's two phases
(DESIGN.md §4): routing produces an irregular token->expert *gather*
(Aggregation-analogue: sort-by-expert + positioned scatter, collision-free by
construction, exactly like the destination-sorted edge layout), and the
expert FFN is a dense grouped GEMM (Combination-analogue).  The same
characterization machinery prices both phases.

Capacity-based, static shapes: tokens beyond an expert's capacity are
dropped (standard top-k MoE training semantics).  With EP over the `model`
mesh axis GSPMD turns the dispatch scatter into an all-to-all.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.launch.sharding import constrain
from repro.nn.layers import init_dense, init_mlp, mlp


def capacity(cfg: MoEConfig, num_tokens: int) -> int:
    c = int(cfg.capacity_factor * num_tokens * cfg.top_k / cfg.num_experts)
    return max(8, -(-c // 8) * 8)


def init_moe(key, d_model: int, cfg: MoEConfig, activation: str,
             dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 4)
    e, f = cfg.num_experts, cfg.expert_d_ff
    gated = activation in ("swiglu", "geglu")
    p = {
        "router": init_dense(ks[0], d_model, e, jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d_model, f), jnp.float32)
               * d_model ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[2], (e, f, d_model), jnp.float32)
               * f ** -0.5).astype(dtype),
    }
    if gated:
        p["wg"] = (jax.random.normal(ks[3], (e, d_model, f), jnp.float32)
                   * d_model ** -0.5).astype(dtype)
    if cfg.dense_residual:
        p["dense"] = init_mlp(jax.random.fold_in(key, 7), d_model,
                              cfg.dense_residual_d_ff, activation, dtype)
    return p


def moe_ffn(params: Dict, x: jnp.ndarray, cfg: MoEConfig, activation: str,
            dropless: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).  Dispatches to the shard_map EP path
    under an active multi-device sharding context (see _moe_sharded);
    single-device (tests, CPU examples) runs the local path below."""
    from repro.launch.sharding import ctx_mesh_axes
    info = ctx_mesh_axes()
    if info is not None:
        mesh, batch_axes, seq_axes = info
        tp = mesh.shape.get("model", 1)
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape[a]
        sp = 1
        for a in seq_axes:
            sp *= mesh.shape[a]
        if (tp > 1 and cfg.num_experts % tp == 0 and
                x.shape[0] % dp == 0 and x.shape[1] % sp == 0 and
                (x.shape[0] * x.shape[1]) // (dp * sp) >= 1):
            return _moe_sharded(params, x, cfg, activation, dropless, mesh,
                                batch_axes, seq_axes)
    return _moe_local(params, x, cfg, activation, dropless)


def _moe_local(params: Dict, x: jnp.ndarray, cfg: MoEConfig, activation: str,
               dropless: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).

    Sorted-segment dispatch (Aggregation phase):
      1. top-k routing; flatten (T*K) assignments,
      2. stable argsort by expert id  == destination-sorted edges,
      3. rank-in-segment via searchsorted == collision-free positions,
      4. scatter into the (E, C, D) dispatch buffer.
    Expert GEMMs (Combination phase) run as dense einsums over experts.

    ``dropless=True`` sizes capacity at the worst case (t*k) so no token is
    ever dropped -- used by the single-token decode path where capacity
    drops would corrupt generation; train/prefill keep the standard
    capacity-factor semantics.
    """
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.num_experts
    c = min(t * k, capacity(cfg, t)) if not dropless else max(8, t * k)
    c = -(-c // 8) * 8
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # -- aux load-balance loss (Switch-style) -------------------------------
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        jnp.ones((t * k,), jnp.float32)) / (t * k)
    aux = cfg.aux_loss_weight * e * jnp.sum(me * ce)

    # -- sorted-segment dispatch (the Aggregation analogue) ------------------
    flat_ids = expert_ids.reshape(-1)                         # (T*K,)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]                              # non-decreasing
    seg_begin = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    pos = jnp.arange(t * k) - seg_begin                       # rank in expert
    keep = pos < c
    tok = order // k                                          # source token
    buf = jnp.zeros((e, c, d), xf.dtype)
    buf = buf.at[sorted_ids, jnp.where(keep, pos, 0)].add(
        xf[tok] * keep[:, None].astype(xf.dtype))

    # -- expert FFN (the Combination analogue) -------------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(buf.dtype),
                   preferred_element_type=jnp.float32).astype(buf.dtype)
    if activation == "swiglu":
        gate_h = jnp.einsum("ecd,edf->ecf", buf,
                            params["wg"].astype(buf.dtype),
                            preferred_element_type=jnp.float32
                            ).astype(buf.dtype)
        h = jax.nn.silu(gate_h) * h
    elif activation == "geglu":
        gate_h = jnp.einsum("ecd,edf->ecf", buf,
                            params["wg"].astype(buf.dtype),
                            preferred_element_type=jnp.float32
                            ).astype(buf.dtype)
        h = jax.nn.gelu(gate_h, approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(h.dtype),
                   preferred_element_type=jnp.float32).astype(h.dtype)

    # -- combine (scatter back, gate-weighted) -------------------------------
    slot_out = y[sorted_ids, jnp.where(keep, pos, 0)]         # (T*K, D)
    gates_sorted = gate_vals.reshape(-1)[order]
    # cast gates BEFORE the multiply: an f32 gate would upcast the whole
    # residual stream (observed: f32 saved layer carries at kimi-k2)
    w = (gates_sorted * keep).astype(slot_out.dtype)
    slot_out = slot_out * w[:, None]
    out = jnp.zeros((t, d), slot_out.dtype).at[tok].add(slot_out)
    out = out.reshape(b, s, d)

    if cfg.dense_residual:
        out = out + mlp(params["dense"], x, activation)
    return out, aux


def _moe_sharded(params: Dict, x: jnp.ndarray, cfg: MoEConfig,
                 activation: str, dropless: bool, mesh, batch_axes,
                 seq_axes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism via shard_map (the production EP flow).

    Per shard: LOCAL routing + sort + dispatch-buffer build (zero comm),
    then one all-to-all over `model` redistributing (E, C_loc) -> experts,
    local grouped GEMMs against the shard's E/tp experts, reverse
    all-to-all, local gate-weighted combine.  GSPMD's scatter-based
    alternative replicates the dispatch buffer (observed 0.5 TiB/device at
    kimi-k2 train_4k); this path wires the canonical a2a instead.
    """
    from jax.sharding import PartitionSpec as P

    bp = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    sp = seq_axes if len(seq_axes) > 1 else (
        seq_axes[0] if seq_axes else None)
    all_axes = tuple(mesh.axis_names)
    gated = activation in ("swiglu", "geglu")

    def local_fn(x_loc, router_w, wi, wo, wg, dense):
        out, aux = _moe_local_with_a2a(
            {"router": {"w": router_w}, "wi": wi, "wo": wo,
             **({"wg": wg} if gated else {}),
             **({"dense": dense} if cfg.dense_residual else {})},
            x_loc, cfg, activation, dropless)
        aux = jax.lax.pmean(aux, all_axes)
        return out, aux

    wg = params.get("wg", jnp.zeros((), x.dtype))
    dense = params.get("dense", jnp.zeros((), x.dtype))
    dense_spec = jax.tree.map(lambda _: P(None, None), dense) \
        if cfg.dense_residual else P()
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bp, sp, None),           # x: tokens sharded dp x seq
                  P(None, None),             # router (gathered)
                  P("model", None, None),    # experts EP over model
                  P("model", None, None),
                  P("model", None, None) if gated else P(),
                  dense_spec),
        out_specs=(P(bp, sp, None), P()),
        check_vma=False,
    )(x, params["router"]["w"], params["wi"], params["wo"], wg, dense)
    return out, aux


def _moe_local_with_a2a(params, x, cfg: MoEConfig, activation: str,
                        dropless: bool):
    """Body run per shard inside shard_map: local dispatch + model-axis a2a.

    params["wi"]/["wo"]/["wg"] hold THIS SHARD's E/tp experts; routing is
    over the full expert id space.
    """
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.num_experts
    e_loc = params["wi"].shape[0]
    tp = e // e_loc
    c = max(8, t * k) if dropless else min(t * k, capacity(cfg, t))
    c = -(-c // 8) * 8
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)

    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        jnp.ones((t * k,), jnp.float32)) / (t * k)
    aux = cfg.aux_loss_weight * e * jnp.sum(me * ce)

    flat_ids = expert_ids.reshape(-1)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    seg_begin = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    pos = jnp.arange(t * k) - seg_begin
    keep = pos < c
    tok = order // k
    buf = jnp.zeros((e, c, d), xf.dtype)
    buf = buf.at[sorted_ids, jnp.where(keep, pos, 0)].add(
        xf[tok] * keep[:, None].astype(xf.dtype))

    # dispatch all-to-all: (E, C, D) -> (E/tp, C*tp, D)
    if tp > 1:
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)

    # expert GEMMs in the model dtype end-to-end: f32 preferred-output here
    # made every backward cotangent f32 (observed: the largest single HBM
    # contributor in the kimi-k2 train profile); TPU MXUs accumulate in f32
    # internally either way.
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(buf.dtype))
    if activation in ("swiglu", "geglu"):
        gate_h = jnp.einsum("ecd,edf->ecf", buf,
                            params["wg"].astype(buf.dtype))
        h = (jax.nn.silu(gate_h) if activation == "swiglu"
             else jax.nn.gelu(gate_h, approximate=True)) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(h.dtype))

    # combine all-to-all back: (E/tp, C*tp, D) -> (E, C, D)
    if tp > 1:
        y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                               tiled=True)

    slot_out = y[sorted_ids, jnp.where(keep, pos, 0)]
    gates_sorted = gate_vals.reshape(-1)[order]
    w = (gates_sorted * keep).astype(slot_out.dtype)
    slot_out = slot_out * w[:, None]
    out = jnp.zeros((t, d), slot_out.dtype).at[tok].add(slot_out)
    out = out.reshape(b, s, d)
    if cfg.dense_residual:
        out = out + mlp(params["dense"], x, activation)
    return out, aux


def moe_flops(cfg: MoEConfig, d_model: int, num_tokens: int,
              activation: str) -> float:
    """Analytic active-FLOPs for one MoE layer (forward)."""
    mats = 3 if activation in ("swiglu", "geglu") else 2
    c = capacity(cfg, num_tokens)
    return 2.0 * cfg.num_experts * c * d_model * cfg.expert_d_ff * mats

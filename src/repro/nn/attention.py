"""GQA attention: projections, chunked (flash-style) XLA path, decode path.

Three execution paths, selected by the model layer:

  * ``direct``      -- materialize (Sq, Sk) scores; small sequences/tests.
  * ``xla_chunked`` -- double-blocked online softmax (lax.map over q chunks,
    lax.scan over kv chunks).  O(chunk^2) live memory; this is what the
    32k-prefill dry-runs lower, keeping peak activation memory in bounds.
    Mirrors the Pallas flash kernel tile-for-tile so the TPU kernel can be
    swapped in (``impl="pallas"``) without touching the model.
  * ``decode``      -- one new token against a padded KV cache (kv_len marks
    validity); pure memory-bound cache sweep.

All paths support GQA grouping WITHOUT materializing repeated K/V (einsum
over a (B, Hkv, G, ...) view) -- with KV sharded over the model axis this
keeps the cache read local.  Causal masking uses decode-style right
alignment (see kernels/flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import AttentionConfig
from repro.launch.sharding import constrain
from repro.nn.layers import apply_rope, init_dense, softcap

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jnp.ndarray       # (B, Hkv, Smax, D)
    v: jnp.ndarray       # (B, Hkv, Smax, D)
    length: jnp.ndarray  # () int32 -- valid entries (uniform across batch)


def init_attention(key, d_model: int, cfg: AttentionConfig,
                   dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d_model, cfg.q_dim, dtype),
        "wk": init_dense(ks[1], d_model, cfg.kv_dim, dtype),
        "wv": init_dense(ks[2], d_model, cfg.kv_dim, dtype),
        "wo": init_dense(ks[3], cfg.q_dim, d_model, dtype,
                         scale=cfg.q_dim ** -0.5),
    }


def _project(params, x, cfg: AttentionConfig, positions):
    """x: (B, S, D) -> q (B,Hq,S,hd), k/v (B,Hkv,S,hd), rope applied."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,df->bsf", x, params["wq"]["w"].astype(x.dtype))
    k = jnp.einsum("bsd,df->bsf", x, params["wk"]["w"].astype(x.dtype))
    v = jnp.einsum("bsd,df->bsf", x, params["wv"]["w"].astype(x.dtype))
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # TP layout: heads over `model` where divisible; otherwise the rules
    # remap to context-parallel (q sequence over `model`, KV replicated).
    q = constrain(q, "batch", "heads", "seq_q", None)
    k = constrain(k, "batch", "kv_heads", None, None)
    v = constrain(v, "batch", "kv_heads", None, None)
    return q, k, v


def _grouped(q, hkv):
    b, hq, s, d = q.shape
    return q.reshape(b, hkv, hq // hkv, s, d)


def direct_attention(q, k, v, *, causal: bool, window: int, cap: float,
                     kv_len=None) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    qg = _grouped(q, hkv).astype(jnp.float32) * d ** -0.5
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    s = softcap(s, cap)
    kvl = jnp.asarray(sk if kv_len is None else kv_len, jnp.int32)
    qpos = jnp.arange(sq) + (kvl - sq)
    kpos = jnp.arange(sk)
    m = kpos[None, :] < kvl
    if causal:
        m = m & (kpos[None, :] <= qpos[:, None])
    if window > 0:
        m = m & (kpos[None, :] > qpos[:, None] - window)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "q_chunk", "kv_chunk"))
def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      cap: float = 0.0, q_chunk: int = 2048,
                      kv_chunk: int = 1024) -> jnp.ndarray:
    """Blockwise online-softmax attention; O(q_chunk*kv_chunk) live scores."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    assert sq % q_chunk == 0 and sk % kv_chunk == 0, (sq, q_chunk, sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = d ** -0.5
    q_off = sk - sq

    qs = q.reshape(b, hkv, g, nq, q_chunk, d).astype(jnp.float32) * scale
    ks = k.reshape(b, hkv, nk, kv_chunk, d).astype(jnp.float32)
    vs = v.reshape(b, hkv, nk, kv_chunk, d).astype(jnp.float32)

    def per_q_chunk(qi):
        qc = qs[:, :, :, qi]                             # (b,hkv,g,qc,d)
        qpos = q_off + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            kc = jax.lax.dynamic_index_in_dim(ks, ki, 2, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vs, ki, 2, keepdims=False)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kc)
            s = softcap(s, cap)
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            m = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                m &= kpos[None, :] <= qpos[:, None]
            if window > 0:
                m &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(m[None, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_run, m_cur)
            m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            p = jnp.exp(s - m_safe)
            p = jnp.where(m[None, None, None], p, 0.0)
            alpha = jnp.exp(jnp.where(m_run <= NEG_INF / 2, NEG_INF,
                                      m_run - m_safe))
            l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vc)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, g, q_chunk, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(nk))
        return acc / jnp.where(l_f == 0.0, 1.0, l_f)

    out = jax.lax.map(per_q_chunk, jnp.arange(nq))       # (nq,b,hkv,g,qc,d)
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, sq, d)
    return out.astype(q.dtype)


def flash_attention_xla(q, k, v, *, causal: bool = True, window: int = 0,
                        cap: float = 0.0, q_chunk: int = 2048,
                        kv_chunk: int = 1024) -> jnp.ndarray:
    """custom-VJP flash attention (nn/flash_vjp.py) on (B,Hq,S,D) layout.

    Under a context-parallel sharding profile (see sharding.rules_for) the
    kernel runs inside shard_map: each `model` shard owns a contiguous slab
    of query positions and attends to the full (replicated) KV.  Chunked
    scans then slice LOCAL arrays only -- GSPMD never sees a dynamic slice
    across a sharded dim (which it would resolve with full gathers).
    """
    from repro.launch.sharding import ctx_parallel_info
    from repro.nn.flash_vjp import flash_mha
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    qg = _grouped(q, hkv) * (d ** -0.5)

    info = ctx_parallel_info()
    if info is not None and sq % info.tp == 0 and (sq // info.tp) >= 128:
        mesh, tp, batch_axes = info.mesh, info.tp, info.batch
        local_sq = sq // tp
        qc = min(q_chunk, local_sq)
        kc = min(kv_chunk, sk)

        def local_attn(qg_l, k_l, v_l):
            idx = jax.lax.axis_index("model").astype(jnp.float32)
            q_start = (sk - sq) + idx * local_sq
            return flash_mha(qg_l, k_l, v_l, q_start, causal, window, cap,
                             qc, kc)

        from jax.sharding import PartitionSpec as P
        bp = batch_axes if batch_axes else None
        out = jax.shard_map(
            local_attn, mesh=mesh,
            in_specs=(P(bp, None, None, "model", None),
                      P(bp, None, None, None),
                      P(bp, None, None, None)),
            out_specs=P(bp, None, None, "model", None),
            check_vma=False)(qg, k, v)
        return out.reshape(b, hq, sq, d)

    qc = min(q_chunk, sq)
    kc = min(kv_chunk, sk)
    # cap the live tile footprint (b * heads * qc * kc): many-KV-head archs
    # (MHA kv=16) would otherwise hold multi-GiB recompute tiles
    while b * hq * qc * kc > (1 << 27) and (qc > 256 or kc > 256):
        if qc >= kc and qc > 256:
            qc //= 2
        elif kc > 256:
            kc //= 2
        else:
            break
    while sq % qc != 0 and qc > 1:
        qc //= 2
    while sk % kc != 0 and kc > 1:
        kc //= 2
    assert sq % qc == 0 and sk % kc == 0, (sq, qc, sk, kc)
    out = flash_mha(qg, k, v, jnp.float32(sk - sq), causal, window, cap,
                    qc, kc)
    return out.reshape(b, hq, sq, d)


def decode_attention(q, cache: KVCache, *, causal: bool = True,
                     window: int = 0, cap: float = 0.0) -> jnp.ndarray:
    """q: (B, Hq, 1, D) against the padded cache; returns (B, Hq, 1, D).

    ``cache.length`` is () for a uniform batch (dry-run decode cells) or
    (B,) for per-slot lengths (serving engine continuous batching).
    """
    b, hq, _, d = q.shape
    hkv, smax = cache.k.shape[1], cache.k.shape[2]
    qg = _grouped(q, hkv).astype(jnp.float32) * d ** -0.5
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, cache.k.astype(jnp.float32))
    s = softcap(s, cap)
    kpos = jnp.arange(smax)
    length = jnp.broadcast_to(cache.length, (b,))
    m = kpos[None, :] < length[:, None]                      # (B, Smax)
    if window > 0:
        m = m & (kpos[None, :] > (length[:, None] - 1 - window))
    s = jnp.where(m[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, cache.v.astype(jnp.float32))
    return o.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block (project -> attend -> out-project), cache-aware
# ---------------------------------------------------------------------------


def attention_block(params: Dict, x: jnp.ndarray, cfg: AttentionConfig, *,
                    layer_window: int = 0, cache: Optional[KVCache] = None,
                    make_cache: bool = False, cache_size: int = 0,
                    impl: str = "auto",
                    ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Returns (output (B,S,D), new/updated cache or None).

    Modes:
      * train/eval:   cache=None, make_cache=False.
      * prefill:      cache=None, make_cache=True, cache_size=Smax.
      * decode:       cache=KVCache, S must be 1; cache is updated in place
                      (functionally) at position cache.length.
    """
    b, s, _ = x.shape
    decode = cache is not None
    if decode:
        if jnp.ndim(cache.length) == 0:
            positions = (cache.length + jnp.arange(s))[None, :]
        else:  # per-slot lengths: (B,) -> (B, 1) position of the new token
            positions = cache.length[:, None] + jnp.arange(s)[None, :]
            positions = positions[:, None, :]  # broadcast over heads
    else:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project(params, x, cfg, positions)

    new_cache = None
    if decode:
        assert s == 1, "decode path is single-token"
        if jnp.ndim(cache.length) == 0:
            pos = cache.length
            k_full = jax.lax.dynamic_update_slice_in_dim(cache.k, k, pos,
                                                         axis=2)
            v_full = jax.lax.dynamic_update_slice_in_dim(cache.v, v, pos,
                                                         axis=2)
        else:  # scatter each slot's row at its own position
            bidx = jnp.arange(b)
            k_full = cache.k.at[bidx, :, cache.length].set(k[:, :, 0])
            v_full = cache.v.at[bidx, :, cache.length].set(v[:, :, 0])
        new_cache = KVCache(k_full, v_full, cache.length + 1)
        o = decode_attention(q, KVCache(k_full, v_full, cache.length + 1),
                             window=layer_window,
                             cap=cfg.attn_logit_softcap)
    else:
        if impl == "pallas":
            from repro.kernels.flash_attention import flash_attention
            o = flash_attention(q, k, v, causal=cfg.causal,
                                window=layer_window,
                                softcap=cfg.attn_logit_softcap)
        elif s <= 2048 or impl == "direct":
            o = direct_attention(q, k, v, causal=cfg.causal,
                                 window=layer_window,
                                 cap=cfg.attn_logit_softcap)
        else:
            # flash path with custom VJP: O(chunk^2) memory fwd AND bwd
            o = flash_attention_xla(q, k, v, causal=cfg.causal,
                                    window=layer_window,
                                    cap=cfg.attn_logit_softcap)
        if make_cache:
            assert cache_size >= s
            pad = ((0, 0), (0, 0), (0, cache_size - s), (0, 0))
            new_cache = KVCache(jnp.pad(k, pad), jnp.pad(v, pad),
                                jnp.asarray(s, jnp.int32))

    b_, hq, s_, d_ = q.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * d_)
    out = jnp.einsum("bsf,fd->bsd", o, params["wo"]["w"].astype(o.dtype))
    return out, new_cache


def cross_attention_block(params: Dict, x: jnp.ndarray, memory: jnp.ndarray,
                          cfg: AttentionConfig) -> jnp.ndarray:
    """Encoder-decoder cross attention (no rope, no causal mask)."""
    b, s, _ = x.shape
    _, sm, _ = memory.shape
    q = jnp.einsum("bsd,df->bsf", x, params["wq"]["w"].astype(x.dtype))
    k = jnp.einsum("bsd,df->bsf", memory, params["wk"]["w"].astype(x.dtype))
    v = jnp.einsum("bsd,df->bsf", memory, params["wv"]["w"].astype(x.dtype))
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, sm, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, sm, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    if s <= 2048 and sm <= 2048:
        o = direct_attention(q, k, v, causal=False, window=0, cap=0.0)
    else:  # flash path: O(S*Sm) scores never materialize (custom VJP)
        o = flash_attention_xla(q, k, v, causal=False)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.q_dim)
    return jnp.einsum("bsf,fd->bsd", o, params["wo"]["w"].astype(o.dtype))

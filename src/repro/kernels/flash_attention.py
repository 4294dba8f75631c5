"""Pallas TPU kernel: blockwise online-softmax (flash) attention.

Substrate kernel for the LM architectures (32k prefill / 500k decode would
materialize O(S^2) score matrices otherwise).  Supports GQA head grouping,
causal masking with decode-style right alignment, sliding windows (gemma2
local layers), logit soft-capping (gemma2), and padded KV caches via a
per-batch valid length.

Tiling: grid (batch, q_heads, Sq/tile_q, Sk/tile_k), KV innermost with
``arbitrary`` semantics; running max/sum and the output accumulator live in
VMEM scratch across KV steps (lane-broadcast (tile_q, 128) layout for the
scalars, the standard Mosaic-friendly shape).  Fully-masked KV blocks are
skipped with ``pl.when`` (causal upper triangle + out-of-window blocks), so
causal attention does ~half the MXU work and sliding-window attention is
O(S * window).

The pure-jnp oracle is ``ref.mha_ref``; tests sweep shapes/dtypes/flags.
"""

from __future__ import annotations

# analysis: allow-file(acc-dtype) -- the online-softmax running max/sum
# and output accumulator are ALWAYS f32 regardless of the plan's dtype
# (numerical requirement of the rescaling recurrence, outside the GCN
# acc_dtype threading contract).

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  tile_q: int, tile_k: int, sk: int, sq: int,
                  causal: bool, window: int, softcap: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions: with a padded cache of kv_len valid entries, the
    # last q row sits at position kv_len - 1 (decode-style right alignment).
    kv_len = kvlen_ref[0]
    q_off = kv_len - sq
    q_lo = q_off + qi * tile_q
    k_lo = ki * tile_k

    # block-level skip: causal => no k block strictly after the last q row;
    # sliding window => no k block before the window of the first q row.
    relevant = k_lo < kv_len
    if causal:
        relevant &= k_lo <= q_lo + tile_q - 1
    if window > 0:
        relevant &= (k_lo + tile_k - 1) > (q_lo - window)

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (tile_q, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (tile_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...][:, :1]                   # (tile_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard all-masked rows (m_new == NEG_INF)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(jnp.where(mask, s - m_safe, NEG_INF))
        alpha = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF,
                                  m_prev - m_safe))
        l_new = l_ref[...][:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "tile_q",
                              "tile_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    kv_len: Optional[jnp.ndarray] = None, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, tile_q: int = 128,
                    tile_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); returns (B, Hq, Sq, D).

    Sq and Sk are padded to tile multiples internally; ``kv_len`` (B,) marks
    valid KV entries (defaults to Sk).  interpret None = auto-detect
    (core.backend.default_interpret).
    """
    from repro.core.backend import resolve_interpret
    interpret = resolve_interpret(interpret)
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = d ** -0.5

    sq_p = -(-sq // tile_q) * tile_q
    sk_p = -(-sk // tile_k) * tile_k
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)
    qp = jnp.pad(q * scale, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    grid = (b, hq, sq_p // tile_q, sk_p // tile_k)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, tile_q=tile_q, tile_k=tile_k, sk=sk, sq=sq,
            causal=causal, window=window, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bb, h, qi, ki: (bb,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, tile_q, d),
                         lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, tile_k, d),
                         lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
            pl.BlockSpec((1, 1, tile_k, d),
                         lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_q, d),
                               lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q, d), jnp.float32),
            pltpu.VMEM((tile_q, 128), jnp.float32),
            pltpu.VMEM((tile_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(kv_len.astype(jnp.int32), qp, kp, vp)
    return out[:, :, :sq]

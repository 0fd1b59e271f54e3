"""Blocked online-softmax attention (flash attention) Pallas kernel.

Used by the LM substrate for train/prefill.  Supports the variants the
assigned architectures need:
  causal        decoder self-attention
  window        local (sliding-window) attention — gemma2/3, recurrentgemma
  softcap       tanh logit soft-capping — gemma2 (50.0)

Tiling: grid (batch*heads, q_tiles, kv_tiles); Q tile (BLK_Q, d) stays
resident while K/V tiles stream; running max m, denominator l and the
accumulator live in VMEM scratch.  MXU-aligned tiles: BLK=128 by default.

The kv grid axis is innermost so the scratch carries across kv steps of
one q tile (Pallas guarantees sequential grid order on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                 sm_scale: float, causal: bool, window: int | None,
                 softcap: float | None, blk_q: int, blk_k: int, nk: int):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, :, :].astype(jnp.float32)           # (blk_q, d)
    k = k_ref[0, :, :].astype(jnp.float32)           # (blk_k, d)
    v = v_ref[0, :, :].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = q_i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = kv_i * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones((blk_q, blk_k), dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]                               # (blk_q, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # guard fully-masked rows (all NEG_INF): keep exp() finite
    p = jnp.exp(s - m_cur)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_cur

    @pl.when(kv_i == nk - 1)
    def _done():
        denom = jnp.where(l_sc[...] == 0.0, 1.0, l_sc[...])
        o_ref[0, :, :] = (acc_sc[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, sm_scale: float | None = None,
                    blk_q: int = 128, blk_k: int = 128, interpret: bool | None = None):
    """q: (bh, sq, d); k, v: (bh, sk, d) — heads pre-flattened into batch.

    GQA is handled by the caller repeating KV heads (or flattening the
    group axis into batch); d and the sequence tiles are MXU-aligned.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    assert sq % blk_q == 0 and sk % blk_k == 0, "pad sequences to tile size"
    nq, nk = sq // blk_q, sk // blk_k
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    kern = functools.partial(
        _attn_kernel, sm_scale=sm_scale, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, nk=nk)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu_scratch((blk_q, 1)),
            pltpu_scratch((blk_q, 1)),
            pltpu_scratch((blk_q, d)),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


def pltpu_scratch(shape):
    """VMEM f32 scratch allocation (portable across pallas versions)."""
    try:
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.VMEM(shape, jnp.float32)
    except Exception:
        return pl.ANY(shape, jnp.float32)  # interpret fallback

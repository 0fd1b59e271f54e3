"""Public wrapper: multi-head attention with GQA handling.

On TPU (compiled, the platform default) this is the production attention for train /
prefill.  The CPU dry-run and the models' default path use ref.py's dense
attention; smoke tests run this wrapper in interpret mode to prove the
kernel integrates.
"""
from __future__ import annotations

import jax.numpy as jnp

from .flash_attn import flash_attention


def mha(q, k, v, *, causal: bool = True, window: int | None = None,
        softcap: float | None = None, interpret: bool | None = None):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) with H % Hkv == 0."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, -1, D)
    vf = v.reshape(B * H, -1, D)
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          softcap=softcap, interpret=interpret)
    return out.reshape(B, H, Sq, D)

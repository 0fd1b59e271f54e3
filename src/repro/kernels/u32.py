"""Exact modular arithmetic on uint32 lanes (shared by the HE kernels).

TPU has no 64-bit integer ALU, so 30-bit-prime RNS arithmetic is built
from 16-bit limb splitting on uint32 vectors:

  mulhi_u32      high 32 bits of a 32x32 product (4 partials + carries)
  shoup_mulmod   a * w mod q with w' = floor(w * 2^32 / q) precomputed —
                 one mulhi + one wrapping mul-sub (twiddles, plaintexts)
  barrett_mulmod general a * b mod q for q in (2^28, 2^30): full 60-bit
                 product in (hi, lo) halves, quotient via mu = 2^60 / q
  barrett_reduce the same reduction of a 60-bit int64 value
  dot_mod        (acc + sum_i c_i * d_i) mod q for c_i < 2^17: an exact
                 sum in (hi, lo) word pairs, one Barrett tail

All functions are shape-polymorphic jnp code: they run identically inside
Pallas kernel bodies and in host-side tests.
"""
from __future__ import annotations

import jax.numpy as jnp



def mulhi_u32(a, b):
    """High 32 bits of the 64-bit product of two uint32 vectors."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    a1, a0 = a >> 16, a & 0xFFFF
    b1, b0 = b >> 16, b & 0xFFFF
    lo = a0 * b0
    mid1 = a1 * b0
    mid2 = a0 * b1
    t = (lo >> 16) + (mid1 & 0xFFFF) + (mid2 & 0xFFFF)       # < 3 * 2^16
    return a1 * b1 + (mid1 >> 16) + (mid2 >> 16) + (t >> 16)


def mullo_u32(a, b):
    """Low 32 bits (uint32 multiply wraps — this is just `*`)."""
    return a.astype(jnp.uint32) * b.astype(jnp.uint32)


def shoup_precompute(w: int, q: int) -> int:
    """w' = floor(w * 2^32 / q) — host-side Python int math."""
    return (int(w) << 32) // int(q)


def shoup_mulmod(a, w, w_shoup, q):
    """a * w mod q with precomputed w' (Longa–Naehrig).  Result < q."""
    a = a.astype(jnp.uint32)
    hi = mulhi_u32(a, w_shoup)
    r = mullo_u32(a, w) - mullo_u32(hi, q)          # in [0, 2q)
    return jnp.where(r >= q, r - q, r)


def barrett_precompute(q: int) -> int:
    """mu = floor(2^60 / q); q in (2^28, 2^30) keeps mu < 2^32."""
    assert (1 << 28) < q < (1 << 30), f"Barrett tuned for 29/30-bit q, got {q}"
    return (1 << 60) // int(q)


def barrett_mulmod(a, b, q, mu):
    """General a*b mod q (a, b < q < 2^30) on uint32 lanes.

    P = a*b < 2^60 held as (hi, lo); x1 = floor(P / 2^29) < 2^31;
    qhat = floor(x1 * mu / 2^31) in [P/q - 3, P/q]; r = P - qhat*q in
    [0, 4q) -> 3 csubs (the third is needed only for q <= 2^29).
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    lo = mullo_u32(a, b)
    hi = mulhi_u32(a, b)                              # < 2^28
    x1 = (hi << 3) | (lo >> 29)                       # floor(P / 2^29)
    return _barrett_tail(lo, x1, q, mu)


def _barrett_tail(lo, x1, q, mu):
    """x mod q from x's low word and x1 = floor(x / 2^29)."""
    qhat = (mulhi_u32(x1, mu) << 1) | (mullo_u32(x1, mu) >> 31)
    r = lo - mullo_u32(qhat, q)                       # exact in low 32 bits
    for _ in range(3):
        r = jnp.where(r >= q, r - q, r)
    return r


def barrett_reduce(x, q, mu):
    """x mod q for int64 x in [0, 2^60), q in (2^28, 2^30), mu as above.

    The reduction half of `barrett_mulmod` with the 60-bit value given
    directly: uint32 lane arithmetic only, so it also replaces a 64-bit
    remainder (a long software division on chips without a 64-bit ALU)
    outside the kernels.  Returns uint32.
    """
    lo = (x & 0xFFFFFFFF).astype(jnp.uint32)
    x1 = (x >> 29).astype(jnp.uint32)                  # floor(x / 2^29) < 2^31
    return _barrett_tail(lo, x1, q, mu)


def _add_wide(hi, lo, x):
    """(hi, lo) + x for a 64-bit value held as two uint32 words."""
    lo = lo + x
    return hi + (lo < x).astype(jnp.uint32), lo


def dot_mod(acc, terms, coeffs, q, mu):
    """(acc + sum_i coeffs[i] * terms[i]) mod q on uint32 lanes.

    acc, terms[i] < q in (2^28, 2^30); coeffs[i] < 2^17 (plaintext
    scalars mod t <= 2^17), any broadcastable shapes.  With d = dh*2^15
    + dl, both dl*c and dh*c are below 2^32: two 32-bit multiplies a
    term, each summed exactly in a (hi, lo) pair of words.  The total
    stays below 2^60, the Barrett window, for up to 2^12 terms; one
    `_barrett_tail` reduces it.
    """
    lo = acc.astype(jnp.uint32)                 # (lo_hi, lo): sum c * dl
    lo_hi = up_hi = up = jnp.zeros_like(lo)     # (up_hi, up): sum c * dh
    for d, c in zip(terms, coeffs):
        d = d.astype(jnp.uint32)
        c = jnp.asarray(c).astype(jnp.uint32)
        lo_hi, lo = _add_wide(lo_hi, lo, (d & 0x7FFF) * c)
        up_hi, up = _add_wide(up_hi, up, (d >> 15) * c)
    # x = (lo_hi, lo) + (up_hi, up) * 2^15
    hi, lo = _add_wide(lo_hi + (up_hi << 15) + (up >> 17), lo, up << 15)
    return _barrett_tail(lo, (hi << 3) | (lo >> 29), q, mu)


def add_mod(a, b, q):
    s = a.astype(jnp.uint32) + b.astype(jnp.uint32)   # < 2q < 2^31
    return jnp.where(s >= q, s - q, s)


def sub_mod(a, b, q):
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    return jnp.where(a >= b, a - b, a + q - b)

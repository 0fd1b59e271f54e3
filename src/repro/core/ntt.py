"""Negacyclic number-theoretic transform — pure-jnp reference path.

Layout convention: polynomials are (k, n) int64 arrays — k RNS limbs of an
n-coefficient polynomial, coefficients in [0, q_i). The forward transform
uses Cooley-Tukey butterflies with premultiplied psi powers (Longa-Naehrig)
and produces the evaluation vector in bit-reversed order; the inverse uses
Gentleman-Sande butterflies and consumes that order, so pointwise products
round-trip without explicit bit-reversal passes.

This module is (a) the execution path on CPU, (b) the oracle for the
Pallas kernel in kernels/ntt and (c) the plaintext-modulus transform of
the batch encoder.  Products are <= (2^30-1)^2 < 2^63: exact in int64.
Given numpy arrays the transforms run in numpy on the host (the encoder
and parameter setup do this: as an int64 XLA program for a TPU the
transform takes many minutes to compile), given jax arrays in jnp.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _xp(a):
    return np if isinstance(a, np.ndarray) else jnp


def ntt_ref(a, psi_rev, q):
    """Forward negacyclic NTT. a: (k, n); psi_rev: (k, n); q: (k,)."""
    xp = _xp(a)
    k, n = a.shape
    qc = q[:, None, None]
    log_n = n.bit_length() - 1
    for s in range(log_n):
        m = 1 << s
        t_len = n >> (s + 1)
        a = a.reshape(k, m, 2, t_len)
        S = psi_rev[:, m : 2 * m]  # (k, m)
        U = a[:, :, 0, :]
        V = (a[:, :, 1, :] * S[:, :, None]) % qc
        a = xp.stack([(U + V) % qc, (U - V) % qc], axis=2)
    return a.reshape(k, n)


def intt_ref(a, ipsi_rev, n_inv, q):
    """Inverse negacyclic NTT (consumes bit-reversed evaluation order)."""
    xp = _xp(a)
    k, n = a.shape
    qc = q[:, None, None]
    log_n = n.bit_length() - 1
    for s in range(log_n):
        t_len = 1 << s
        h = n >> (s + 1)
        a = a.reshape(k, h, 2, t_len)
        S = ipsi_rev[:, h : 2 * h]  # (k, h)
        U = a[:, :, 0, :]
        V = a[:, :, 1, :]
        a = xp.stack([(U + V) % qc, ((U - V) * S[:, :, None]) % qc], axis=2)
    a = a.reshape(k, n)
    return (a * n_inv[:, None]) % q[:, None]


def pointwise_mul(a, b, q):
    """Hadamard product of evaluation vectors. (k, n) x (k, n) -> (k, n)."""
    return (a * b) % q[:, None]


def polymul_ref(a, b, tables):
    """Full negacyclic polynomial product via NTT (test helper)."""
    fa = ntt_ref(a, tables.psi_rev, tables.q)
    fb = ntt_ref(b, tables.psi_rev, tables.q)
    return intt_ref(pointwise_mul(fa, fb, tables.q), tables.ipsi_rev, tables.n_inv, tables.q)


def negacyclic_naive(a, b, q):
    """O(n^2) schoolbook negacyclic product — independent oracle for tests.

    a, b: (n,) python/numpy int arrays (single limb); returns (n,) mod q.
    """
    import numpy as np

    n = len(a)
    out = np.zeros(n, dtype=object)
    for i in range(n):
        ai = int(a[i])
        if ai == 0:
            continue
        for j in range(n):
            e = i + j
            v = ai * int(b[j])
            if e < n:
                out[e] += v
            else:
                out[e - n] -= v
    return np.array([int(x) % q for x in out], dtype=np.int64)

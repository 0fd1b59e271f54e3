"""Oracle: exact int64 pointwise modular arithmetic."""
from __future__ import annotations

import jax.numpy as jnp


def mul_mod_ref(a_i64, b_i64, q_i64):
    """(rows, n) x (rows, n) mod q[rows]; products < 2^60, exact int64."""
    return (a_i64 * b_i64) % q_i64[:, None]


def add_mod_ref(a_i64, b_i64, q_i64):
    return (a_i64 + b_i64) % q_i64[:, None]


def sub_mod_ref(a_i64, b_i64, q_i64):
    return (a_i64 - b_i64) % q_i64[:, None]


def dot_mod_ref(acc_i64, terms_i64, coeffs, q_i64):
    """(acc + sum_i coeffs[i] * terms[i]) mod q[rows]; exact while the
    sum stays below 2^63."""
    return (acc_i64 + sum(int(c) * d for c, d in zip(coeffs, terms_i64))) % q_i64[:, None]

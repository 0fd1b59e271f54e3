"""Public wrappers: int64 limb layout in/out, Barrett constants cached."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..u32 import barrett_precompute
from .modops import add_mod_pallas, mul_mod_pallas, sub_mod_pallas

_MU: dict[tuple[int, ...], jnp.ndarray] = {}


def _mu_for(primes: tuple[int, ...]) -> jnp.ndarray:
    if primes not in _MU:
        _MU[primes] = jnp.asarray(
            np.array([barrett_precompute(q) for q in primes], dtype=np.uint32))[:, None]
    return _MU[primes]


def mul_mod(a_i64, b_i64, primes: tuple[int, ...], *, interpret: bool | None = None):
    q = jnp.asarray(np.array(primes, dtype=np.uint32))[:, None]
    out = mul_mod_pallas(a_i64.astype(jnp.uint32), b_i64.astype(jnp.uint32),
                         q, _mu_for(tuple(primes)), interpret=interpret)
    return out.astype(jnp.int64)


def add_mod(a_i64, b_i64, primes: tuple[int, ...], *, interpret: bool | None = None):
    q = jnp.asarray(np.array(primes, dtype=np.uint32))[:, None]
    return add_mod_pallas(a_i64.astype(jnp.uint32), b_i64.astype(jnp.uint32),
                          q, interpret=interpret).astype(jnp.int64)


def sub_mod(a_i64, b_i64, primes: tuple[int, ...], *, interpret: bool | None = None):
    q = jnp.asarray(np.array(primes, dtype=np.uint32))[:, None]
    return sub_mod_pallas(a_i64.astype(jnp.uint32), b_i64.astype(jnp.uint32),
                          q, interpret=interpret).astype(jnp.int64)

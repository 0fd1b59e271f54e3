"""Public wrapper for the rotate-reduce kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .rotate_reduce import rotate_reduce_pallas


def rotate_reduce(x, t: int, chunk: int | None = None, *,
                  interpret: bool | None = None):
    """x: (rows, n) integer array mod t -> reduced array, same shape."""
    x = jnp.asarray(x, dtype=jnp.int32) % t
    return rotate_reduce_pallas(x, t, chunk=chunk, interpret=interpret)

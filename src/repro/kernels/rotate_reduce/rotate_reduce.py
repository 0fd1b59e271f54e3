"""Rotate-and-add reduction Pallas kernel (paper §4.2.2 COUNT/SUM).

The packed-aggregation doubling pattern — rotate by 1, 2, 4, ... and add
— executed entirely in VMEM for a batch of plaintext-domain rows.  On the
HE path the rotation is a Galois automorphism (core/bfv.py); this kernel
is the slot-domain equivalent used by the serving-side post-processing
and demonstrates the log-depth schedule the engine charges for.

Grid over rows; each row (n x 4 B = 128 KiB at n=32,768) stays resident
across all log2(n) stages as an (n/128, 128) tile (kernels/ntt layout).
A flat rotation by d is a sublane roll when d is a multiple of 128, and
otherwise a lane roll whose wrapped lanes take the next sublane's values
(one more sublane roll and an iota-masked select).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import resolve_interpret
from ..ntt.ntt import roll_by, tile_shape


def _rotate_left(x, d: int):
    """[i] <- x[(i + d) mod n] on the flat index of an (R, L) tile."""
    R, L = x.shape
    if d % L == 0:
        return roll_by(x, R - d // L, 0)
    lanes = roll_by(x, L - d, 1)         # [r, c] <- x[r, (c + d) mod L]
    if R == 1:
        return lanes
    wrapped = roll_by(lanes, R - 1, 0)   # [r, c] <- lanes[r + 1, c]
    col = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    return jnp.where(col < L - d, lanes, wrapped)


def _kernel(x_ref, o_ref, *, t: int, stop_log: int):
    x = x_ref[...]
    for s in range(stop_log):
        y = x + _rotate_left(x, 1 << s)     # both in [0, t): y < 2t
        x = jnp.where(y >= t, y - t, y)
    o_ref[...] = x


def rotate_reduce_pallas(x, t: int, *, chunk: int | None = None,
                         interpret: bool | None = None):
    """x: (rows, n) int32 values in [0, t).

    chunk=None reduces fully (every slot = row total); chunk=c stops at
    log2(c) stages — the exact-partial-sums mode (n/c partials per row).
    """
    rows, n = x.shape
    R, L = tile_shape(n)
    log_n = n.bit_length() - 1
    stop_log = log_n if chunk is None else (chunk.bit_length() - 1)
    zero = np.int32(0)    # block indices stay int32 when jax_enable_x64 is on
    row = pl.BlockSpec((None, R, L), lambda i: (i, zero, zero))
    out = pl.pallas_call(
        functools.partial(_kernel, t=int(t), stop_log=stop_log),
        grid=(rows,),
        in_specs=[row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((rows, R, L), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x.reshape(rows, R, L))
    return out.reshape(rows, n)

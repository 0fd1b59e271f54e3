"""Dyadic ciphertext-ciphertext Pallas kernels (Barrett uint32 path).

Pointwise modular multiply / add / sub over RNS limbs — the inner loop of
every BFV evaluation-domain operation (tensor products, key-switch digit
products, plaintext mask multiplies).

Tiling: grid over (row block, column tile).  A block is 8 rows (the
sublane count; the whole row axis when there are fewer) by TILE columns,
with each row's modulus and Barrett constant in an (8, 1) column beside
it.  Row counts that are not a multiple of 8 (k = 30, 31, B*30) end in a
partial edge block whose out-of-range rows are never written.  At
TILE = 8,192 a block is 256 KiB per operand, far below VMEM, letting the
compiler double-buffer HBM streams while the VPU does the ~30-op Barrett
sequence per lane.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import resolve_interpret, u32

ROW_BLOCK = 8
TILE = 8192
_ZERO = np.int32(0)   # block indices stay int32 when jax_enable_x64 is on


def _mul_kernel(a_ref, b_ref, q_ref, mu_ref, o_ref):
    o_ref[...] = u32.barrett_mulmod(a_ref[...], b_ref[...], q_ref[...], mu_ref[...])


def _add_kernel(a_ref, b_ref, q_ref, o_ref):
    o_ref[...] = u32.add_mod(a_ref[...], b_ref[...], q_ref[...])


def _sub_kernel(a_ref, b_ref, q_ref, o_ref):
    o_ref[...] = u32.sub_mod(a_ref[...], b_ref[...], q_ref[...])


def _pointwise(kernel, a, b, *cols, tile: int, interpret):
    """a, b: (rows, n) uint32; cols: per-row (rows, 1) uint32 constants."""
    rows, n = a.shape
    rb = rows if rows <= ROW_BLOCK else ROW_BLOCK
    tile = min(tile, n)
    spec = pl.BlockSpec((rb, tile), lambda i, j: (i, j))
    col = pl.BlockSpec((rb, 1), lambda i, j: (i, _ZERO))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, rb), pl.cdiv(n, tile)),
        in_specs=[spec, spec] + [col] * len(cols),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(a, b, *cols)


def mul_mod_pallas(a, b, q, mu, *, tile: int = TILE, interpret: bool | None = None):
    """a, b: (rows, n) uint32; q, mu: (rows, 1) uint32."""
    return _pointwise(_mul_kernel, a, b, q, mu, tile=tile, interpret=interpret)


def add_mod_pallas(a, b, q, *, tile: int = TILE, interpret: bool | None = None):
    return _pointwise(_add_kernel, a, b, q, tile=tile, interpret=interpret)


def sub_mod_pallas(a, b, q, *, tile: int = TILE, interpret: bool | None = None):
    return _pointwise(_sub_kernel, a, b, q, tile=tile, interpret=interpret)

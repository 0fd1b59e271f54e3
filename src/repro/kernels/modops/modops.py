"""Dyadic ciphertext-ciphertext Pallas kernels (Barrett uint32 path).

Pointwise modular multiply / add / sub over RNS limbs — the inner loop of
every BFV evaluation-domain operation (tensor products, key-switch digit
products, plaintext mask multiplies) — and the plaintext-scalar inner
product acc + sum_i c_i * d_i mod q of the LT's baby-step sums
(`dot_mod_pallas`, T terms as T operands, the c_i in SMEM).

Tiling: grid over (row block, column tile).  A block is 8 rows (the
sublane count; the whole row axis when there are fewer) by TILE columns,
with each row's modulus and Barrett constant in an (8, 1) column beside
it.  Row counts that are not a multiple of 8 (k = 30, 31, B*30) end in a
partial edge block whose out-of-range rows are never written.  At
TILE = 8,192 a block is 256 KiB per operand, far below VMEM, letting the
compiler double-buffer HBM streams while the VPU does the ~30-op Barrett
sequence per lane.  The inner product streams T + 2 arrays (T = 32 in
the engine), so its tile is DOT_TILE: 34 double-buffered (8, 2048)
blocks take 4.25 MiB of VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret, u32

ROW_BLOCK = 8
TILE = 8192
DOT_TILE = 2048
_ZERO = np.int32(0)   # block indices stay int32 when jax_enable_x64 is on


def _mul_kernel(a_ref, b_ref, q_ref, mu_ref, o_ref):
    o_ref[...] = u32.barrett_mulmod(a_ref[...], b_ref[...], q_ref[...], mu_ref[...])


def _add_kernel(a_ref, b_ref, q_ref, o_ref):
    o_ref[...] = u32.add_mod(a_ref[...], b_ref[...], q_ref[...])


def _sub_kernel(a_ref, b_ref, q_ref, o_ref):
    o_ref[...] = u32.sub_mod(a_ref[...], b_ref[...], q_ref[...])


def _dot_kernel(c_ref, acc_ref, *refs):
    *terms, q_ref, mu_ref, o_ref = refs
    o_ref[...] = u32.dot_mod(acc_ref[...], [t[...] for t in terms],
                             [c_ref[i] for i in range(len(terms))],
                             q_ref[...], mu_ref[...])


def _pointwise(kernel, arrays, cols, *, scalars=(), tile: int, interpret):
    """arrays: (rows, n) uint32; cols: per-row (rows, 1) uint32 constants;
    scalars: whole arrays in SMEM, before the others in the kernel's
    arguments."""
    rows, n = arrays[0].shape
    rb = rows if rows <= ROW_BLOCK else ROW_BLOCK
    tile = min(tile, n)
    spec = pl.BlockSpec((rb, tile), lambda i, j: (i, j))
    col = pl.BlockSpec((rb, 1), lambda i, j: (i, _ZERO))
    smem = [pl.BlockSpec(x.shape, lambda i, j, d=x.ndim: (_ZERO,) * d,
                         memory_space=pltpu.SMEM) for x in scalars]
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, rb), pl.cdiv(n, tile)),
        in_specs=smem + [spec] * len(arrays) + [col] * len(cols),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(*scalars, *arrays, *cols)


def mul_mod_pallas(a, b, q, mu, *, tile: int = TILE, interpret: bool | None = None):
    """a, b: (rows, n) uint32; q, mu: (rows, 1) uint32."""
    return _pointwise(_mul_kernel, [a, b], [q, mu], tile=tile, interpret=interpret)


def add_mod_pallas(a, b, q, *, tile: int = TILE, interpret: bool | None = None):
    return _pointwise(_add_kernel, [a, b], [q], tile=tile, interpret=interpret)


def sub_mod_pallas(a, b, q, *, tile: int = TILE, interpret: bool | None = None):
    return _pointwise(_sub_kernel, [a, b], [q], tile=tile, interpret=interpret)


def dot_mod_pallas(acc, terms, coeffs, q, mu, *, interpret: bool | None = None):
    """(acc + sum_i coeffs[i] * terms[i]) mod q.  acc and each of the T
    terms: (rows, n) uint32 residues, passed as separate operands (no
    stacked copy); coeffs: (T,) uint32, each < 2^17; q, mu: (rows, 1)."""
    return _pointwise(_dot_kernel, [acc, *terms], [q, mu], scalars=(coeffs,),
                      tile=DOT_TILE, interpret=interpret)

"""Negacyclic NTT Pallas kernel.

One grid step transforms one (row = batch x limb) polynomial held
entirely in VMEM: n = 32,768 coefficients x 4 B = 128 KiB per row, laid
out as an (n/128, 128) tile so every stage works on whole vregs.  All
log2(n) radix-2 stages run in-register with zero HBM round-trips between
stages.

Butterflies pair index i with i + d (the stage stride).  With the row
flattened as i = r * 128 + c:

  d >= 128  the partner sits d/128 sublanes away: a sublane roll
  d <  128  the partner sits d lanes away: a lane roll

Each stage rolls the tile both ways with `pltpu.roll`, picks the
partner and the butterfly half with iota masks (bit d of the flat
index), and multiplies by a twiddle table expanded on the host to the
same (n/128, 128) layout — one table per stage, holding at both
positions of a pair that pair's twiddle (`stage_twiddles`).

Twiddles use Shoup precomputation (w' = floor(w*2^32/q)): one mulhi +
one wrapping mul-sub per butterfly — no 64-bit arithmetic.

Layout (matches core/ntt.py): forward = Cooley-Tukey with premultiplied
psi powers in bit-reversed order, output bit-reversed; inverse =
Gentleman-Sande consuming that order.  Pointwise products round-trip
without bit-reversal passes.

Rows are ordered batch-major (row = b * k + limb).  The grid runs limb-
major over (k, B), so a limb's twiddle tables stay resident in VMEM
while its B rows stream through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret, u32

LANES = 128
_ZERO = np.int32(0)   # block indices stay int32 when jax_enable_x64 is on


def tile_shape(n: int) -> tuple[int, int]:
    """(sublanes, lanes) of one n-coefficient row."""
    lanes = min(n, LANES)
    return n // lanes, lanes


def stage_twiddles(tw: np.ndarray, q: np.ndarray, inverse: bool):
    """Expand bit-reversed twiddles to per-stage (n/128, 128) tables.

    tw: (k, n) psi_rev (forward) or ipsi_rev (inverse); q: (k,).
    Returns uint32 (k, log_n, R, L) twiddles and their Shoup companions:
    stage s entry i holds the twiddle of the butterfly pair that owns
    flat index i.
    """
    tw = np.asarray(tw, dtype=np.uint64)
    k, n = tw.shape
    log_n = n.bit_length() - 1
    i = np.arange(n)
    idx = []
    for s in range(log_n):
        if inverse:   # GS: stride 2^s, group j -> ipsi[h + j], h = n >> (s+1)
            idx.append((n >> (s + 1)) + (i >> (s + 1)))
        else:         # CT: stride n >> (s+1), group j -> psi[2^s + j]
            idx.append((1 << s) + (i >> (log_n - s)))
    w = tw[:, np.stack(idx)]                                   # (k, log_n, n)
    ws = (w << np.uint64(32)) // np.asarray(q, dtype=np.uint64)[:, None, None]
    R, L = tile_shape(n)
    return (w.astype(np.uint32).reshape(k, log_n, R, L),
            ws.astype(np.uint32).reshape(k, log_n, R, L))


def roll_by(x, shift: int, axis: int):
    """`jnp.roll(x, shift, axis)` (shift in [0, size)) via pltpu.roll."""
    return pltpu.roll(x, np.int32(shift), axis)


def _partners(a, d: int):
    """(a[i + d], a[i - d], mask of i with bit d clear) on the flat
    row index, for a row held as an (R, L) tile."""
    R, L = a.shape
    if d >= L:
        axis, step, size = 0, d // L, R
    else:
        axis, step, size = 1, d, L
    lo = (jax.lax.broadcasted_iota(jnp.int32, (R, L), axis) & step) == 0
    ahead = roll_by(a, size - step, axis)     # [i] <- a[i + d]
    behind = roll_by(a, step, axis) # [i] <- a[i - d]
    return ahead, behind, lo


def _fwd_kernel(a_ref, w_ref, ws_ref, q_ref, o_ref, *, log_n: int):
    """Cooley-Tukey: (U, V) -> (U + wV, U - wV) at stride n >> (s+1)."""
    n = 1 << log_n
    a = a_ref[...]
    q = q_ref[...]
    for s in range(log_n):
        ahead, behind, lo = _partners(a, n >> (s + 1))
        v = u32.shoup_mulmod(jnp.where(lo, ahead, a), w_ref[s], ws_ref[s], q)
        u = jnp.where(lo, a, behind)
        a = jnp.where(lo, u32.add_mod(u, v, q), u32.sub_mod(u, v, q))
    o_ref[...] = a


def _inv_kernel(a_ref, w_ref, ws_ref, q_ref, ninv_ref, ninvs_ref, o_ref,
                *, log_n: int):
    """Gentleman-Sande: (U, V) -> (U + V, w(U - V)) at stride 2^s, then n^-1."""
    a = a_ref[...]
    q = q_ref[...]
    for s in range(log_n):
        ahead, behind, lo = _partners(a, 1 << s)
        u = jnp.where(lo, a, behind)
        v = jnp.where(lo, ahead, a)
        diff = u32.shoup_mulmod(u32.sub_mod(u, v, q), w_ref[s], ws_ref[s], q)
        a = jnp.where(lo, u32.add_mod(u, v, q), diff)
    o_ref[...] = u32.shoup_mulmod(a, ninv_ref[...], ninvs_ref[...], q)


def _call(kernel, a, limb_tabs, *, interpret):
    """Run `kernel` over (rows, n) uint32 `a`, rows = B * k.

    limb_tabs: per-limb arrays with a leading k axis — the (k, log_n,
    R, L) stage tables and (k, 1, L) constants — indexed by limb only.
    """
    rows, n = a.shape
    k = limb_tabs[0].shape[0]
    B = rows // k
    R, L = tile_shape(n)
    row = pl.BlockSpec((None, R, L), lambda i, b: (b * k + i, _ZERO, _ZERO))
    specs = [row]
    for tab in limb_tabs:
        shape = (None,) + tab.shape[1:]
        specs.append(pl.BlockSpec(
            shape, lambda i, b, nd=len(tab.shape) - 1: (i,) + (_ZERO,) * nd))
    table_bytes = sum(int(np.prod(t.shape[1:])) * 4 for t in limb_tabs)
    out = pl.pallas_call(
        functools.partial(kernel, log_n=n.bit_length() - 1),
        grid=(k, B),
        in_specs=specs,
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((rows, R, L), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel"),
            vmem_limit_bytes=2 * table_bytes + (24 << 20)),
        interpret=resolve_interpret(interpret),
    )(a.reshape(rows, R, L), *limb_tabs)
    return out.reshape(rows, n)


def ntt_fwd_pallas(a, w, ws, q, *, interpret: bool | None = None):
    """a: (B*k, n) uint32; w/ws: (k, log_n, R, L) from `stage_twiddles`;
    q: (k, 1, L) uint32 (each limb's modulus broadcast along lanes)."""
    return _call(_fwd_kernel, a, (w, ws, q), interpret=interpret)


def ntt_inv_pallas(a, w, ws, q, ninv, ninv_shoup, *,
                   interpret: bool | None = None):
    """Inverse of `ntt_fwd_pallas`; ninv/ninv_shoup: (k, 1, L) uint32."""
    return _call(_inv_kernel, a, (w, ws, q, ninv, ninv_shoup),
                 interpret=interpret)

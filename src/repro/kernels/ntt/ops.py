"""Public wrapper for the NTT kernel: int64 (k, n) limb layout in/out,
per-stage Shoup tables built once per parameter base and cached."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...core.params import NttTables
from .ntt import ntt_fwd_pallas, ntt_inv_pallas, stage_twiddles, tile_shape

_CACHE: dict[tuple[NttTables, bool], tuple] = {}


def kernel_tables(tables: NttTables, inverse: bool = False) -> tuple:
    """Device arrays the kernel takes after the data, for one base:
    (w, w_shoup, q) forward, plus (n_inv, n_inv_shoup) inverse."""
    key = (tables, inverse)
    if key in _CACHE:
        return _CACHE[key]
    q = np.asarray(tables.q, dtype=np.uint64)
    k, n = tables.psi_rev.shape
    lanes = tile_shape(n)[1]

    def per_limb(v):                       # (k,) -> (k, 1, lanes) uint32
        return jnp.asarray(np.broadcast_to(
            v.astype(np.uint32)[:, None, None], (k, 1, lanes)))

    w, ws = stage_twiddles(tables.ipsi_rev if inverse else tables.psi_rev,
                           q, inverse)
    out = (jnp.asarray(w), jnp.asarray(ws), per_limb(q))
    if inverse:
        ninv = np.asarray(tables.n_inv, dtype=np.uint64)
        out += (per_limb(ninv), per_limb((ninv << np.uint64(32)) // q))
    _CACHE[key] = out
    return out


def ntt_fwd(a_i64, tables: NttTables, *, interpret: bool | None = None):
    """Forward NTT of (B*k, n) int64 limbs via the Pallas kernel."""
    out = ntt_fwd_pallas(a_i64.astype(jnp.uint32), *kernel_tables(tables),
                         interpret=interpret)
    return out.astype(jnp.int64)


def ntt_inv(a_i64, tables: NttTables, *, interpret: bool | None = None):
    out = ntt_inv_pallas(a_i64.astype(jnp.uint32),
                         *kernel_tables(tables, inverse=True),
                         interpret=interpret)
    return out.astype(jnp.int64)

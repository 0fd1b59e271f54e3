"""Batched limb-level dispatch: Pallas kernels vs pure-jnp reference.

`LimbOps` binds one RNS base (an `NttTables`) and routes the hot
primitives of BFV evaluation — pointwise mul/add/sub-mod and the
forward/inverse negacyclic NTT — either through the Pallas kernels
(`kernels/modops`, `kernels/ntt`) or through the pure-jnp `*_ref`
oracles, selected by a backend flag (and the plaintext-scalar inner
product `dot` of the LT's baby-step sums likewise):

    "ref"     exact int64 jnp arithmetic (always available)
    "pallas"  uint32 Barrett/Shoup kernels; interpret mode off-TPU,
              compiled on TPU
    "auto"    "pallas" when running on a TPU, "ref" otherwise

The default comes from the NSHEDB_LIMB_BACKEND environment variable
("auto" if unset).  The Barrett path needs every prime inside
(2^28, 2^30); both bases of every parameter set (core/params.py) sit
there, and asking for "pallas" on a base outside it raises.

Every entry point accepts arrays of shape (..., k, n) — any number of
leading batch axes over the (limb, coefficient) layout — and is safe to
call from inside jit.  Batches are flattened to the (rows, n) layout the
kernels grid over, so a whole column of ciphertext blocks runs as one
kernel launch.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from . import ntt as nttm
from .params import NttTables
from ..kernels import resolve_interpret
from ..kernels.u32 import barrett_precompute, barrett_reduce
from ..kernels.modops.modops import (add_mod_pallas, dot_mod_pallas, mul_mod_pallas,
                                     sub_mod_pallas)
from ..kernels.ntt.ntt import ntt_fwd_pallas, ntt_inv_pallas
from ..kernels.ntt.ops import kernel_tables

BACKENDS = ("ref", "pallas", "auto")

# Barrett window (kernels/u32.barrett_precompute): mu = 2^60/q < 2^32.
_Q_MIN, _Q_MAX = 1 << 28, 1 << 30


def default_backend() -> str:
    return os.environ.get("NSHEDB_LIMB_BACKEND", "auto")


def pallas_supported(primes) -> bool:
    """True iff every modulus sits in the uint32 Barrett window."""
    return all(_Q_MIN < int(q) < _Q_MAX for q in primes)


def resolve_backend(backend: str | None, primes) -> str:
    """Normalize a user flag to the backend that will run; raises when
    the kernels are asked for (or chosen on a TPU) but a modulus lies
    outside their Barrett window."""
    b = backend or default_backend()
    if b not in BACKENDS:
        raise ValueError(f"unknown limb backend {b!r}; expected one of {BACKENDS}")
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "ref"
    if b == "pallas" and not pallas_supported(primes):
        raise ValueError(
            f"limb backend 'pallas' needs every modulus in (2^28, 2^30); "
            f"got {[int(q).bit_length() for q in primes]}-bit primes")
    return b


class LimbLocalOps:
    """Per-device limb-slice primitives for shard_map bodies.

    Inside a `("data", "model")` shard_map region each device holds a
    contiguous (kL = k/M)-limb slice of every polynomial plus the
    matching slice of the twiddle/modulus tables, so the pointwise and
    NTT primitives are plain limb-major math over (..., kL, n) — zero
    communication (the all-gather of key-switch digits happens *before*
    these run; see core/bfv.py: kswitch_gathered).  Always ref-backed:
    the limb axis has no chip path yet (k = 30 has no real placement on
    four chips), and the ref path is bit-identical.
    """

    def __init__(self, q, psi, ipsi, ninv):
        self.q, self.psi, self.ipsi, self.ninv = q, psi, ipsi, ninv
        self.kl, self.n = psi.shape

    def _rows(self, a):
        """(..., kL, n) -> (B*kL, n) plus the batch factor B."""
        B = 1
        for d in a.shape[:-2]:
            B *= d
        return a.reshape(B * self.kl, self.n), B

    def _tile(self, tab, B: int):
        return jnp.concatenate([tab] * B, axis=0) if B > 1 else tab

    def mul(self, a, b):
        return (a * b) % self.q[:, None]

    def ntt(self, a):
        ar, B = self._rows(a)
        return nttm.ntt_ref(ar, self._tile(self.psi, B),
                            self._tile(self.q, B)).reshape(a.shape)

    def intt(self, a):
        ar, B = self._rows(a)
        return nttm.intt_ref(ar, self._tile(self.ipsi, B),
                             self._tile(self.ninv, B),
                             self._tile(self.q, B)).reshape(a.shape)


@jax.tree_util.register_pytree_node_class
class LimbOps:
    """Pointwise + NTT primitives for one RNS base, kernel- or ref-backed.

    A pytree whose leaves are the base's device tables: jitted callers
    take the instance as an argument (core/bfv.py), so the tables reach
    the compiled program as buffers instead of embedded constants.
    """

    def __init__(self, tables: NttTables, backend: str | None = None,
                 interpret: bool | None = None):
        primes = tuple(int(q) for q in tables.primes)
        backend = resolve_backend(backend, primes)
        arrays = {"q": jnp.asarray(tables.q),
                  "psi": jnp.asarray(tables.psi_rev),
                  "ipsi": jnp.asarray(tables.ipsi_rev),
                  "ninv": jnp.asarray(tables.n_inv)}
        if backend == "pallas":
            arrays["q_col"] = jnp.asarray(np.asarray(primes, dtype=np.uint32)[:, None])
            arrays["mu_col"] = jnp.asarray(np.array(
                [barrett_precompute(q) for q in primes], dtype=np.uint32)[:, None])
            arrays["fwd"] = kernel_tables(tables)
            arrays["inv"] = kernel_tables(tables, inverse=True)
        self._bind(tables, backend, resolve_interpret(interpret), arrays)

    def _bind(self, tables, backend, interpret, arrays):
        self.tables = tables
        self.primes = tuple(int(q) for q in tables.primes)
        self.k = len(self.primes)
        self.n = tables.psi_rev.shape[1]
        self.backend = backend
        self.interpret = interpret
        self.arrays = arrays
        self.q = arrays["q"]

    def tree_flatten(self):
        return (self.arrays,), (self.tables, self.backend, self.interpret)

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)
        obj._bind(*aux, children[0])
        return obj

    # --------------------------------------------------------- shape glue
    def _rows(self, a):
        """(..., k, n) -> (B*k, n) plus the batch factor B."""
        assert a.shape[-2:] == (self.k, self.n), (a.shape, self.k, self.n)
        B = 1
        for d in a.shape[:-2]:
            B *= d
        return a.reshape(B * self.k, self.n), B

    def _tile(self, tab, B: int):
        """Tile a per-limb table (k, ...) to (B*k, ...) row layout."""
        return jnp.concatenate([tab] * B, axis=0) if B > 1 else tab

    def _use_ref(self) -> bool:
        return self.backend == "ref"

    # ----------------------------------------------------- pointwise ops
    def _pointwise(self, a, b, kern_fn, ref_fn):
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        a = jnp.broadcast_to(a, shape)
        b = jnp.broadcast_to(b, shape)
        if self._use_ref():
            return ref_fn(a.reshape(-1, self.n), b.reshape(-1, self.n)).reshape(shape)
        ar, B = self._rows(a)
        br, _ = self._rows(b)
        out = kern_fn(ar.astype(jnp.uint32), br.astype(jnp.uint32),
                      self._tile(self.arrays["q_col"], B))
        return out.astype(jnp.int64).reshape(shape)

    def mul(self, a, b):
        """Pointwise a*b mod q over (..., k, n); exact, result in [0, q)."""
        return self._pointwise(
            a, b,
            lambda x, y, q: mul_mod_pallas(
                x, y, q, self._tile(self.arrays["mu_col"], x.shape[0] // self.k),
                interpret=self.interpret),
            lambda x, y: (x * y) % self._row_q(x))

    def add(self, a, b):
        return self._pointwise(
            a, b,
            lambda x, y, q: add_mod_pallas(x, y, q, interpret=self.interpret),
            lambda x, y: (x + y) % self._row_q(x))

    def sub(self, a, b):
        return self._pointwise(
            a, b,
            lambda x, y, q: sub_mod_pallas(x, y, q, interpret=self.interpret),
            lambda x, y: (x - y) % self._row_q(x))

    def dot(self, acc, datas, cs):
        """(acc + sum_i cs[i] * datas[i]) mod q over (..., k, n): residues
        in [0, q), cs a (T,) array of plaintext scalars < 2^17.  One
        uint32 multiply-accumulate kernel call for any batch on the
        kernel backend, exact int64 on the reference."""
        if self._use_ref():
            return (acc + sum(d * cs[i] for i, d in enumerate(datas))) % self.q[:, None]
        shape = jnp.broadcast_shapes(acc.shape, *(d.shape for d in datas))
        rows = lambda x: self._rows(jnp.broadcast_to(x, shape))[0].astype(jnp.uint32)
        a = rows(acc)
        B = a.shape[0] // self.k
        out = dot_mod_pallas(a, [rows(d) for d in datas], cs.astype(jnp.uint32),
                             self._tile(self.arrays["q_col"], B),
                             self._tile(self.arrays["mu_col"], B),
                             interpret=self.interpret)
        return out.astype(jnp.int64).reshape(shape)

    def reduce(self, x):
        """x mod q over (..., k, n) for int64 x in [0, 2^60): the uint32
        Barrett reduction on the kernel backend (a 64-bit remainder is a
        long software division on a TPU), int64 `%` on the reference."""
        if self.backend == "ref":
            return x % self.q[:, None]
        return barrett_reduce(x, self.arrays["q_col"],
                              self.arrays["mu_col"]).astype(jnp.int64)

    def _row_q(self, rows):
        """(B*k,) -> (B*k, 1) modulus column for flattened-row ref math."""
        B = rows.shape[0] // self.k
        return self._tile(self.q, B)[:, None]

    # -------------------------------------------------------------- NTT
    @jax.named_scope("he.ntt")
    def ntt(self, a):
        """Forward negacyclic NTT over (..., k, n)."""
        shape = a.shape
        ar, B = self._rows(a)
        if self._use_ref():
            out = nttm.ntt_ref(ar, self._tile(self.arrays["psi"], B),
                               self._tile(self.q, B))
        else:
            out = ntt_fwd_pallas(ar.astype(jnp.uint32), *self.arrays["fwd"],
                                 interpret=self.interpret).astype(jnp.int64)
        return out.reshape(shape)

    @jax.named_scope("he.intt")
    def intt(self, a):
        """Inverse negacyclic NTT over (..., k, n)."""
        shape = a.shape
        ar, B = self._rows(a)
        if self._use_ref():
            out = nttm.intt_ref(ar, self._tile(self.arrays["ipsi"], B),
                                self._tile(self.arrays["ninv"], B),
                                self._tile(self.q, B))
        else:
            out = ntt_inv_pallas(ar.astype(jnp.uint32), *self.arrays["inv"],
                                 interpret=self.interpret).astype(jnp.int64)
        return out.reshape(shape)

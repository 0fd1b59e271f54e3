"""BFV batch encoder: n integer slots per plaintext polynomial.

Slots are the CRT components of R_t = Z_t[X]/(X^n+1) (t prime, 2n | t-1),
laid out as 2 rows x n/2 columns so that the Galois element 3^r rotates
rows by r and 2n-1 swaps rows (see params._make_slot_map).
"""
from __future__ import annotations

import numpy as np

from . import ntt as nttm
from .params import HEParams


class BatchEncoder:
    """Encoding is plaintext work on the client's side of the trust
    boundary: it runs in numpy on the host (t < 2^17 keeps every
    product exact in int64), and only the encoded polynomial goes to
    the device."""

    def __init__(self, params: HEParams):
        self.params = params
        T = params.T
        self.qt = np.asarray(T.q)
        self.psi = np.asarray(T.psi_rev)
        self.ipsi = np.asarray(T.ipsi_rev)
        self.ninv = np.asarray(T.n_inv)
        self.slot_to_coeff = np.asarray(params.slot_to_coeff)
        # inverse permutation: coeff index -> slot
        self.coeff_to_slot = np.zeros(params.n, dtype=np.int32)
        self.coeff_to_slot[self.slot_to_coeff] = np.arange(params.n)

    def encode(self, values) -> np.ndarray:
        """values: up to n ints (taken mod t); returns plaintext poly (n,)."""
        p = self.params
        vals = np.zeros(p.n, dtype=np.int64)
        v = np.asarray(values, dtype=np.int64) % p.t
        vals[: v.shape[0]] = v
        evals = vals[self.coeff_to_slot][None, :]
        return nttm.intt_ref(evals, self.ipsi, self.ninv, self.qt)[0]

    def decode(self, poly) -> np.ndarray:
        evals = nttm.ntt_ref(np.asarray(poly, dtype=np.int64)[None, :],
                             self.psi, self.qt)[0]
        return evals[self.slot_to_coeff]

    def decode_signed(self, poly) -> np.ndarray:
        """Decode with centered representatives in (-t/2, t/2]."""
        v = self.decode(poly)
        t = self.params.t
        return v - t * (v > t // 2)

    # Common mask plaintexts -------------------------------------------------
    def constant(self, c: int) -> np.ndarray:
        return self.encode(np.full(self.params.n, c, dtype=np.int64))

    def basis(self, slot: int) -> np.ndarray:
        """All-zeros except a single 1 at `slot` (the paper's Extract mask)."""
        v = np.zeros(self.params.n, dtype=np.int64)
        v[slot] = 1
        return self.encode(v)

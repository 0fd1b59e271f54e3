"""Bytes a kernel call must move, from its shapes alone.

The negacyclic NTT over `rows` residue vectors of length n, of which
`limbs` distinct primes: it reads and writes each residue once (4 bytes:
the primes are 30-bit) and needs one n-entry twiddle table per prime.
The count is the algorithm's, whatever table layout or dtype an
implementation uses, so a kernel that moves more shows as a lower share.
"""
from __future__ import annotations

RESIDUE_BYTES = 4


def ntt_required_bytes(rows: int, n: int, limbs: int) -> int:
    """Input + output residues plus one twiddle table per limb."""
    return (2 * rows + limbs) * n * RESIDUE_BYTES


def roofline_share(required_bytes: float, seconds: float,
                   bytes_per_s: float) -> float:
    """Least time the memory allows over the time taken, in percent."""
    return 100.0 * required_bytes / bytes_per_s / seconds

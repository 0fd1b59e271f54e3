"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture
page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600
Gbit/s inter-chip interconnect per chip.  No integer (int32/uint32)
vector-unit peak is published, so the engine's kernels are held to the
HBM roofline alone.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "bf16_flops": 197e12, "int8_ops": 393e12,
                    "ici_bits_per_s": 1.6e12},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source") from None

"""Required bytes of the NTT and the table of peaks."""
import pytest

from bench import peaks, roofline


def test_ntt_required_bytes():
    # 2 x 30 limbs of one ciphertext at n = 32768: read + write each
    # residue once, one twiddle table per limb, 4 bytes each
    n = 32768
    assert roofline.ntt_required_bytes(60, n, 30) == (2 * 60 + 30) * n * 4
    assert roofline.ntt_required_bytes(1, 8, 1) == 3 * 8 * 4


def test_roofline_share():
    assert roofline.roofline_share(819e9, 1.0, 819e9) == pytest.approx(100.0)
    assert roofline.roofline_share(819e9, 4.0, 819e9) == pytest.approx(25.0)


def test_v5e_peaks_and_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _rec(kernel_s, ntt_s, ntt_calls):
    import types
    trace = types.SimpleNamespace(kernel_s=kernel_s, ntt_s=ntt_s,
                                  ntt_calls=ntt_calls, ntt_bytes=819_000_000)
    return types.SimpleNamespace(trace=trace, peaks=peaks.peaks("TPU v5 lite"))


def test_ntt_reader_fails_loudly_when_kernels_ran_but_no_ntt_matched():
    from bench.harness import read_metric
    import os
    mdir = os.path.join(os.path.dirname(peaks.__file__), "metrics")
    assert read_metric(mdir, "ntt_roofline", _rec(2.0, 0.01, 3)) == pytest.approx(10.0)
    assert read_metric(mdir, "ntt_roofline", _rec(0.0, 0.0, 0)) is None
    with pytest.raises(RuntimeError, match="NTT's signature"):
        read_metric(mdir, "ntt_roofline", _rec(2.0, 0.0, 0))

"""NTT kernels' share of the HBM roofline, in percent: the bytes the
calls must move (bench/roofline.py) over the chip's HBM bandwidth
(bench/peaks.py), divided by the NTT kernels' device time in the trace.
No integer vector peak is published, so HBM is the only bound.  Moves
query_s.

The trace names a Pallas call after the jitted program around it, not
after its kernel, so NTT calls are told apart by their operands
(`xplane.ntt_call`).  Where Pallas kernels ran and none of them is
recognised as an NTT, the NTT's signature has changed: that raises,
rather than leaving the metric out as if no NTT had run."""
from bench.roofline import roofline_share


def read(rec):
    tr = rec.trace
    if tr is None or rec.peaks is None or tr.kernel_s <= 0:
        return None
    if tr.ntt_calls == 0 or tr.ntt_s <= 0:
        raise RuntimeError(
            f"ntt_roofline: {tr.kernel_s:.3f} s of Pallas kernels in the "
            "window but no call matches the NTT's signature (bench/xplane.py "
            "ntt_call): the NTT's operands changed; teach ntt_call the new "
            "layout")
    return roofline_share(tr.ntt_bytes, tr.ntt_s, rec.peaks["hbm_bytes_per_s"])

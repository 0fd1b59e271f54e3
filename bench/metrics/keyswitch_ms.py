"""Device milliseconds per key switch of one ciphertext block: the device
time under the `he.keyswitch` scope in the trace (`xplane.Summary.scope_s`,
a mean over chips, times the chips), over the window's key switches.
Every ct-ct multiply relinearizes with one key switch (OpStats `mul`),
and every Galois rotation, the row swap included, is one (OpStats
`rotate`: `BFVBackend.rotate` charges one per power-of-two hop,
`swap_rows` one).  Both counters charge one per block of the operand,
on one chip or on a data mesh alike, so the time is summed over the
chips too.  Moves query_s.

A CPU trace names no op by its scope, so the metric is read on a chip
only.  Where key switches ran there and no device time carries the
scope, the scope was renamed: that raises, rather than leaving the
metric out as if no key switch had run."""


def read(rec):
    tr = rec.trace
    switches = rec.ops["mul"] + rec.ops["rotate"]
    if tr is None or rec.peaks is None or not switches:
        return None
    seconds = tr.scope_s.get("he.keyswitch", 0.0) * tr.devices
    if seconds <= 0:
        raise RuntimeError(
            f"keyswitch_ms: {switches} key switches in the window but no "
            "device time under the he.keyswitch scope (bench/xplane.py "
            f"scope_s holds {sorted(tr.scope_s)}): the scope was renamed")
    return 1e3 * seconds / switches

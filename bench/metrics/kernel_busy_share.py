"""Pallas kernel (tpu_custom_call) device time over device busy time,
from the trace, in percent.  Moves query_s."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.busy_s <= 0 or tr.kernel_s <= 0:
        return None
    return 100.0 * tr.kernel_s / tr.busy_s

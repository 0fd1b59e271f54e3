"""Share of the window in which no operation ran on the device (1 -
busy union over the window, from the trace), in percent.  Moves
query_s."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share

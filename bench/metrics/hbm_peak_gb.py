"""Peak device memory in GB: `peak_bytes_in_use` after the window on
the fullest chip.  A process-lifetime peak (set-up and warm-up
included), not a per-query one.  Moves query_s."""


def read(rec):
    return rec.memory_peak_bytes / 1e9 if rec.memory_peak_bytes else None

"""Admission: Executor.compile + verify_compiled on the window's plan,
host clock, mean of the repeats (the planner in the window's cache
state).  Moves query_s."""


def read(rec):
    if not rec.admit_s:
        return None
    return 1e3 * sum(rec.admit_s) / len(rec.admit_s)

"""Ciphertext-ciphertext multiplies (with relinearization) per query
(OpStats `mul` over the window).  Moves query_s."""


def read(rec):
    return rec.ops["mul"] / rec.queries if rec.queries else None

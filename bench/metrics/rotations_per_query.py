"""Galois rotations, one key switch each, per query (OpStats `rotate`
over the window).  Moves query_s."""


def read(rec):
    return rec.ops["rotate"] / rec.queries if rec.queries else None

"""Primitive HE calls the DAG executor issued per query (OpStats
`launches` over the window).  Moves query_s."""


def read(rec):
    return rec.ops["launches"] / rec.queries if rec.queries else None

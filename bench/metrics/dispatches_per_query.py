"""Jitted device programs the host called per query (OpStats
`dispatches` over the window: one per lane program, shard_map program
and elementwise program that `BFVContext._dispatch` runs).  Nothing
where the backend runs no device program (the mock).  Moves query_s."""


def read(rec):
    if not rec.queries or not rec.ops["dispatches"]:
        return None
    return rec.ops["dispatches"] / rec.queries

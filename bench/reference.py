"""Plain reference for the benchmark's queries, and the comparison that
decides `correct`.

Straight numpy over the generated rows (bench/lineitem.py): no import
of the engine, nothing the program made.  Results follow the engine's
output conventions, which are part of what is checked: one row per
combination of the group columns' distinct values in sorted order, AVG
as a (sum, count) pair, fixed-point factors (price * (100 - discount))
and every value reduced mod t.

`control` is the same computation with the columns held in float16 and
summed in float32: an approximate answer, the guarantee of exact
results broken.  The comparison has to fail it.
"""
from __future__ import annotations

import numpy as np

Q1_AGGS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
           "avg_qty", "avg_price", "avg_disc", "count_order")


def _load(xp, exact: bool):
    """Column loader: int64, or float16 storage computed in float32."""
    if exact:
        return lambda x: xp.asarray(np.asarray(x), dtype=np.int64)
    return lambda x: xp.asarray(xp.asarray(np.asarray(x), dtype=np.float16),
                                dtype=np.float32)


def q1(cols: dict, t: int, cutoff: int, xp=np, exact: bool = True) -> dict:
    """TPC-H Q1: WHERE l_shipdate <= cutoff, GROUP BY returnflag,
    linestatus."""
    load = _load(xp, exact)
    c = {k: load(cols[k]) for k in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax")}
    sel = np.asarray(cols["l_shipdate"]) <= cutoff
    rf, ls = np.asarray(cols["l_returnflag"]), np.asarray(cols["l_linestatus"])

    def total(x, m):
        return int(xp.sum(x * load(m))) % t

    price, qty, disc, tax = (c["l_extendedprice"], c["l_quantity"],
                             c["l_discount"], c["l_tax"])
    disc_price = price * (100 - disc)
    out = {}
    for f in sorted(set(rf.tolist())):
        for s in sorted(set(ls.tolist())):
            m = sel & (rf == f) & (ls == s)
            cnt = int(m.sum()) % t
            sq, sp, sd = total(qty, m), total(price, m), total(disc, m)
            out[(f, s)] = {
                "sum_qty": sq,
                "sum_base_price": sp,
                "sum_disc_price": total(disc_price, m),
                "sum_charge": total(disc_price * (100 + tax), m),
                "avg_qty": (sq, cnt), "avg_price": (sp, cnt),
                "avg_disc": (sd, cnt), "count_order": cnt}
    return out


def q6(cols: dict, t: int, lo_day: int, hi_day: int, disc: tuple[int, int],
       qty: int, xp=np, exact: bool = True) -> dict:
    """TPC-H Q6: shipdate in [lo_day, hi_day), discount (hundredths)
    BETWEEN disc[0] AND disc[1], quantity < qty; SUM(price * discount)."""
    ship, d, q = (np.asarray(cols[k]) for k in (
        "l_shipdate", "l_discount", "l_quantity"))
    m = ((ship >= lo_day) & (ship < hi_day) & (d >= disc[0]) & (d <= disc[1])
         & (q < qty))
    load = _load(xp, exact)
    rev = xp.sum(load(cols["l_extendedprice"]) * load(d) * load(m))
    return {"revenue": int(rev) % t}


QUERIES = {"q1": q1, "q6": q6}


def answer(cols: dict, t: int, query: str, args: dict, **kw) -> dict:
    return QUERIES[query](cols, t, **args, **kw)


def control(cols: dict, t: int, query: str, args: dict, xp=np) -> dict:
    """The reference with float16 columns and float32 sums."""
    return answer(cols, t, query, args, xp=xp, exact=False)


def _flat(result: dict) -> dict:
    """{path: int} over every value of a result (pairs count twice)."""
    out = {}
    for key, val in result.items():
        if isinstance(val, dict):
            for k2, v2 in _flat(val).items():
                out[(key,) + k2] = v2
        elif isinstance(val, (tuple, list)):
            for i, v in enumerate(val):
                out[(key, i)] = v
        else:
            out[(key,)] = val
    return out


def wrong_values(got: dict, want: dict) -> tuple[int, int]:
    """(values that differ or are missing or extra, values expected)."""
    g, w = _flat(got), _flat(want)
    bad = sum(1 for k, v in w.items() if k not in g or int(g[k]) != int(v))
    bad += sum(1 for k in g if k not in w)
    return bad, len(w)

"""TPC-H lineitem rows from a seed (TPC-H v3 spec §4.2.3), at the value
domains NSHEDB stores (16-bit encodings under t = 65537, paper §5.1).

The benchmark's own generator: it shares no code with the engine's
`engine/tpch.py`, so the data and the plain reference stay independent
of the program under test.  Dates are day numbers with 1992-01-01 = 1
(0 is the engine's slot padding).  Decimals are returned both as the
program receives them (floats) and as the integer hundredths the
reference computes with.
"""
from __future__ import annotations

import datetime as _dt

import numpy as np

EPOCH = _dt.date(1992, 1, 1)
CURRENT_DATE = _dt.date(1995, 6, 17)            # TPC-H CURRENTDATE
ORDER_FIRST = _dt.date(1992, 1, 1)              # STARTDATE
ORDER_LAST = _dt.date(1998, 8, 2)               # ENDDATE - 151 days
SHIPINSTRUCT = ("COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN")
SHIPMODES = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
# l_comment: TPC-H text (4.2.2.10) from a short word list, three words a
# comment, so a 32,768-row sample keeps its dictionary ids below t / 2.
COMMENT_WORDS = ("furiously", "quickly", "carefully", "blithely", "slyly",
                 "final", "regular", "express", "ironic", "pending", "bold",
                 "even", "special", "silent", "unusual", "deposits", "requests",
                 "accounts", "packages", "instructions", "foxes", "ideas",
                 "theodolites", "pinto beans", "dependencies", "sleep",
                 "wake", "haggle", "nag", "cajole")


def day(d: _dt.date | str) -> int:
    """Day number of a date (1992-01-01 = 1)."""
    if isinstance(d, str):
        d = _dt.date.fromisoformat(d)
    return (d - EPOCH).days + 1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def generate(cfg: dict, seed: int) -> dict[str, np.ndarray]:
    """`cfg["rows"]` lineitem rows.  Every column is a numpy array:
    integers (keys, quantity, dates, price), integer hundredths
    (discount, tax) and strings (flags, ship instruction and mode)."""
    rng = rng_for(seed, 0)
    rows = int(cfg["rows"])
    # Orders of 1-7 lines each (4.2.3), numbered 1, 2, ... until `rows`
    # lines are filled; the last order is cut at the row count.
    lines = rng.integers(1, 8, rows)
    orders = int(np.searchsorted(np.cumsum(lines), rows)) + 1
    okey = np.repeat(np.arange(1, orders + 1), lines[:orders])[:rows]
    first = np.concatenate(([0], np.cumsum(lines[:orders])[:-1]))
    linenumber = np.arange(rows) - np.repeat(first, lines[:orders])[:rows] + 1
    odate = rng.integers(day(ORDER_FIRST), day(ORDER_LAST) + 1, orders)
    ship = odate[okey - 1] + rng.integers(1, 122, rows)
    commit = odate[okey - 1] + rng.integers(30, 91, rows)
    receipt = ship + rng.integers(1, 31, rows)
    cur = day(CURRENT_DATE)
    ra = np.where(rng.integers(0, 2, rows) == 0, "R", "A")
    words = np.asarray(COMMENT_WORDS)[rng.integers(0, len(COMMENT_WORDS),
                                                   (rows, 3))]
    return {
        "l_orderkey": okey,
        "l_partkey": rng.integers(1, int(cfg["parts"]) + 1, rows),
        "l_suppkey": rng.integers(1, int(cfg["suppliers"]) + 1, rows),
        "l_linenumber": linenumber,
        "l_quantity": rng.integers(1, 51, rows),
        "l_extendedprice": rng.integers(*cfg["price_range"], rows),
        "l_discount": rng.integers(0, 11, rows),
        "l_tax": rng.integers(0, 9, rows),
        "l_returnflag": np.where(receipt <= cur, ra, "N"),
        "l_linestatus": np.where(ship > cur, "O", "F"),
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": np.asarray(SHIPINSTRUCT)[rng.integers(0, 4, rows)],
        "l_shipmode": np.asarray(SHIPMODES)[rng.integers(0, 7, rows)],
        "l_comment": np.char.add(np.char.add(words[:, 0], " "),
                                 np.char.add(np.char.add(words[:, 1], " "),
                                             words[:, 2])),
    }


def program_columns(cols: dict[str, np.ndarray], specs: list[dict]) -> dict:
    """The columns as `Database.load_table` takes them: decimals as
    floats (value / scale), strings as lists, the rest as integers."""
    out = {}
    for spec in specs:
        v = cols[spec["name"]]
        if spec["kind"] == "decimal":
            out[spec["name"]] = v / float(spec.get("scale", 1))
        elif spec["kind"] in ("str", "flag"):
            out[spec["name"]] = v.tolist()
        else:
            out[spec["name"]] = v
    return out

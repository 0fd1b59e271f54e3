"""The benchmark's plain reference against the engine's own plaintext
oracles (engine/queries.py) on tiny seeded data: a cross-check of the
two, not the yardstick (the reference imports nothing of the engine)."""
import datetime as dt

import numpy as np
import pytest

from bench import lineitem, querygen, reference

CFG = {"rows": 600, "parts": 40, "suppliers": 10,
       "price_range": [100, 10001]}


@pytest.fixture(scope="module")
def data():
    import json
    import os
    from repro.engine.backend import MockBackend
    from repro.engine.schema import ColumnSpec, TableSchema
    from repro.engine.storage import Database
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    with open(os.path.join(root, "bench/configs/tpch-li32k-1chip.json")) as f:
        specs = json.load(f)["columns"]
    cols = lineitem.generate(CFG, 2**33 + 21)
    db = Database(MockBackend())
    db.load_table(TableSchema("lineitem", [
        ColumnSpec(c["name"], c["kind"], scale=c.get("scale", 1)) for c in specs]),
        lineitem.program_columns(cols, specs), CFG["rows"])
    return cols, db


def _iso(day: int) -> str:
    return (lineitem.EPOCH + dt.timedelta(days=day - 1)).isoformat()


@pytest.mark.parametrize("delta", [60, 90, 120])
def test_q1_matches_engine_oracle(data, delta):
    from repro.engine import queries
    cols, db = data
    args = querygen.TEMPLATES["q1"]({"delta_days": delta})
    want = queries.oracle_q1(db, cutoff=_iso(args["cutoff"]))
    assert reference.answer(cols, db.bk.t, "q1", args) == want


@pytest.mark.parametrize("year,disc,qty", [(1993, 0.02, 24), (1995, 0.06, 25),
                                           (1997, 0.09, 24)])
def test_q6_matches_engine_oracle(data, year, disc, qty):
    from repro.engine import queries
    cols, db = data
    args = querygen.TEMPLATES["q6"]({"year": year, "discount": disc,
                                     "quantity": qty})
    want = queries.oracle_q6(db, year=year, disc=(disc - 0.01, disc + 0.01),
                             qty=qty)
    assert reference.answer(cols, db.bk.t, "q6", args) == want


def test_plan_runs_to_reference_on_the_engine(data):
    """The generator's plans, run through the engine on the mock
    backend, decrypt to the reference."""
    from repro.engine.executor import run_via_plan
    from repro.engine.planner import Planner
    cols, db = data
    for query, p in (("q1", {"delta_days": 75}),
                     ("q6", {"year": 1994, "discount": 0.05, "quantity": 24})):
        args = querygen.TEMPLATES[query](p)
        got = run_via_plan(Planner(db), querygen.plan(query, args), verify=True)
        assert reference.wrong_values(
            got, reference.answer(cols, db.bk.t, query, args)) == (0, 66 if query == "q1" else 1)


def test_wrong_values_counts_each_value():
    want = {("A", "F"): {"s": 3, "avg": (4, 5)}}
    assert reference.wrong_values(want, want) == (0, 3)
    assert reference.wrong_values({("A", "F"): {"s": 3, "avg": (4, 6)}}, want) == (1, 3)
    assert reference.wrong_values({}, want) == (3, 3)
    extra = {("A", "F"): {"s": 3, "avg": (4, 5), "x": 1}}
    assert reference.wrong_values(extra, want) == (1, 3)


def test_generator_repeats_for_a_seed_and_keeps_domains():
    a, b = lineitem.generate(CFG, 7), lineitem.generate(CFG, 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    c = lineitem.generate(CFG, 8)
    assert not np.array_equal(a["l_shipdate"], c["l_shipdate"])
    assert a["l_receiptdate"].max() < 65537 // 2
    assert set(a["l_returnflag"]) <= {"A", "N", "R"}
    # TPC-H 4.2.3: N exactly when received after CURRENTDATE
    cur = lineitem.day(lineitem.CURRENT_DATE)
    assert np.array_equal(a["l_returnflag"] == "N", a["l_receiptdate"] > cur)
    assert np.array_equal(a["l_linestatus"] == "O", a["l_shipdate"] > cur)
    # 4.2.3: orders of 1-7 lines, numbered 1.. within each order
    key, line = a["l_orderkey"], a["l_linenumber"]
    assert np.all(np.diff(key) >= 0) and line[0] == 1 and line.max() <= 7
    same = np.diff(key) == 0
    assert np.array_equal(line[1:][same], line[:-1][same] + 1)
    assert np.all(line[1:][~same] == 1)

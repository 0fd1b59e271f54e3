"""TPC-H substitution parameters: inside the spec's ranges, repeatable
for a seed, and the same work for every seed."""
import json
import os

import pytest

from bench import lineitem, querygen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mix(name):
    return querygen.load_mix(os.path.join(ROOT, "bench", "traffic", f"{name}.json"))


def q6_dashboard():
    """A Q6 mix drawn once per run on a shared planner (no cell runs it)."""
    return querygen.load_mix(os.path.join(os.path.dirname(__file__),
                                          "q6-dashboard.json"))


@pytest.mark.parametrize("seed", [0, 17, 2**31 + 5, 2**33 + 1])
def test_q1_delta_in_spec_range_and_repeats(seed):
    m = mix("q1-cold")
    a, b = querygen.Traffic(m, seed), querygen.Traffic(m, seed)
    got = [a.next() for _ in range(50)]
    assert got == [b.next() for _ in range(50)]
    for p, args in got:
        assert 60 <= p["delta_days"] <= 120
        assert args["cutoff"] == lineitem.day("1998-12-01") - p["delta_days"]
    assert len({p["delta_days"] for p, _ in got}) > 1      # drawn per query


@pytest.mark.parametrize("seed", [3, 2**31 + 9, 2**33 + 4])
def test_q6_parameters_in_spec_range_once_per_run(seed):
    m = q6_dashboard()
    tr = querygen.Traffic(m, seed)
    got = [tr.next() for _ in range(5)]
    assert all(g == got[0] for g in got)                    # drawn per run
    p, args = got[0]
    assert 1993 <= p["year"] <= 1997 and p["quantity"] in (24, 25)
    assert p["discount"] in [d / 100 for d in range(2, 10)]
    assert args["lo_day"] == lineitem.day(f"{p['year']}-01-01")
    assert args["hi_day"] == lineitem.day(f"{p['year'] + 1}-01-01")
    d = round(p["discount"] * 100)
    assert args["disc"] == (d - 1, d + 1)
    assert querygen.Traffic(m, seed).next() == got[0]


def test_every_draw_covers_the_range():
    m = q6_dashboard()
    seen = {querygen.Traffic(m, s).next()[0]["year"] for s in range(200)}
    assert seen == set(range(1993, 1998))


def test_plans_have_the_cells_shape():
    from repro.engine.plan import And, Pred
    q1 = querygen.plan("q1", {"cutoff": 2400})
    assert q1.where == Pred("l_shipdate", "<=", 2400) and len(q1.aggs) == 8
    q6 = querygen.plan("q6", {"lo_day": 1, "hi_day": 366, "disc": (5, 7), "qty": 24})
    assert isinstance(q6.where, And) and len(q6.where.children) == 4


def test_bad_mix_is_refused(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"query": "q99", "params": {}, "draw": "per_run",
                             "planner": "fresh", "warmup_queries": 0}))
    with pytest.raises(ValueError):
        querygen.load_mix(str(p))

"""Compiled-DAG execution (engine/physical.py + engine/executor.py).

Parity: `run_via_plan(planner, plan_qN())` must decrypt to exactly the
same result as the legacy hand-written `run_qN` body AND the plaintext
oracle, in both planner regimes, on the mock backend at paper parameters
and on real RNS-BFV ciphertexts (micro domain).  The scheduler claims —
fewer fused launches at equal op-depth accounting, CSE reuse, predicted
depth/refresh counts matching the executed op history — are asserted
against OpStats.

Every ported query runs once per regime in the module-scoped `runs`
fixture (queries at the paper profile are expensive); the tests assert
on the captured results/reports.
"""
import numpy as np
import pytest

from repro.engine import queries as Q
from repro.engine.executor import Executor, run_via_plan
from repro.engine.plan import Agg, And, Factor, JoinHop, Pred, QueryPlan, Translated
from repro.engine.planner import Planner

PORTED = list(Q.PLAN_EXECUTABLE)          # Q1, Q6, Q12, Q19


def _legacy_unfused(db):
    """The pre-DAG schedule: one circuit launch per predicate, no CSE."""
    pl = Planner(db, optimized=True)
    pl.fuse_masks = False
    pl.share_masks = False
    return pl


@pytest.fixture(scope="module")
def runs(tiny_db, mock_paper):
    """One legacy + one compiled-DAG execution per (query, regime)."""
    bk = mock_paper
    out = {}
    for qn in PORTED:
        plan_f, run_f, oracle_f = Q.QUERIES[qn]
        for opt in (True, False):
            bk.stats.reset()
            bk.op_log.clear()
            legacy = run_f(Planner(tiny_db, optimized=opt))
            leg_stats = bk.stats.clone()
            bk.stats.reset()
            bk.op_log.clear()
            ex = Executor(Planner(tiny_db, optimized=opt))
            got = ex.run(plan_f(), validate=True)
            out[(qn, opt)] = {
                "legacy": legacy, "got": got, "oracle": oracle_f(tiny_db),
                "legacy_stats": leg_stats, "stats": bk.stats.clone(),
                "eq_circuits": bk.op_log["eq"], "report": ex.report,
            }
    bk.stats.reset()
    bk.op_log.clear()
    return out


@pytest.fixture(scope="module")
def unfused_runs(tiny_db, mock_paper):
    """Q1/Q19 through the legacy bodies with fusion + CSE disabled —
    the pre-DAG launch schedule the benchmark compares against."""
    bk = mock_paper
    out = {}
    for qn in ("Q1", "Q19"):
        bk.stats.reset()
        bk.op_log.clear()
        Q.QUERIES[qn][1](_legacy_unfused(tiny_db))
        out[qn] = {"stats": bk.stats.clone(), "eq_circuits": bk.op_log["eq"]}
    bk.stats.reset()
    bk.op_log.clear()
    return out


# ---------------------------------------------------------------------------
# Parity: compiled DAG == legacy body == plaintext oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimized", [True, False])
@pytest.mark.parametrize("qn", PORTED)
def test_via_plan_matches_legacy_and_oracle(runs, qn, optimized):
    r = runs[(qn, optimized)]
    assert r["got"] == r["legacy"], f"{qn}: DAG != legacy body"
    assert r["got"] == r["oracle"], f"{qn}: DAG != plaintext oracle"


# ---------------------------------------------------------------------------
# Scheduler: fused cross-mask launches + CSE beat the pre-DAG schedule
# at identical op-depth accounting.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qn", ["Q1", "Q19"])
def test_fused_fewer_launches_equal_depth(runs, unfused_runs, qn):
    sep = unfused_runs[qn]["stats"]
    fused = runs[(qn, True)]["stats"]
    assert fused.launches < sep.launches, (fused.launches, sep.launches)
    assert fused.mul <= sep.mul                  # CSE never adds multiplies
    assert fused.max_depth == sep.max_depth      # equal op-depth accounting
    assert fused.refresh <= sep.refresh


def test_q1_group_cse_drops_duplicate_eq_circuits(runs, unfused_runs):
    """Legacy Q1 re-evaluates the l_linestatus EQ mask for every
    l_returnflag group; the DAG evaluates each distinct (col, =, value)
    subgraph once: 5 EQ circuits instead of 9."""
    assert unfused_runs["Q1"]["eq_circuits"] == 9
    assert runs[("Q1", True)]["eq_circuits"] == 5


def test_cse_cache_reused_across_runs(tiny_db, mock_paper):
    """Second execution of the same plan on one planner re-evaluates no
    comparison circuit at all (the whole atom set hits the CSE cache)."""
    pl = Planner(tiny_db, optimized=True)
    first = run_via_plan(pl, Q.plan_q6())
    ex = Executor(pl)
    assert ex.run(Q.plan_q6()) == first
    atoms_stage = ex.report.history[0]
    assert atoms_stage["stage"] == "atoms[fused]"
    assert atoms_stage["mul"] == 0, "cached atoms must not re-run circuits"


@pytest.mark.slow
def test_group_mask_memoization_feeds_sort(tiny_db, mock_paper):
    """ORDER BY reuses the GROUP BY EQ masks through the planner cache:
    the sort pass after group_masks adds zero equality circuits."""
    bk = mock_paper
    pl = Planner(tiny_db, optimized=True)
    li = tiny_db.tables["lineitem"]
    plain = tiny_db.plain["lineitem"]["l_quantity"]
    domain = sorted(set(plain.tolist()))
    pl.group_masks(li, "l_quantity", domain)
    bk.op_log.clear()
    out = pl.sort_column(li, "l_quantity", domain)
    assert bk.op_log["eq"] == 0, "sort must reuse memoized EQ masks"
    dec = bk.decrypt(out)
    np.testing.assert_array_equal(dec[: li.nrows], np.sort(plain))


# ---------------------------------------------------------------------------
# Predicted depth / refreshes vs the executed op history.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimized", [True, False])
@pytest.mark.parametrize("qn", PORTED)
def test_report_matches_plan_model(runs, qn, optimized):
    r = runs[(qn, optimized)]["report"]
    r.validate()                          # the executor's own contract
    assert r.history, "executor must record an op history"
    assert r.measured_depth == max(h["max_depth"] for h in r.history)
    assert r.refreshes == sum(h["refresh"] for h in r.history)
    # Table-3 composition bounds the executed chain from above...
    assert r.measured_depth <= r.predicted_depth + 3
    if optimized:
        # ...and tightly from below in the optimized regime.
        assert r.predicted_depth <= r.measured_depth + 7
        if r.predicted_refreshes == 0:
            assert r.refreshes == 0
    if r.refreshes:
        assert r.predicted_refreshes > 0


def test_group_pushdown_keeps_extra_in_predicates(tiny_db, mock_paper):
    """Only ONE IN predicate on the group column is absorbed into the
    enumeration; further predicates on the same column stay in WHERE."""
    import numpy as np
    plan = QueryPlan(
        name="double_in", fact="lineitem",
        where=And((Pred("l_shipmode", "in", ["MAIL", "SHIP"]),
                   Pred("l_shipmode", "in", ["SHIP", "RAIL"]))),
        group_by="l_shipmode", group_domain=2,
        aggs=(Agg("count", (), "n"),))
    got = run_via_plan(Planner(tiny_db, optimized=True), plan)
    sm = tiny_db.tables["lineitem"].schema.col("l_shipmode").dictionary
    li = tiny_db.plain["lineitem"]
    both = np.isin(li["l_shipmode"], [sm["SHIP"], sm["RAIL"]])
    for mode in ("MAIL", "SHIP"):
        exp = int((both & (li["l_shipmode"] == sm[mode])).sum())
        assert got[mode]["n"] == exp, mode


def test_group_pushdown_unknown_value_is_empty_group(tiny_db, mock_paper):
    """A pushed-down group constant absent from the data behaves like
    the predicate would: an (all-zero) group, not a KeyError."""
    plan = QueryPlan(
        name="ghost_group", fact="lineitem",
        where=Pred("l_shipmode", "in", ["MAIL", "NO SUCH MODE"]),
        group_by="l_shipmode", group_domain=2,
        aggs=(Agg("count", (), "n"),))
    got = run_via_plan(Planner(tiny_db, optimized=True), plan)
    assert got["NO SUCH MODE"]["n"] == 0
    assert got["MAIL"]["n"] > 0


def test_optimized_via_plan_refresh_free(runs):
    """The headline invariant on the in-budget queries: the compiled DAG
    keeps Q1/Q6/Q12 bootstrap-free under the optimized planner."""
    for qn in ("Q1", "Q6", "Q12"):
        assert runs[(qn, True)]["report"].refreshes == 0, qn


# ---------------------------------------------------------------------------
# Real ciphertexts: the compiled DAG on the BFV backend (micro domain).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bfv_db(bfv_micro):
    from repro.engine.schema import ColumnSpec, TableSchema
    from repro.engine.storage import Database
    rng = np.random.default_rng(9)
    db = Database(bfv_micro)
    n = 40
    db.load_table(TableSchema("sales", [
        ColumnSpec("day", "int"), ColumnSpec("price", "int"),
        ColumnSpec("qty", "int"), ColumnSpec("region", "str")]), {
        "day": rng.integers(1, 101, n),
        "price": rng.integers(1, 101, n),
        "qty": rng.integers(1, 11, n),
        "region": [["N", "S", "E", "W"][i] for i in rng.integers(0, 4, n)],
    }, n)
    db.load_table(TableSchema("dim", [
        ColumnSpec("key", "int"), ColumnSpec("flag", "int")]), {
        "key": np.arange(1, 5), "flag": np.array([1, 0, 1, 0])}, 4)
    db.load_table(TableSchema("fact", [
        ColumnSpec("fk", "int"), ColumnSpec("v", "int")]), {
        "fk": rng.integers(1, 5, 24), "v": rng.integers(1, 20, 24)}, 24)
    return db


@pytest.mark.slow
def test_via_plan_group_by_on_real_he(bfv_db, bfv_micro):
    bk = bfv_micro
    t = bk.t
    plan = QueryPlan(
        name="sales_report", fact="sales",
        where=And((Pred("day", "<", 50), Pred("qty", ">=", 3))),
        group_by="region", group_domain=4,
        aggs=(Agg("sum", (Factor("price"),), "s"), Agg("count", (), "c")))
    bk.stats.reset()
    got = run_via_plan(Planner(bfv_db, optimized=True), plan)
    plain = bfv_db.plain["sales"]
    sel = (plain["day"] < 50) & (plain["qty"] >= 3)
    rdict = bfv_db.tables["sales"].schema.col("region").dictionary
    for name, rid in sorted(rdict.items()):
        m = sel & (plain["region"] == rid)
        assert got[name] == {"s": int(plain["price"][m].sum()) % t,
                             "c": int(m.sum()) % t}, name
    assert bk.stats.refresh == 0, "optimized DAG must stay in budget"


@pytest.mark.slow
def test_via_plan_translated_join_on_real_he(bfv_db, bfv_micro):
    bk = bfv_micro
    t = bk.t
    hop = JoinHop("dim", "fk", "fact")
    plan = QueryPlan(
        name="flagged_volume", fact="fact",
        where=And((Translated(hop, Pred("flag", "=", 1)), Pred("v", "<", 15))),
        aggs=(Agg("sum", (Factor("v"),), "vol"), Agg("count", (), "n")))
    got = run_via_plan(Planner(bfv_db, optimized=True), plan)
    dim, fact = bfv_db.plain["dim"], bfv_db.plain["fact"]
    m = (dim["flag"][fact["fk"] - 1] == 1) & (fact["v"] < 15)
    assert got == {"vol": int(fact["v"][m].sum()) % t, "n": int(m.sum()) % t}


def test_circuit_lane_cap_keeps_masks_and_counts(bfv_db, bfv_micro, monkeypatch):
    """A backend that bounds the lanes of a stacked circuit (device
    memory, BFVBackend.circuit_lanes) gets the same masks and op counts
    from more, smaller launches."""
    bk = bfv_micro
    table = bfv_db.tables["sales"]
    expr = And((Pred("region", "=", "N"), Pred("qty", "=", 3)))

    def run():
        bk.stats.reset()
        mask = Planner(bfv_db, optimized=True).where_mask(table, expr)
        return [bk.decrypt(b) for b in mask], bk.stats.clone()

    masks, stats = run()
    monkeypatch.setattr(bk, "circuit_lanes", lambda kind: 1)
    capped, capped_stats = run()
    for a, b in zip(masks, capped):
        np.testing.assert_array_equal(a, b)
    assert (capped_stats.mul, capped_stats.add) == (stats.mul, stats.add)
    assert capped_stats.launches > stats.launches
    plain = bfv_db.plain["sales"]
    rid = table.schema.col("region").dictionary["N"]
    want = ((plain["region"] == rid) & (plain["qty"] == 3)).astype(np.int64)
    np.testing.assert_array_equal(masks[0][:len(want)], want)


def test_lanes_within_device_memory():
    """At the paper's size one LT lane outgrows what a 16 GB chip has
    free beside its keys; EQ lanes are small.  Kinds are the atoms'
    circuit names."""
    from repro.core.noise import paper_profile
    from repro.engine.backend import lanes_within
    p = paper_profile()
    free = 16_909_336_064 - 9_102_939_136      # a v5e after keygen
    assert lanes_within(free, "lt", p.t, p) == 1
    assert lanes_within(free, "eq", p.t, p) == 15
    assert lanes_within(0, "lt", p.t, p) == 1

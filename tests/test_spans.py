"""The engine's own instruments: host spans on the profiler's clock
(runtime/spans.py) and the dispatch counter (OpStats.dispatches).

A grouped query on real micro-profile ciphertexts runs under the JAX
profiler on the CPU; the span tree is read back from the `.xplane.pb`
the profiler wrote."""
import glob

import jax
import numpy as np
import pytest

from repro.engine.executor import Executor
from repro.engine.plan import Agg, Factor, Pred, QueryPlan
from repro.engine.planner import Planner
from repro.runtime import spans

PLAN = QueryPlan(name="region_sales", fact="sales",
                 where=Pred("qty", "=", 3), group_by="region", group_domain=2,
                 aggs=(Agg("sum", (Factor("price"),), "s"),
                       Agg("count", (), "c")))


@pytest.fixture(scope="module")
def sales_db(bfv_micro):
    from repro.engine.schema import ColumnSpec, TableSchema
    from repro.engine.storage import Database
    rng = np.random.default_rng(5)
    n = 24
    db = Database(bfv_micro)
    db.load_table(TableSchema("sales", [
        ColumnSpec("price", "int"), ColumnSpec("qty", "int"),
        ColumnSpec("region", "str")]), {
        "price": rng.integers(1, 50, n), "qty": rng.integers(1, 5, n),
        "region": [["N", "S"][i] for i in rng.integers(0, 2, n)]}, n)
    return db


def _host_spans(path: str) -> list:
    """[(start, end, name, stats)] of the thread that ran the query."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            if any(e.name == spans.QUERY for e in evs):
                return sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                dict(e.stats)) for e in evs
                               if e.name.startswith("nshedb.")),
                              key=lambda s: (s[0], -s[1]))
    raise AssertionError("no nshedb.query span in the trace")


@pytest.fixture(scope="module")
def traced_query(sales_db, bfv_micro, tmp_path_factory):
    Executor(Planner(sales_db, optimized=True)).run(PLAN)   # compile first
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ex = Executor(Planner(sales_db, optimized=True))
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        got = ex.run(PLAN)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    return ex, got, _host_spans(path)


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_query_span_tree(traced_query, sales_db, bfv_micro):
    ex, got, evs = traced_query
    named = lambda prefix: [e for e in evs if e[2].startswith(prefix)]
    (query,) = named(spans.QUERY)
    (admit,) = named(spans.ADMIT)
    (comp,), (ver,) = named(spans.COMPILE), named(spans.VERIFY)
    assert _inside(admit, query)
    assert _inside(comp, admit) and _inside(ver, admit)
    assert query[3]["plan"] == PLAN.name

    stages = named(spans.STAGE)
    assert [s[2][len(spans.STAGE):] for s in stages] == \
        [h["stage"] for h in ex.report.history]
    assert "gmasks" in [h["stage"] for h in ex.report.history]
    assert all(_inside(s, query) and not _inside(s, admit) for s in stages)

    he = named(spans.HE)
    assert {e[2] for e in he} >= {spans.HE + op for op in
                                  ("mul", "rotate", "swap_rows", "decrypt")}
    assert all(any(_inside(e, s) for s in stages) for e in he)
    circuits = named(spans.CIRCUIT)
    assert circuits and all(int(c[3]["lanes"]) >= 1 for c in circuits)

    runs = {int(e[3]["run"]) for e in [query, admit, *stages]}
    assert runs == {ex.run_id}

    plain = sales_db.plain["sales"]
    rdict = sales_db.tables["sales"].schema.col("region").dictionary
    for name, rid in rdict.items():
        m = (plain["qty"] == 3) & (plain["region"] == rid)
        assert got[name] == {"s": int(plain["price"][m].sum()) % bfv_micro.t,
                             "c": int(m.sum())}


def test_stage_history_covers_the_run(traced_query):
    """Every op the run charged lies in some stage's history entry."""
    ex, _, _ = traced_query
    r = ex.report
    assert r.muls == sum(h["mul"] for h in r.history)
    assert r.launches == sum(h["launches"] for h in r.history)
    assert all(h["dispatches"] >= 0 for h in r.history)
    assert sum(h["dispatches"] for h in r.history) > 0


PROGRAMS = ("_encrypt_j", "_decrypt_j", "_mul_j", "_mul_plain_j", "_rotate_j",
            "_elementwise_j", "_dot_j")


def test_dispatches_count_every_program_call(bfv_micro, monkeypatch):
    bk = bfv_micro
    calls = []
    for name in PROGRAMS:
        prog = getattr(bk.ctx, name)

        def counted(*args, _prog=prog, _name=name, **kwargs):
            calls.append(_name)
            return _prog(*args, **kwargs)
        monkeypatch.setattr(bk.ctx, name, counted)
    before = bk.stats.dispatches
    x = [bk.encrypt(np.arange(8) + i) for i in range(3)]
    col = bk.stack_blocks(x)
    y = bk.mul(col, bk.stack_blocks(x[::-1]))
    y = bk.add(bk.rotate(y, 3), bk.swap_rows(x[0]))
    y = bk.mul_plain(bk.mul_scalar(y, 5), np.arange(bk.slots))
    z = bk.dot_plain(x, [1, 2, 3])
    bk.decrypt(y)
    bk.decrypt(z)
    assert bk.stats.dispatches - before == len(calls)
    # three lanes: a batched op runs its one-ciphertext program per lane
    assert calls.count("_mul_j") == 3 and calls.count("_decrypt_j") == 4
    assert calls.count("_rotate_j") == 3 * 2 + 1       # step 3: two hops
    assert calls.count("_dot_j") == 1


def test_mock_backend_dispatches_nothing(tiny_db, mock_paper):
    from repro.engine import queries as Q
    mock_paper.stats.reset()
    Executor(Planner(tiny_db, optimized=True)).run(Q.QUERIES["Q6"][0]())
    assert mock_paper.stats.launches > 0
    assert mock_paper.stats.dispatches == 0

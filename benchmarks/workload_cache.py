"""Persistent WorkloadCache across a query mix: repeated-query and
drill-down suites (engine/workload.py).

In HE engines the comparison circuits dominate query cost, so reuse
across a dashboard's query mix is the cheapest speedup available — the
encrypted analogue of PartitionCache's cached partition-key conditions.
Two suites, both on the mock backend at the paper parameter profile:

  repeated    the executable TPC-H mix (Q1, Q6, Q12, Q19) scheduled
              twice through `run_workload`: the cold pass batch-fuses
              every distinct circuit of all four queries into one
              stacked launch per shape; the warm pass serves every atom
              and per-key join bank from the cache (noise-checked) and
              re-runs none.
  drilldown   a progressively narrowed Q6-style predicate stack — each
              step adds one predicate and reuses every mask the previous
              steps derived, so the hit rate climbs step over step.

Emits results/workload_cache.json; CI's smoke lane asserts the summary
reports a nonzero cross-query hit rate.
"""
from __future__ import annotations

import time

from repro.engine import queries as Q
from repro.engine import tpch
from repro.engine.backend import MockBackend
from repro.engine.plan import Agg, And, Factor, Pred, QueryPlan
from repro.engine.planner import Planner
from repro.engine.workload import WorkloadCache, run_workload

from .common import save_json, table

MIX = list(Q.PLAN_EXECUTABLE)             # Q1, Q6, Q12, Q19


def _drill_plans() -> list[QueryPlan]:
    """Dashboard drill-down: each step narrows the previous WHERE."""
    D = Q.D
    year = (Pred("l_shipdate", ">=", D("1994-01-01")),
            Pred("l_shipdate", "<", D("1995-01-01")))
    disc = (Pred("l_discount", "between", (0.05, 0.07)),)
    qty = (Pred("l_quantity", "<", 24),)
    mode = (Pred("l_shipmode", "in", ["MAIL", "SHIP"]),)
    steps = [
        ("d1_year", year),
        ("d2_discount", year + disc),
        ("d3_quantity", year + disc + qty),
        ("d4_shipmode", year + disc + qty + mode),
    ]
    return [QueryPlan(name=name, fact="lineitem", where=And(preds),
                      aggs=(Agg("sum", (Factor("l_extendedprice"),
                                        Factor("l_discount")), "revenue"),
                            Agg("count", (), "n")))
            for name, preds in steps]


def _pass_row(label: str, rep, wall: float) -> dict:
    return {
        "pass": label,
        "launches": rep.launches,
        "ct_mul": rep.muls,
        "refreshes": rep.refreshes,
        "hits": rep.cache.hits,
        "misses": rep.cache.misses,
        "hit_rate": round(rep.hit_rate, 3),
        "wall_s": round(wall, 3),
    }


def run(scale=None, quick: bool = False) -> dict:
    scale = scale or (tpch.Scale.tiny() if quick else tpch.Scale.small())
    bk = MockBackend()
    db = tpch.load(bk, scale)

    # -- repeated-query suite --------------------------------------------
    cache = WorkloadCache()
    pl = Planner(db, optimized=True, cache=cache)
    plans = [Q.QUERIES[qn][0]() for qn in MIX]
    repeated = []
    passes = {}
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        rep = run_workload(pl, plans)
        passes[label] = rep
        repeated.append(_pass_row(label, rep, time.perf_counter() - t0))
    cold, warm = passes["cold"], passes["warm"]
    assert cold.results == warm.results, "warm pass decrypts must match cold"
    oracles = [Q.QUERIES[qn][2](db) for qn in MIX]
    assert cold.results == oracles, "workload results != plaintext oracle"
    assert warm.hit_rate > 0.5, f"warm hit rate {warm.hit_rate} <= 0.5"
    assert warm.launches < cold.launches, "warm pass must launch fewer circuits"

    # -- drill-down suite ------------------------------------------------
    dcache = WorkloadCache()
    dpl = Planner(db, optimized=True, cache=dcache)
    drill = []
    for plan in _drill_plans():
        t0 = time.perf_counter()
        rep = run_workload(dpl, [plan])
        drill.append({
            "step": plan.name,
            "launches": rep.launches,
            "hits": rep.cache.hits,
            "misses": rep.cache.misses,
            "wall_s": round(time.perf_counter() - t0, 3),
        })
    assert drill[0]["hits"] == 0 and all(d["hits"] > 0 for d in drill[1:]), \
        "every narrowed step must reuse earlier masks"

    payload = {
        "repeated": repeated,
        "drilldown": drill,
        "summary": {
            "queries": MIX,
            "cross_query_hit_rate": round(warm.hit_rate, 3),
            "cold_launches": cold.launches,
            "warm_launches": warm.launches,
            "launch_ratio": round(cold.launches / warm.launches, 2),
            "warm_circuit_evals": warm.cache.misses,
            "fk_bank_hits_warm": warm.cache.fk_hits,
        },
    }
    save_json("workload_cache.json", payload)
    return payload


def main(quick: bool = False) -> str:
    payload = run(quick=quick)
    out = table(payload["repeated"],
                "Workload cache — cold vs warm pass over Q1+Q6+Q12+Q19 "
                "(mock backend, cross-query fused scheduling)")
    out += "\n" + table(payload["drilldown"],
                        "Drill-down suite — each step narrows the WHERE and "
                        "reuses cached masks")
    s = payload["summary"]
    out += (f"\ncross-query hit rate {s['cross_query_hit_rate']}, launches "
            f"{s['cold_launches']} -> {s['warm_launches']} "
            f"({s['launch_ratio']}x)")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

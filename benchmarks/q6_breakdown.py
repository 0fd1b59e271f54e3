"""Table 5: Q6 execution breakdown — bootstrapping / filter / conversion /
aggregation.  NSHEDB's column must show zero bootstrap and zero
transciphering; the filter (comparison circuits) dominates."""
from __future__ import annotations

from repro.engine import ops, tpch
from repro.engine.backend import MockBackend
from repro.engine.baseline import PAPER_QUERY_SECONDS, nshedb_seconds
from repro.engine.plan import Agg, And, Factor, Pred
from repro.engine.planner import Planner
from repro.engine.schema import date_to_int as D

from .common import fmt_s, paper_costs, save_json, seal_norm_factor, table


def main(quick: bool = False) -> str:
    costs = paper_costs(quick)
    bk = MockBackend()
    db = tpch.load(bk, tpch.Scale.tiny() if quick else tpch.Scale.small(),
                   tables=["lineitem"])
    pl = Planner(db, optimized=True)
    li = db.tables["lineitem"]

    # phase 1: filter (all comparison masks + combine)
    bk.stats.reset()
    mask = pl.where_mask(li, And((
        Pred("l_shipdate", ">=", D("1994-01-01")),
        Pred("l_shipdate", "<", D("1995-01-01")),
        Pred("l_discount", "between", (0.05, 0.07)),
        Pred("l_quantity", "<", 24))))
    filter_stats = bk.stats.clone()

    # phase 2: aggregation (mask multiply + rotate-reduce)
    bk.stats.reset()
    pl.aggregate(li, Agg("sum", (Factor("l_extendedprice"),
                                 Factor("l_discount")), "revenue"), mask)
    agg_stats = bk.stats.clone()

    norm = seal_norm_factor(quick)
    filt_s = nshedb_seconds(filter_stats, costs) * norm
    agg_s = nshedb_seconds(agg_stats, costs) * norm
    boot_s = (filter_stats.refresh + agg_stats.refresh) * costs.refresh
    total = filt_s + agg_s + boot_s
    rows = [
        {"system": "HE3DB (paper)", "boot_s": 11509, "filter_s": 251,
         "conv_s": 42, "agg_s": 0.01, "total_s": 11802},
        {"system": "ArcEDB (paper)", "boot_s": 2753, "filter_s": 430,
         "conv_s": 74, "agg_s": 0.21, "total_s": 3257},
        {"system": "NSHEDB (paper)", "boot_s": 0, "filter_s": 589,
         "conv_s": 0, "agg_s": 1.41, "total_s": 590},
        {"system": "NSHEDB (ours)", "boot_s": fmt_s(boot_s),
         "filter_s": fmt_s(filt_s), "conv_s": 0, "agg_s": fmt_s(agg_s),
         "total_s": fmt_s(total)},
    ]
    save_json("table5_q6_breakdown.json", rows)
    return table(rows, "Table 5 — Q6 execution breakdown (seconds, 32K rows)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

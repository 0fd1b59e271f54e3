"""The one traffic generator: a mix file (`bench/traffic/<mix>.json`)
names a TPC-H query template, the ranges its substitution parameters
are drawn from, and how the client issues it:

    query           template name (below)
    params          {parameter: [low, high]} inclusive ranges
    draw            "per_query": new parameters for every query;
                    "per_run": one draw, re-issued all run (a dashboard)
    planner         "fresh": a new Planner per query (empty mask cache);
                    "shared": one Planner for the whole run
    warmup_queries  queries run in set-up, before the window, on the
                    same planner policy (warms every program the window
                    runs; on a shared planner it also fills the cache)
    warmup_plan     "full": the warm-up runs the window's query as is;
                    "one_agg_per_kind": the same predicate and grouping
                    with one aggregate of each function (the one with
                    the most factors), which runs every program and
                    shape the full query does for a fraction of its
                    aggregation work (optional, default "full")

The client is one closed loop.  Parameters come from the seed alone, so
the same seed gives the same queries; every draw does the same
encrypted work (the circuits are data-oblivious), only the answer moves.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from .lineitem import day, rng_for

Q1_BASE = "1998-12-01"


def _q1(p: dict):
    """TPC-H Q1 (spec 2.4.1): DELTA days before 1998-12-01."""
    cutoff = day(Q1_BASE) - int(p["delta_days"])
    return {"cutoff": cutoff}


def _q6(p: dict):
    """TPC-H Q6 (spec 2.4.6): DATE = Jan 1 of YEAR, DISCOUNT +- 0.01,
    QUANTITY."""
    year, disc = int(p["year"]), int(round(p["discount"] * 100))
    return {"lo_day": day(f"{year}-01-01"), "hi_day": day(f"{year + 1}-01-01"),
            "disc": (disc - 1, disc + 1), "qty": int(p["quantity"])}


TEMPLATES = {"q1": _q1, "q6": _q6}
WARMUP_PLANS = ("full", "one_agg_per_kind")


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["query"] not in TEMPLATES:
        raise ValueError(f"{path}: unknown query template {mix['query']!r}")
    if mix["draw"] not in ("per_query", "per_run"):
        raise ValueError(f"{path}: draw must be per_query or per_run")
    if mix["planner"] not in ("fresh", "shared"):
        raise ValueError(f"{path}: planner must be fresh or shared")
    if mix.setdefault("warmup_plan", "full") not in WARMUP_PLANS:
        raise ValueError(f"{path}: warmup_plan must be one of {WARMUP_PLANS}")
    return mix


def draw(mix: dict, rng: np.random.Generator) -> dict:
    """One set of substitution parameters.  Integer ranges draw
    integers; a float range draws hundredths (TPC-H's DISCOUNT)."""
    out = {}
    for name, (lo, hi) in sorted(mix["params"].items()):
        if isinstance(lo, float) or isinstance(hi, float):
            out[name] = int(rng.integers(round(lo * 100), round(hi * 100) + 1)) / 100
        else:
            out[name] = int(rng.integers(lo, hi + 1))
    return out


class Traffic:
    """The queries of one run, in order: warm-up queries, then the
    window's.  Each is (drawn parameters, reference arguments)."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.rng = rng_for(seed, 1)
        self._fixed = draw(mix, self.rng) if mix["draw"] == "per_run" else None

    def next(self) -> tuple[dict, dict]:
        p = self._fixed if self._fixed is not None else draw(self.mix, self.rng)
        return p, TEMPLATES[self.mix["query"]](p)


def plan(query: str, args: dict):
    """The engine's QueryPlan for a template and its reference args."""
    from repro.engine.plan import Agg, And, Factor, Pred, QueryPlan
    if query == "q1":
        return QueryPlan(
            name="Q1", fact="lineitem",
            where=Pred("l_shipdate", "<=", args["cutoff"]),
            group_by="l_returnflag,l_linestatus", group_domain=6,
            aggs=(
                Agg("sum", (Factor("l_quantity"),), "sum_qty"),
                Agg("sum", (Factor("l_extendedprice"),), "sum_base_price"),
                Agg("sum", (Factor("l_extendedprice"),
                            Factor("l_discount", -1, 100)), "sum_disc_price"),
                Agg("sum", (Factor("l_extendedprice"), Factor("l_discount", -1, 100),
                            Factor("l_tax", 1, 100)), "sum_charge"),
                Agg("avg", (Factor("l_quantity"),), "avg_qty"),
                Agg("avg", (Factor("l_extendedprice"),), "avg_price"),
                Agg("avg", (Factor("l_discount"),), "avg_disc"),
                Agg("count", (), "count_order")),
            order_by="l_returnflag,l_linestatus")
    if query == "q6":
        lo, hi = args["disc"]
        return QueryPlan(
            name="Q6", fact="lineitem",
            where=And((Pred("l_shipdate", ">=", args["lo_day"]),
                       Pred("l_shipdate", "<", args["hi_day"]),
                       Pred("l_discount", "between", (lo / 100, hi / 100)),
                       Pred("l_quantity", "<", args["qty"]))),
            aggs=(Agg("sum", (Factor("l_extendedprice"), Factor("l_discount")),
                      "revenue"),))
    raise ValueError(f"unknown query template {query!r}")


def warmup_plan(plan, kind: str):
    """The plan a warm-up query runs in place of `plan` (see
    `warmup_plan` in the module docstring)."""
    if kind == "full":
        return plan
    widest = {}
    for a in plan.aggs:
        if a.kind not in widest or len(a.factors) > len(widest[a.kind].factors):
            widest[a.kind] = a
    keep = tuple(a for a in plan.aggs if widest[a.kind] is a)
    return dataclasses.replace(plan, name=plan.name + "-warmup", aggs=keep)

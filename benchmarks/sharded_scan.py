"""Sharded scan execution at SF=1.0 (engine/sharded.py, DESIGN §4).

Weak/strong scaling of the data-parallel block scan on the mock backend
at the paper parameter profile (n=32768, t=65537, k=30).  SF=1.0
lineitem is 6,001,215 rows = 184 ciphertext blocks per column; every
query runs once per shard count with a fresh `Planner(db, shards=s)`,
decrypted results are asserted identical across shard counts AND against
the plaintext oracle, and the ShardContext ledger prices each run with
the measured per-op costs (results/op_costs.json extrapolated to paper
parameters) — distributed scan lanes divide by the shard count,
replicated singleton work and the psum combine tree do not.

Two query arms, both EQ-only so a single host can execute the full
SF=1.0 ciphertext arithmetic in-process:

  grouped   GROUP BY l_returnflag with the IN pushdown (3 EQ circuits
            over 184 blocks) + SUM(qty), SUM(price), COUNT
  filtered  WHERE l_shipmode IN (1,2) AND l_returnflag = 1,
            SUM(l_quantity)

Emits results/sharded_scan.json.  Full mode asserts the §5 acceptance
bar: > 1.5x modeled speedup at 4 shards; smoke mode (--smoke / quick)
runs 8 blocks at shards (1, 2) and asserts speedup >= 1.

`--limb-shards M` additionally sweeps the model (RNS limb) axis of the
2-D mesh on the filtered arm — limb-local ops divide by the limb
factor, the all-gathered key-switch digits are charged per byte — and
emits results/limb_sharding.json (speedup > 1 required at M=2, >= 1 in
smoke mode).
"""
from __future__ import annotations

import time

import numpy as np

from repro.engine.backend import MockBackend
from repro.engine.executor import run_via_plan
from repro.engine.plan import Agg, And, Factor, Pred, QueryPlan
from repro.engine.planner import Planner
from repro.engine.schema import ColumnSpec, TableSchema
from repro.engine.storage import Database

from .common import fmt_s, op_costs, save_json, table

SF1_ROWS = 6_001_215          # TPC-H lineitem at scale factor 1.0
T = 65537


def _lineitem_db(bk, nrows: int, seed: int = 3) -> tuple[Database, dict]:
    """Integer-coded lineitem slice: enough columns for the two arms.
    Dictionary encoding 6M strings would dominate setup, so categorical
    columns are generated directly as their dictionary ids."""
    rng = np.random.default_rng(seed)
    schema = TableSchema("lineitem", [
        ColumnSpec("l_returnflag", "int"),     # 1..3  (A/N/R)
        ColumnSpec("l_shipmode", "int"),       # 1..7
        ColumnSpec("l_quantity", "int"),       # 1..50
        ColumnSpec("l_extendedprice", "int"),  # fixed-point, < t/2
    ])
    data = {
        "l_returnflag": rng.integers(1, 4, nrows),
        "l_shipmode": rng.integers(1, 8, nrows),
        "l_quantity": rng.integers(1, 51, nrows),
        "l_extendedprice": rng.integers(100, 1000, nrows),
    }
    db = Database(bk)
    db.load_table(schema, data, nrows)
    return db, data


def _arms() -> list[QueryPlan]:
    grouped = QueryPlan(
        "sf1_grouped", "lineitem",
        where=Pred("l_returnflag", "in", (1, 2, 3)),
        group_by="l_returnflag", group_domain=3,
        aggs=(Agg("sum", (Factor("l_quantity"),), "sum_qty"),
              Agg("sum", (Factor("l_extendedprice"),), "sum_price"),
              Agg("count", (), "count")))
    filtered = QueryPlan(
        "sf1_filtered", "lineitem",
        where=And((Pred("l_shipmode", "in", (1, 2)),
                   Pred("l_returnflag", "=", 1))),
        aggs=(Agg("sum", (Factor("l_quantity"),), "sum_qty"),))
    return [grouped, filtered]


def _oracle(plan: QueryPlan, data: dict):
    if plan.name == "sf1_grouped":
        return {v: {"sum_qty": int(data["l_quantity"][data["l_returnflag"] == v].sum() % T),
                    "sum_price": int(data["l_extendedprice"][data["l_returnflag"] == v].sum() % T),
                    "count": int((data["l_returnflag"] == v).sum() % T)}
                for v in (1, 2, 3)}
    keep = np.isin(data["l_shipmode"], (1, 2)) & (data["l_returnflag"] == 1)
    return {"sum_qty": int(data["l_quantity"][keep].sum() % T)}


def _check_same(a, b, where: str) -> None:
    assert a == b, f"sharded result mismatch ({where}): {a} != {b}"


def _run_arm(db, data, plan, shard_counts, costs) -> list[dict]:
    """One strong-scaling curve: same table, rising shard count."""
    rows, base = [], None
    oracle = _oracle(plan, data)
    for s in shard_counts:
        pl = Planner(db, shards=s)
        db.bk.stats.reset()
        t0 = time.time()
        got = run_via_plan(pl, plan)
        wall = time.time() - t0
        _check_same(got, oracle, f"{plan.name} @ {s} vs oracle")
        if base is None:
            base = got
        _check_same(got, base, f"{plan.name} @ {s} vs 1 shard")
        ctx = pl.shard_ctx
        modeled = ctx.modeled_seconds(costs)
        rows.append({
            "query": plan.name, "shards": s,
            "nblocks": db.tables["lineitem"].nblocks,
            "modeled_s": round(modeled, 2),
            "dist_units": sum(ctx.dist.values()),
            "repl_units": sum(ctx.repl.values()),
            "folds": ctx.folds, "mock_wall_s": round(wall, 2),
        })
    t1 = rows[0]["modeled_s"]
    for r in rows:
        r["speedup"] = round(t1 / r["modeled_s"], 2)
    return rows


def _weak_scaling(bk, shard_counts, costs, blocks_per_shard: int) -> list[dict]:
    """Fixed work per shard: table grows with the shard count, so the
    modeled time should stay ~flat (the replicated tail is the
    Amdahl floor)."""
    plan = _arms()[1]
    rows = []
    for s in shard_counts:
        nrows = blocks_per_shard * s * bk.slots - 7     # uneven tail block
        db, data = _lineitem_db(bk, nrows)
        pl = Planner(db, shards=s)
        got = run_via_plan(pl, plan)
        _check_same(got, _oracle(plan, data), f"weak @ {s}")
        rows.append({
            "shards": s, "nblocks": db.tables["lineitem"].nblocks,
            "modeled_s": round(pl.shard_ctx.modeled_seconds(costs), 2),
        })
    return rows


def _limb_sweep(db, data, costs, limb_shards: int, quick: bool) -> list[dict]:
    """Model-axis strong scaling: same table, the k RNS limbs split over
    M devices.  Decrypt must stay byte-identical at every M (the gather
    key-switch preserves the summation order exactly); the ledger prices
    limb-local work at 1/limb_factor and charges the all-gathered
    key-switch digits at gather_byte * (M-1)/M per byte."""
    plan = _arms()[1]                      # filtered arm: cheapest scan
    oracle = _oracle(plan, data)
    sweep = sorted({1, 2, limb_shards} & set(range(1, limb_shards + 1)))
    rows, base = [], None
    for m in sweep:
        pl = Planner(db, shards=1, limb_shards=m)
        got = run_via_plan(pl, plan)
        _check_same(got, oracle, f"limb sweep @ {m} vs oracle")
        if base is None:
            base = got
        _check_same(got, base, f"limb sweep @ {m} vs limb_shards=1")
        ctx = pl.shard_ctx
        rows.append({
            "limb_shards": m,
            "modeled_s": round(ctx.modeled_seconds(costs), 2),
            "limb_factor": ctx.limb_factor(),
            "gathers": ctx.gathers,
            "gather_bytes": int(ctx.gather_bytes),
            "limb_local_bytes": int(ctx.limb_local_bytes),
        })
    t1 = rows[0]["modeled_s"]
    for r in rows:
        r["speedup"] = round(t1 / r["modeled_s"], 2)
    return rows


def main(quick: bool = False, limb_shards: int | None = None) -> str:
    bk = MockBackend()
    costs = op_costs(quick)
    shard_counts = (1, 2) if quick else (1, 2, 4, 8)
    nrows = 8 * bk.slots - 1000 if quick else SF1_ROWS
    db, data = _lineitem_db(bk, nrows)

    strong = []
    for plan in _arms():
        strong += _run_arm(db, data, plan, shard_counts, costs)

    weak = _weak_scaling(bk, shard_counts, costs,
                         blocks_per_shard=2 if quick else 23)

    # Uneven tables pad to the shard multiple and stay byte-identical:
    # 6 blocks at 4 shards -> 8 physical lanes.
    pad_db, pad_data = _lineitem_db(bk, 6 * bk.slots - 11)
    pad_plan = _arms()[1]
    pad_got = run_via_plan(Planner(pad_db, shards=4 if not quick else 2), pad_plan)
    _check_same(pad_got, _oracle(pad_plan, pad_data), "uneven padding")

    speedups = {r["shards"]: r["speedup"] for r in strong
                if r["query"] == "sf1_grouped"}
    if quick:
        assert speedups[2] >= 1.0, f"smoke: no speedup at 2 shards: {speedups}"
    else:
        assert speedups[4] > 1.5, f"acceptance: {speedups[4]}x at 4 shards"

    payload = {
        "profile": {"n": bk.slots, "t": bk.t, "k": bk.profile.k},
        "rows": nrows, "quick": quick, "costs": costs,
        "strong_scaling": strong, "weak_scaling": weak,
        "speedups_grouped": speedups,
    }
    save_json("sharded_scan.json", payload)

    out = table(strong, f"strong scaling, {nrows} rows "
                        f"({db.tables['lineitem'].nblocks} blocks)")
    out += table(weak, "weak scaling (fixed blocks per shard)")
    out += (f"modeled speedup at {max(shard_counts)} shards: "
            f"{fmt_s(strong[0]['modeled_s'])} -> "
            f"{fmt_s(strong[len(shard_counts) - 1]['modeled_s'])}\n")

    if limb_shards is not None and limb_shards > 1:
        limb_rows = _limb_sweep(db, data, costs, limb_shards, quick)
        top = limb_rows[-1]
        if quick:
            assert top["speedup"] >= 1.0, \
                f"smoke: limb axis slowdown: {limb_rows}"
        else:
            assert top["speedup"] > 1.0, \
                f"acceptance: no limb-axis speedup: {limb_rows}"
        save_json("limb_sharding.json", {
            "profile": {"n": bk.slots, "t": bk.t, "k": bk.profile.k},
            "rows": nrows, "quick": quick,
            "gather_byte_s": costs["gather_byte"],
            "sweep": limb_rows,
        })
        out += table(limb_rows, "limb sharding (model axis, filtered arm)")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="8-block table, shards (1, 2): CI smoke mode")
    ap.add_argument("--limb-shards", type=int, default=None, metavar="M",
                    help="also sweep the model (RNS limb) axis up to M "
                         "and emit results/limb_sharding.json")
    a = ap.parse_args()
    print(main(quick=a.smoke, limb_shards=a.limb_shards))

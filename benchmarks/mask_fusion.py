"""Fused cross-mask launches vs the per-predicate legacy schedule.

The compiled operator DAG (engine/physical.py + engine/executor.py)
evaluates every distinct comparison circuit of a query in ONE stacked
launch per circuit shape — all EQ square chains together, all LT
interpolants together, across columns and tables — and CSE-deduplicates
repeated (column, op, value) subgraphs.  This benchmark measures that
against the pre-DAG schedule (one launch per predicate, no sharing) on
the two queries the refactor targets:

  Q1   9 group/WHERE EQ circuits collapse to 5 (CSE) in 1 fused launch
  Q19  ~30 per-branch part/lineitem circuit launches collapse to one EQ
       and one LT launch; the shared `p_size >= 1` atoms are CSE hits

Launch count = primitive *calls* into the backend (OpStats.launches, the
quantity batching removes); ct_mul / max_depth are charged per block and
must NOT improve from fusion alone — equal op-depth accounting — only
from CSE.  Wall-clock is the mock backend at the paper profile.
"""
from __future__ import annotations

import time

from repro.engine import ops
from repro.engine import queries as Q
from repro.engine import tpch
from repro.engine.backend import MockBackend
from repro.engine.executor import run_via_plan
from repro.engine.planner import Planner

from .common import save_json, table

QUERIES = ["Q1", "Q19"]


def _measure(bk, fn):
    bk.stats.reset()
    bk.op_log.clear()
    t0 = time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    s = bk.stats.clone()
    return s, bk.op_log["eq"] + bk.op_log["cmp"], wall


def _planner(db, fused: bool) -> Planner:
    pl = Planner(db, optimized=True)
    pl.fuse_masks = fused
    pl.share_masks = fused
    return pl


def _mask_phase(pl: Planner, db, qn: str) -> None:
    """Predicate-mask evaluation only (no aggregation): Q1's WHERE + the
    3x2 group-pair EQ grid as the legacy nested loop walks it (the inner
    dictionary re-evaluated per outer value — CSE's target), and Q19's
    full three-branch WHERE tree including the part-side translates."""
    bk = pl.bk
    li = db.tables["lineitem"]
    if qn == "Q1":
        plan = Q.plan_q1()
        where = pl.where_mask(li, plan.where)
        rf = li.schema.col("l_returnflag").dictionary
        ls = li.schema.col("l_linestatus").dictionary
        for _, rv in sorted(rf.items()):
            rfm = dict(pl.group_masks(li, "l_returnflag", [rv]))[rv]
            for _, lv in sorted(ls.items()):
                lsm = dict(pl.group_masks(li, "l_linestatus", [lv]))[lv]
                ops.and_masks(bk, [rfm, lsm, where])
    else:
        pl.where_mask(li, Q.plan_q19().where)


def bfv_mask_phase(quick: bool = False) -> list[dict]:
    """The same fused-vs-separate schedule on REAL ciphertexts (micro
    t=257 domain): here per-launch dispatch overhead is genuine, so the
    launch reduction turns into wall-clock."""
    import numpy as np

    from repro.core.params import make_params
    from repro.engine.backend import BFVBackend
    from repro.engine.plan import And, Pred
    from repro.engine.schema import ColumnSpec, TableSchema
    from repro.engine.storage import Database

    bk = BFVBackend(make_params(n=128, t=257, k=12), seed=5)
    db = Database(bk)
    rng = np.random.default_rng(5)
    n = 128 if quick else 512                     # 1 / 4 ciphertext blocks
    db.load_table(TableSchema("sales", [
        ColumnSpec("day", "int"), ColumnSpec("price", "int"),
        ColumnSpec("qty", "int")]), {
        "day": rng.integers(1, 101, n), "price": rng.integers(1, 101, n),
        "qty": rng.integers(1, 11, n)}, n)
    tbl = db.tables["sales"]
    expr = And((Pred("day", "<", 50), Pred("qty", ">=", 3),
                Pred("price", "between", (20, 80)), Pred("day", ">", 5),
                Pred("qty", "=", 7)))
    rows = []
    results = {}
    for arm, fused in (("separate", False), ("fused", True)):
        times = []
        for rep in range(3):                      # rep 0 warms the jit cache
            pl = _planner(db, fused)
            bk.stats.reset()
            t0 = time.perf_counter()
            mask = pl.where_mask(tbl, expr)
            times.append(time.perf_counter() - t0)
            results[arm] = bk.decrypt(mask[0])
        rows.append({
            "backend": f"bfv(n=128,t=257) x{tbl.nblocks} blocks",
            "arm": arm,
            "launches": bk.stats.launches,
            "ct_mul": bk.stats.mul,
            "wall_ms": round(min(times[1:]) * 1e3, 1),
        })
    assert (results["separate"] == results["fused"]).all(), "mask drift"
    save_json("mask_fusion_bfv.json", rows)
    return rows


def run(scale=None, quick: bool = False) -> list[dict]:
    scale = scale or (tpch.Scale.tiny() if quick else tpch.Scale.small())
    bk = MockBackend()
    db = tpch.load(bk, scale)
    rows = []
    for qn in QUERIES:
        plan_f, run_f, oracle_f = Q.QUERIES[qn]
        # Mask phase in isolation: separate (per-predicate launches, no
        # sharing) vs fused (cross-mask batches + CSE).
        msep, msep_circ, msep_wall = _measure(
            bk, lambda: _mask_phase(_planner(db, False), db, qn))
        mfus, mfus_circ, mfus_wall = _measure(
            bk, lambda: _mask_phase(_planner(db, True), db, qn))
        # Whole query end to end: legacy body unfused vs compiled DAG.
        sep, _, sep_wall = _measure(bk, lambda: run_f(_planner(db, False)))
        got = {}
        fused, _, fused_wall = _measure(
            bk, lambda: got.update(run_via_plan(_planner(db, True), plan_f())))
        assert got == oracle_f(db), f"{qn}: fused result != oracle"
        assert fused.max_depth == sep.max_depth, "op-depth accounting drifted"
        rows.append({
            "query": qn,
            "mask_launches_sep": msep.launches,
            "mask_launches_fused": mfus.launches,
            "mask_launch_ratio": round(msep.launches / mfus.launches, 2),
            "circuits_sep": msep_circ,
            "circuits_fused": mfus_circ,
            "mask_wall_sep_s": round(msep_wall, 3),
            "mask_wall_fused_s": round(mfus_wall, 3),
            "query_launches_sep": sep.launches,
            "query_launches_fused": fused.launches,
            "query_ct_mul_sep": sep.mul,
            "query_ct_mul_fused": fused.mul,
            "max_depth": fused.max_depth,
            "query_wall_sep_s": round(sep_wall, 3),
            "query_wall_fused_s": round(fused_wall, 3),
        })
    save_json("mask_fusion.json", rows)
    return rows


def main(quick: bool = False) -> str:
    out = table(run(quick=quick),
                "Cross-mask fusion + CSE — compiled DAG vs per-predicate "
                "launches (mock backend, optimized regime)")
    out += "\n" + table(bfv_mask_phase(quick=quick),
                        "Fused mask evaluation on real BFV ciphertexts "
                        "(5-predicate WHERE, launch overhead is real)")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

"""Recovery overhead under injected faults (runtime/faults.py,
DESIGN §9).

For each query of the executable TPC-H mix (Q1, Q6, Q12, Q19) we run a
fault-free baseline and then one run per fault class — transient noise
under-prediction, device loss mid-scan, a 10x straggler, and a poisoned
mask cache — and compare circuit-launch counts and recovery events.
Launches are the overhead metric because they are deterministic: the
stage checkpoints mean a retry replays completed stages from their
materialized masks instead of recomputing them, so a recovered run
should relaunch only the failed tail.  The headline contract asserted
here (and in CI's tests-chaos lane via --smoke): every recovered run
decrypts byte-identical to its baseline, and worst-case launch overhead
stays under 2x fault-free.

Emits results/fault_recovery.json.
"""
from __future__ import annotations

from repro.core.noise import NoiseProfile
from repro.engine import queries as Q
from repro.engine import tpch
from repro.engine.backend import MockBackend
from repro.engine.executor import Executor
from repro.engine.planner import Planner
from repro.engine.workload import WorkloadCache
from repro.runtime import faults
from repro.runtime.elastic import StragglerDetector

from .common import op_costs, save_json, table

MIX = list(Q.PLAN_EXECUTABLE)             # Q1, Q6, Q12, Q19
MULTIBLOCK = NoiseProfile(n=64, t=65537, k=30)
# Calibrated per-op seconds: straggler thresholds are relative to the
# fleet median, so any consistent cost scale gives the same exclusions.
COSTS = op_costs(quick=True)
MAX_OVERHEAD = 2.0


def _exec(db, qname, fault_plan=None, shards=2, cache=None, det=None):
    pl = Planner(db, optimized=True, shards=shards, cache=cache)
    if det is not None:
        pl.attach_straggler_detector(det, COSTS)
    ex = Executor(pl)
    qplan = Q.QUERIES[qname][0]()
    if fault_plan is None:
        out = ex.run(qplan)
    else:
        with faults.inject(fault_plan):
            out = ex.run(qplan)
    return out, ex.report


def _scenarios(db, qname):
    """(label, runner) pairs; each runner returns (result, report)."""
    def overflow():
        return _exec(db, qname, faults.FaultPlan(underpredict_bits=500.0,
                                                 underpredict_count=3))

    def device_loss():
        return _exec(db, qname, faults.FaultPlan(device_loss_stage="any",
                                                 device_loss_worker=1))

    def straggler():
        det = StragglerDetector(threshold=2.0, patience=1, timeout_s=1e9)
        fp = faults.FaultPlan(straggler_slowdown={3: 10.0})
        pl = Planner(db, optimized=True, shards=4)
        pl.attach_straggler_detector(det, COSTS)
        with faults.inject(fp):
            ex = Executor(pl)
            ex.run(Q.QUERIES[qname][0]())         # round 1: strike + reshard
            ex2 = Executor(pl)
            out = ex2.run(Q.QUERIES[qname][0]())  # round 2: on survivors
        return out, ex2.report

    def cache_poison():
        # One corrupted entry (a realistic bit-flip event; wholesale
        # corruption is a correctness case in tests/test_chaos.py, and
        # its unfused per-atom re-derivation costs more than a cold run).
        cache = WorkloadCache()
        pl = Planner(db, optimized=True, cache=cache)
        Executor(pl).run(Q.QUERIES[qname][0]())   # populate
        faults.poison_cache(cache, db.bk, entries=1)
        ex = Executor(pl)
        out = ex.run(Q.QUERIES[qname][0]())
        assert cache.stats.poison_drops > 0
        return out, ex.report

    return [("overflow", overflow), ("device-loss", device_loss),
            ("straggler", straggler), ("cache-poison", cache_poison)]


def run(quick: bool = False) -> dict:
    bk = MockBackend(MULTIBLOCK)
    db = tpch.load(bk, tpch.Scale.tiny(), seed=7)
    queries = ["Q6"] if quick else MIX

    rows, worst = [], 0.0
    for qname in queries:
        base_out, base_rep = _exec(db, qname)
        for fault, runner in _scenarios(db, qname):
            out, rep = runner()
            assert out == base_out, \
                f"{fault}/{qname}: recovered decrypt differs from baseline"
            base_launch = max(base_rep.launches, 1)
            overhead = rep.launches / base_launch
            worst = max(worst, overhead)
            rows.append({
                "query": qname,
                "fault": fault,
                "base_launches": base_rep.launches,
                "launches": rep.launches,
                "overhead": round(overhead, 3),
                "recoveries": len(rep.recoveries),
                "refreshes": rep.refreshes,
            })

    payload = {
        "profile": {"n": MULTIBLOCK.n, "t": MULTIBLOCK.t, "k": MULTIBLOCK.k},
        "queries": queries,
        "rows": rows,
        "summary": {
            "worst_launch_overhead": round(worst, 3),
            "budget": MAX_OVERHEAD,
            "all_identical": True,        # asserted above per scenario
            "total_recoveries": sum(r["recoveries"] for r in rows),
        },
    }
    save_json("fault_recovery.json", payload)
    assert worst < MAX_OVERHEAD, \
        f"worst recovery launch overhead {worst:.2f}x >= {MAX_OVERHEAD}x budget"
    return payload


def main(quick: bool = False) -> str:
    payload = run(quick=quick)
    s = payload["summary"]
    out = table(payload["rows"],
                "Fault recovery — launch overhead vs fault-free baseline "
                "(mock backend, paper noise profile, stage checkpoints)")
    out += (f"\nworst launch overhead {s['worst_launch_overhead']}x "
            f"(budget {s['budget']}x), {s['total_recoveries']} recoveries, "
            f"all decrypts identical to baseline")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single-query run + overhead assertion (CI mode)")
    print(main(quick=ap.parse_args().smoke))

"""Static plan verification cost: wall-clock of `Planner.verify` per
TPC-H builder and regime, against the execution time it fronts.

The verifier (engine/verify.py, DESIGN §10) re-executes the compiled
DAG over abstract noise states — scalar model arithmetic instead of
32768-slot ciphertext ops — so admission should cost milliseconds per
query while the guarded execution costs seconds.  This benchmark pins
that ratio and the per-query verdicts down in results/static_verify.json
so a verifier-cost regression (or a shipped plan going red) shows up in
the smoke lane.
"""
from __future__ import annotations

import time

from repro.engine import queries as Q
from repro.engine import tpch
from repro.engine.backend import MockBackend
from repro.engine.executor import Executor
from repro.engine.planner import Planner

from .common import save_json, table


def main(quick: bool = False) -> str:
    bk = MockBackend()
    db = tpch.load(bk, tpch.Scale.tiny())
    names = list(Q.PLAN_EXECUTABLE)
    if quick:
        names = ["Q6", "Q19"]           # shallowest + deepest shipped DAG
    rows = []
    for qn in names:
        for optimized in (True, False):
            pl = Planner(db, optimized=optimized, verify=False)
            cq = Executor(pl).compile(Q.QUERIES[qn][0]())
            t0 = time.time()
            rep = pl.verify(cq.plan)
            verify_s = time.time() - t0
            t0 = time.time()
            Executor(pl).run(Q.QUERIES[qn][0]())
            exec_s = time.time() - t0
            rows.append({
                "query": qn,
                "regime": "optimized" if optimized else "unoptimized",
                "verdict": "ok" if rep.ok else "FAIL",
                "errors": len(rep.errors),
                "warnings": len(rep.warnings),
                "decrypts": len(rep.decrypts),
                "verify_ms": round(verify_s * 1e3, 1),
                "exec_s": round(exec_s, 2),
                "overhead_pct": round(100.0 * verify_s / max(exec_s, 1e-9), 2),
            })
    worst = max(r["overhead_pct"] for r in rows)
    summary = {
        "all_ok": all(r["verdict"] == "ok" for r in rows),
        "worst_overhead_pct": worst,
        "total_verify_ms": round(sum(r["verify_ms"] for r in rows), 1),
    }
    save_json("static_verify.json", {"rows": rows, "summary": summary})
    out = table(rows, "Static plan verification vs execution (tiny scale)")
    return out + (f"all plans verify clean; worst admission overhead "
                  f"{worst:.2f}% of execution\n")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

"""Table 6: Q6 runtime vs row count within the packing limit — NSHEDB is
flat (one ciphertext covers <= 32,768 rows; every op is whole-ciphertext)
while the bit-level baseline scales linearly with rows."""
from __future__ import annotations

from repro.engine import queries as Q
from repro.engine import tpch
from repro.engine.backend import MockBackend
from repro.engine.baseline import baseline_seconds, nshedb_seconds
from repro.engine.planner import Planner

from .common import fmt_s, paper_costs, save_json, seal_norm_factor, table


def main(quick: bool = False) -> str:
    costs = paper_costs(quick)
    norm = seal_norm_factor(quick)
    rows = []
    sizes = [512, 2048] if quick else [4096, 8192, 16384, 32768]
    for n in sizes:
        bk = MockBackend()
        scale = tpch.Scale(lineitem=n, orders=max(n // 4, 16),
                           customer=16, supplier=8, part=16, partsupp=16)
        db = tpch.load(bk, scale, tables=["lineitem"])
        pl = Planner(db, optimized=True)
        bk.stats.reset()
        bk.op_log.clear()
        Q.run_q6(pl)
        ours = nshedb_seconds(bk.stats, costs) * norm
        he3 = baseline_seconds("he3db", bk.op_log, n)
        rows.append({"rows": n, "nshedb_s": fmt_s(ours),
                     "he3db_model_s": fmt_s(he3),
                     "speedup": round(he3 / max(ours, 1e-9), 1),
                     "ciphertext_blocks": db.tables["lineitem"].nblocks})
    save_json("table6_packing_scaling.json", rows)
    return table(rows, "Table 6 — Q6 scaling within the packing limit")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

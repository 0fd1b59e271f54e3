"""Fig. 7: storage expansion — NSHEDB's packed word-level ciphertexts vs
raw data and vs the ~8000x bit-level systems."""
from __future__ import annotations

from repro.core.noise import paper_profile
from repro.engine import tpch
from repro.engine.backend import MockBackend
from repro.engine.baseline import storage_report

from .common import save_json, table


def main(quick: bool = False) -> str:
    prof = paper_profile()
    rows = []
    for nrows in (4096, 8192, 16384, 32768):
        r = storage_report(prof, nrows, ncols=14, raw_bits=16)
        rows.append({
            "rows": nrows,
            "raw_MB": round(r["raw_bytes"] / 2**20, 2),
            "nshedb_MB": round(r["nshedb_bytes"] / 2**20, 1),
            "bitlevel_MB": round(r["bitlevel_bytes"] / 2**20, 0),
            "expansion_x_16bit": round(r["nshedb_expansion"], 1),
            "expansion_x_64bit": round(prof.expansion_ratio(64), 1),  # paper's ~28x base
            "reduction_vs_bitlevel_x": round(r["reduction_vs_bitlevel"], 1),
        })
    # whole-database view (all eight tables at bench scale)
    bk = MockBackend()
    db = tpch.load(bk, tpch.Scale.tiny() if quick else tpch.Scale.small())
    rows.append({
        "rows": "all 8 tables",
        "raw_MB": round(db.raw_bytes() / 2**20, 3),
        "nshedb_MB": round(db.storage_bytes() / 2**20, 1),
        "bitlevel_MB": round(db.raw_bytes() * 8000 / 2**20, 0),
        "expansion_x": round(db.storage_bytes() / db.raw_bytes(), 1),
        "reduction_vs_bitlevel_x": round(
            db.raw_bytes() * 8000 / db.storage_bytes(), 1),
    })
    save_json("fig7_storage.json", rows)
    return table(rows, "Fig. 7 — storage footprint (16-bit values)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

"""Run every benchmark (one per paper table/figure) + the roofline.

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny scales / fewer sizes (CI mode)")
    ap.add_argument("--only", default=None, help="comma-separated module names")
    args = ap.parse_args()

    from . import (depth_model, fault_recovery, mask_fusion, packing_scaling,
                   primitive_ops, q6_breakdown, roofline, sharded_scan,
                   static_verify, storage, tpch_queries, workload_cache)
    mods = {
        "depth_model": depth_model,
        "static_verify": static_verify,
        "primitive_ops": primitive_ops,
        "storage": storage,
        "q6_breakdown": q6_breakdown,
        "packing_scaling": packing_scaling,
        "mask_fusion": mask_fusion,
        "workload_cache": workload_cache,
        "sharded_scan": sharded_scan,
        "tpch_queries": tpch_queries,
        "fault_recovery": fault_recovery,
        "roofline": roofline,
    }
    if args.only:
        mods = {k: v for k, v in mods.items() if k in args.only.split(",")}
    failed = []
    for name, mod in mods.items():
        t0 = time.time()
        print(f"\n######## {name} ########", flush=True)
        try:
            print(mod.main(quick=args.quick))
        except Exception:
            traceback.print_exc()
            print(f"[{name}] FAILED")
            failed.append(name)
        print(f"[{name}] {time.time() - t0:.1f}s", flush=True)
    if failed:
        raise SystemExit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

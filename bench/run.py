#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (`bench/configs/`), traffic mix
(`bench/traffic/`) and metrics come from BENCHMARK.json at the root of
the checkout.  The run builds keys and the encrypted table from the
seed, warms up, runs a closed loop of queries for `--seconds`, then
checks every answer against the plain reference.  The last line of
standard output is one JSON object; the numbers compared, each with its
limit, are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  A cell whose configuration is laid out for another number of
chips (its `chips`, or its `planner.shards`, 1 where absent) is refused
with exit code 2 before set-up.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_compile_cache")


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) for a cell name."""
    from bench import querygen
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    mix = querygen.load_mix(os.path.join(BENCH_DIR, "traffic",
                                         f"{cell['traffic']}.json"))
    return bench, cell, cfg, mix


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def layout_mismatch(cell: dict, cfg: dict) -> str | None:
    """Why the configuration cannot run on the cell's chips, or None."""
    chips = int(cell["chips"])
    stated = {"chips": int(cfg.get("chips", 1)),
              "planner.shards": int(cfg["planner"].get("shards", 1))}
    for key, n in stated.items():
        if n != chips:
            return (f"cell {cell['name']} asks for {chips} chip(s) but its "
                    f"configuration states {key} = {n}: refusing")
    return None


def require_chips(n: int) -> list:
    """The TPU devices, or SystemExit(2) when there are fewer than n."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"needs {n} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s): refusing", file=sys.stderr)
        raise SystemExit(2)
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)          # `python bench/run.py`: import as `bench.*`
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench, cell, cfg, mix = load_cell(args.workload)
    mismatch = layout_mismatch(cell, cfg)
    if mismatch is not None:
        print(mismatch, file=sys.stderr)
        return 2

    # One fixed cache directory inside the checkout: only a cell's first
    # run there compiles.  The program's own cache setup reads this too.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = require_chips(int(cell["chips"]))

    from bench import harness
    rec, e2e, checks, failed = harness.run_cell(
        cell, cfg, mix, args.seed, args.seconds, bool(args.trace), T_START)
    if args.trace:
        metrics = {}
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                v = harness.read_metric(os.path.join(BENCH_DIR, "metrics"),
                                        m["name"], rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, cell["name"])}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": rec.memory_peak_bytes}
    result = {"correct": failed == 0 and all(
                  c["value"] <= c["limit"] for c in checks.values()),
              "attempted": rec.queries, "failed": failed, "metrics": metrics,
              "device": device}
    if args.trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The control of the benchmark's comparison: the plain reference put
in the program's place with its columns held in float16 and summed in
float32 (bench/reference.py `control`), an approximate answer that
breaks the configuration's exact-results guarantee.  The comparison
that decides `correct` has to fail it.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--queries N]

For each seed it builds the cell's rows and draws the queries a run
would (after its warm-up queries), computes the control's answers with
jax.numpy on the default device, and prints how many values the
comparison finds wrong.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_reading(cfg: dict, mix: dict, seed: int, queries: int,
                    xp=None) -> tuple[int, int]:
    """(values the comparison finds wrong, values compared)."""
    from bench import lineitem, querygen, reference
    cols = lineitem.generate(cfg, seed)
    traffic = querygen.Traffic(mix, seed)
    for _ in range(int(mix["warmup_queries"])):
        traffic.next()
    t = int(cfg["he"]["t"])
    wrong = total = 0
    for _ in range(queries):
        _, args = traffic.next()
        got = reference.control(cols, t, mix["query"], args,
                                **({} if xp is None else {"xp": xp}))
        bad, n = reference.wrong_values(
            got, reference.answer(cols, t, mix["query"], args))
        wrong, total = wrong + bad, total + n
    return wrong, total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--queries", type=int, default=1,
                    help="queries compared per seed, as many as a run has")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import jax.numpy as jnp
    from bench.run import load_cell
    _, cell, cfg, mix = load_cell(args.workload)
    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        wrong, total = control_reading(cfg, mix, seed, args.queries, xp=jnp)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control_wrong_values": wrong, "compared": total,
                          "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

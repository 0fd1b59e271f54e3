"""Table 3: multiplicative depth per operator — analytic formula vs the
depth actually measured on the mock backend at paper parameters."""
from __future__ import annotations

import math

import numpy as np

from repro.core import compare as cmp
from repro.engine.backend import MockBackend

from .common import save_json, table


def _measure(fn) -> int:
    bk = MockBackend()
    x = bk.encrypt(np.arange(64))
    y = bk.encrypt(np.arange(64)[::-1])
    bk.stats.reset()
    fn(bk, x, y)
    return bk.stats.max_depth


def main(quick: bool = False) -> str:
    t = 65537
    n = 32768
    lg = math.ceil(math.log2(t - 1))
    rows = [
        {"operator": "equality", "formula": "ceil(log2(p-1))", "predicted": lg,
         "measured": _measure(lambda bk, x, y: cmp.eq_ct(bk, x, y))},
        {"operator": "comparison (<)", "formula": "ceil(log2(p-1)) + 1",
         "predicted": lg + 1,
         "measured": _measure(lambda bk, x, y: cmp.lt_ct(bk, x, y))},
        {"operator": "between", "formula": "ceil(log2(p-1)) + 2",
         "predicted": lg + 2,
         "measured": _measure(lambda bk, x, y: cmp.between_scalar(bk, x, 3, 9))},
        {"operator": "in (k=4)", "formula": "ceil(log2(p-1)) + log(k)/p",
         "predicted": lg,
         "measured": _measure(lambda bk, x, y: cmp.in_set(bk, x, [1, 2, 3, 4]))},
        {"operator": "aggregation", "formula": "log(n)/p  (rotations only)",
         "predicted": 0,
         "measured": _measure(lambda bk, x, y: bk.sum_slots(x))},
        {"operator": "join (EQ+mask)", "formula": "ceil(log2(p-1)) + 1",
         "predicted": lg + 1,
         "measured": _measure(lambda bk, x, y: bk.mul(cmp.eq_ct(bk, x, y), y))},
        {"operator": "group by (per value)", "formula": "ceil(log2(p-1))",
         "predicted": lg,
         "measured": _measure(lambda bk, x, y: cmp.eq_scalar(bk, x, 3))},
    ]
    for r in rows:
        r["ok"] = r["measured"] <= r["predicted"]
    save_json("table3_depth_model.json", rows)
    return table(rows, "Table 3 — multiplicative depth per operator (t=65537)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

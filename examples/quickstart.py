"""Quickstart: encrypted SQL in 60 lines.

Loads a tiny table under real RNS-BFV (t=257 micro parameters so it runs
in seconds), then evaluates

    SELECT SUM(price), COUNT(*) FROM sales
    WHERE day < 50 AND qty >= 3

entirely on ciphertexts — equality/range masks via arithmetic circuits,
aggregation via rotate-reduce — and decrypts only the final scalars.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.params import make_params
from repro.engine.backend import BFVBackend
from repro.engine.plan import Agg, And, Factor, Pred
from repro.engine.planner import Planner
from repro.engine.schema import ColumnSpec, TableSchema
from repro.engine.storage import Database


def main():
    print("keygen (n=128, t=257, 12 RNS limbs) ...")
    bk = BFVBackend(make_params(n=128, t=257, k=12), seed=0)

    rng = np.random.default_rng(42)
    n = 50
    data = {"day": rng.integers(1, 101, n),
            "price": rng.integers(1, 101, n),
            "qty": rng.integers(1, 11, n)}
    schema = TableSchema("sales", [ColumnSpec("day", "int"),
                                   ColumnSpec("price", "int"),
                                   ColumnSpec("qty", "int")])
    db = Database(bk)
    db.load_table(schema, data, n)
    print(f"encrypted {n} rows into {db.tables['sales'].ct_count} ciphertexts")

    pl = Planner(db, optimized=True)
    tbl = db.tables["sales"]
    where = And((Pred("day", "<", 50), Pred("qty", ">=", 3)))
    mask = pl.where_mask(tbl, where)

    total = pl.aggregate(tbl, Agg("sum", (Factor("price"),), "s"), mask)
    cnt = pl.aggregate(tbl, Agg("count", (), "c"), mask)

    sel = (data["day"] < 50) & (data["qty"] >= 3)
    got_sum, got_cnt = int(bk.decrypt(total)[0]), int(bk.decrypt(cnt)[0])
    print(f"SUM(price) = {got_sum}   (plaintext: {int(data['price'][sel].sum()) % bk.t})")
    print(f"COUNT(*)   = {got_cnt}   (plaintext: {int(sel.sum())})")
    print(f"ct-ct muls: {bk.stats.mul}, rotations: {bk.stats.rotate}, "
          f"refreshes: {bk.stats.refresh} (planner kept the budget)")
    assert got_sum == int(data["price"][sel].sum()) % bk.t
    assert got_cnt == int(sel.sum())
    print("OK")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

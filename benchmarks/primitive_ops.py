"""Table 4: primitive database operations — NSHEDB per-op latency
(measured/extrapolated on our JAX BFV) vs the paper's HE3DB/ArcEDB
numbers, reported per slot at 32K rows like the paper.

Also measures the batched column path (one stacked jitted call for a
whole column of blocks) against the per-block Python loop on the real
RNS-BFV backend — the before/after of the batched evaluation layer —
for pointwise add, plaintext multiply, ct-ct multiply, and the raw
forward NTT."""
from __future__ import annotations

import time

import numpy as np

from repro.engine.backend import BFVBackend, MockBackend
from repro.engine.baseline import TABLE4_MS_PER_SLOT
from repro.core import compare as cmp

from .common import paper_costs, save_json, seal_norm_factor, table


def op_counts() -> dict[str, object]:
    """Run each primitive once on the mock backend; return its OpStats."""
    out = {}
    ops_to_run = {
        "count": lambda bk, x: bk.sum_slots(x),
        "sum": lambda bk, x: bk.sum_slots(bk.mul(x, x)),
        "eq": lambda bk, x: cmp.eq_scalar(bk, x, 7),
        "cmp": lambda bk, x: cmp.lt_scalar(bk, x, 7),
        "between": lambda bk, x: cmp.between_scalar(bk, x, 3, 9),
        "in": lambda bk, x: cmp.in_set(bk, x, [1, 2, 3]),
        "groupby": lambda bk, x: [cmp.eq_scalar(bk, x, v) for v in (1, 2, 3)],
    }
    for name, fn in ops_to_run.items():
        bk = MockBackend()
        x = bk.encrypt(np.arange(100))
        bk.stats.reset()
        fn(bk, x)
        out[name] = bk.stats.clone()
    return out


def batched_vs_looped(nblocks: int = 8, quick: bool = False) -> list[dict]:
    """Per-op wall clock: batched column call vs per-block loop.

    Real ciphertexts at the test parameter set (n=2048, k=5, or 256/3 in
    quick mode) — large enough that per-call dispatch overhead, the thing
    batching removes, is visible against real kernel work."""
    import jax
    from repro.core.params import make_params, test_params

    params = test_params() if quick else make_params(n=2048, t=65537, k=5)
    bk = BFVBackend(params, seed=0)
    ctx = bk.ctx
    rng = np.random.default_rng(0)
    xs = [bk.encrypt(rng.integers(0, params.t, params.n)) for _ in range(nblocks)]
    ys = [bk.encrypt(rng.integers(0, params.t, params.n)) for _ in range(nblocks)]
    sx, sy = ctx.stack_cts(xs), ctx.stack_cts(ys)
    m_poly = bk.enc.encode(rng.integers(0, params.t, params.n))
    poly_batch = sx.data[:, 0]                      # (nblocks, k, n) limbs

    def timed(fn, out_of):
        jax.block_until_ready(out_of(fn()))         # warmup / compile, drained
        reps = 3 if quick else 10
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn()
        jax.block_until_ready(out_of(r))
        return (time.perf_counter() - t0) / reps

    cases = {
        "add": (lambda: [ctx.add(a, b) for a, b in zip(xs, ys)],
                lambda: ctx.add(sx, sy)),
        "mul_plain": (lambda: [ctx.mul_plain(a, m_poly) for a in xs],
                      lambda: ctx.mul_plain(sx, m_poly)),
        "mul": (lambda: [ctx.mul(a, b, bk.keys.rlk) for a, b in zip(xs, ys)],
                lambda: ctx.mul(sx, sy, bk.keys.rlk)),
        "ntt_fwd": (lambda: [ctx._ntt_q(x.data[0]) for x in xs],
                    lambda: ctx._ntt_q(poly_batch)),
    }

    def leaves(r):
        if isinstance(r, list):
            return [getattr(x, "data", x) for x in r]
        return getattr(r, "data", r)

    rows = []
    for op, (looped, batched) in cases.items():
        t_loop = timed(looped, leaves)
        t_batch = timed(batched, leaves)
        rows.append({
            "op": op,
            "nblocks": nblocks,
            "looped_ms": round(t_loop * 1e3, 3),
            "batched_ms": round(t_batch * 1e3, 3),
            "speedup": round(t_loop / max(t_batch, 1e-9), 2),
        })
    save_json("batched_vs_looped.json", rows)
    return rows


def main(quick: bool = False) -> str:
    costs = paper_costs(quick)
    norm = seal_norm_factor(quick)
    counts = op_counts()
    slots = 32768
    rows = []
    for op, stats in counts.items():
        ours_s = stats.cost_seconds(costs.as_dict())
        ours_ms_slot = ours_s / slots * 1000
        div = 3 if op == "groupby" else 1   # per-distinct-value, like Table 4
        ours = ours_ms_slot / div
        normed = ours * norm                 # anchored to the paper's EQ
        paper = TABLE4_MS_PER_SLOT["nshedb_paper"].get(op)
        row = {
            "op": op,
            "ct_muls": stats.mul,
            "rotations": stats.rotate,
            "ours_jax1core_ms": round(ours, 3),
            "ours_seal_normed_ms": round(normed, 3),
            "nshedb_paper_ms": paper,
            "he3db_ms": TABLE4_MS_PER_SLOT["he3db"].get(op, ""),
            "arcedb_ms": TABLE4_MS_PER_SLOT["arcedb"].get(op, ""),
        }
        if paper:
            row["struct_match"] = round(normed / paper, 2)   # ~1.0 = faithful
        he3 = TABLE4_MS_PER_SLOT["he3db"].get(op)
        if he3:
            row["speedup_vs_he3db"] = round(he3 / max(normed, 1e-9), 1)
        rows.append(row)
    save_json("table4_primitive_ops.json", rows)
    out = table(rows, "Table 4 — primitive operations (ms per slot, 32K rows; "
                      "normed = anchored to the paper's EQ measurement)")
    out += "\n" + table(batched_vs_looped(quick=quick),
                        "Batched column path vs per-block loop (real BFV, "
                        "wall-clock per column op)")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(main())

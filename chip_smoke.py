#!/usr/bin/env python3
"""Chip smoke test: real RNS-BFV at the paper's parameters on a TPU.

One chip (the default):

    python chip_smoke.py

builds the paper's parameter set (n = 32768, t = 65537, k = 30), keys
and the 32,768-row TPC-H lineitem table (paper §5.1, one ciphertext
block per column), then runs TPC-H Q1 and Q6 through the normal engine
path — Planner -> compiled DAG -> static verification -> execution ->
decryption — and checks each decrypted result equals the plaintext
oracle exactly.

Four chips:

    python chip_smoke.py --chips 4

runs only the data-axis sharded scan: Q6 with shards=4 over a
131,072-row lineitem (4 blocks, one per chip), checked against the
oracle, plus where each chip's shard of one column lives.

Every line but the last is informational.  The last line is one JSON
object, {"ok": true, "device": {...}}, printed only when every phase
passed.  Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

SEED = 12


def log(msg: str) -> None:
    print(msg, flush=True)


def key_bytes(keys) -> int:
    ksks = [keys.rlk, *keys.gks.values()]
    return sum(int(k.b.nbytes) + int(k.a.nbytes) for k in ksks)


def sgn_table_phase() -> None:
    """The LT interpolant's coefficient table: built once, then cached."""
    from repro.core import compare
    path = os.path.join(compare._CACHE_DIR, "sgn_65537.npy")
    cached = os.path.exists(path)
    t0 = time.perf_counter()
    compare.sgn_odd_coeffs(65537)
    log(f"sgn_65537 table: {time.perf_counter() - t0:.3f} s "
        f"({'loaded from cache' if cached else 'built on the host'})")


def kernel_phase(params) -> None:
    """Every kernel at the real size, bit-exact against the numpy
    reference (core/ntt.py on the host), on both RNS bases."""
    import jax
    from repro.core import ntt as ref
    from repro.core.limbops import LimbOps
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    for name, tabs in (("Q", params.Q), ("P", params.P)):
        lo = LimbOps(tabs, backend="pallas")
        q = np.asarray(tabs.q)
        a = rng.integers(0, q[:, None], (2, tabs.k, params.n))
        b = rng.integers(0, q[:, None], (2, tabs.k, params.n))
        want = {"mul": a * b % q[:, None], "add": (a + b) % q[:, None],
                "sub": (a - b) % q[:, None],
                "ntt": np.stack([ref.ntt_ref(x, tabs.psi_rev, q) for x in a]),
                "intt": np.stack([ref.intt_ref(x, tabs.ipsi_rev, tabs.n_inv, q)
                                  for x in a])}
        exact = {}
        for op, w in want.items():
            args = (a, b) if op in ("mul", "add", "sub") else (a,)
            got = jax.jit(getattr(LimbOps, op))(lo, *map(jax.numpy.asarray, args))
            exact[op] = bool(np.array_equal(np.asarray(got), w))
        log(f"kernels, base {name} ({tabs.k} limbs x 2 blocks, n={params.n}): "
            f"exact vs numpy reference {exact}")
        assert all(exact.values()), name
    log(f"kernel phase {time.perf_counter() - t0:.1f} s")


def backend_phase(params):
    """Keys and the resolved limb backends of both RNS bases."""
    import jax
    from repro.engine.backend import BFVBackend
    log(f"params: n={params.n} t={params.t} k={params.k} "
        f"log2 Q={params.logQ:.1f} log2 P="
        f"{sum(math.log2(q) for q in params.P.primes):.1f}")
    t0 = time.perf_counter()
    bk = BFVBackend(params, seed=SEED)
    jax.block_until_ready(bk.keys.rlk.b)
    log(f"keygen: {time.perf_counter() - t0:.1f} s, switching keys hold "
        f"{key_bytes(bk.keys)} bytes ({1 + len(bk.keys.gks)} keys); device "
        f"memory stats {jax.devices()[0].memory_stats()}")
    for name in ("limb_q", "limb_p"):
        lo = getattr(bk.ctx, name)
        log(f"{name}: backend={lo.backend} interpret={lo.interpret} "
            f"primes={lo.k}x{max(q.bit_length() for q in lo.primes)}-bit")
        assert (lo.backend, lo.interpret) == ("pallas", False), name
    return bk


def multiply_hlo_phase(bk) -> None:
    """The compiled ct-ct multiply must contain the Pallas kernels."""
    ctx = bk.ctx
    ct = bk.encrypt(np.arange(bk.slots) % bk.t)
    rlk = bk.keys.rlk
    t0 = time.perf_counter()
    compiled = ctx._mul_j.lower(ctx.limb_q, ctx.limb_p, rlk.b, rlk.a,
                                ct.data, ct.data).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    log(f"multiply program: compiled in {time.perf_counter() - t0:.1f} s, "
        f"tpu_custom_call count={calls}, temp bytes="
        f"{getattr(mem, 'temp_size_in_bytes', 'n/a')}")
    assert calls > 0, "compiled multiply has no Pallas kernel"


def op_times_phase(bk) -> None:
    """Warm host-clock milliseconds of the main ops on one block."""
    import jax
    ct = bk.encrypt(np.arange(bk.slots) % bk.t)
    ops = {"mul": lambda: bk.mul(ct, ct), "rotate": lambda: bk.rotate(ct, 1),
           "mul_plain": lambda: bk.mul_plain(ct, np.arange(bk.slots) % 7),
           "mul_scalar+add": lambda: bk.add(bk.mul_scalar(ct, 3), ct)}
    ms = {}
    for name, op in ops.items():
        jax.block_until_ready(op().data)                       # compile
        t0 = time.perf_counter()
        for _ in range(5):
            out = op()
        jax.block_until_ready(out.data)
        ms[name] = round((time.perf_counter() - t0) / 5 * 1e3, 3)
    bk.stats.reset()
    log(f"warm ms per op on one block (mean of 5): {ms}")


def hps_float64_phase(bk) -> None:
    """HPS base conversion Q -> P rounds a float64 sum (core/bfv.py
    `_fbc`).  Run it on the device and compare with exact integer
    arithmetic and with IEEE float64 on the host: random residues must
    convert exactly; near-half probes (X/Q = 1/2 +- 2^-e) show how fine
    the device's rounding is."""
    import jax
    from repro.core.bfv import BFVContext
    p, ctx = bk.params, bk.ctx
    qs, ps = [int(q) for q in p.Q.primes], [int(q) for q in p.P.primes]
    A = p.bigQ()
    rng = np.random.default_rng(SEED)
    xs = [int.from_bytes(rng.bytes(128), "little") % A for _ in range(p.n)]
    exps = list(range(16, 56, 4))
    probes = [A // 2 + s * (A >> e) for e in exps for s in (1, -1)]
    xs[:len(probes)] = probes
    x = np.array([[v % q for v in xs] for q in qs], dtype=np.int64)
    cent = [v - A if 2 * v >= A else v for v in xs]
    exact = np.array([[c % b for c in cent] for b in ps], dtype=np.int64)
    device = np.asarray(jax.jit(
        lambda r: BFVContext._fbc(r, ctx.c_qp, ctx.limb_q, ctx.limb_p))(x))
    hat_inv, hat_mod_b, a_mod_b, a_inv = (np.asarray(c) for c in ctx.c_qp)
    y = x * hat_inv[:, None] % np.asarray(qs)[:, None]
    v = np.round(np.sum(y * a_inv[:, None], axis=0)).astype(np.int64)
    acc = np.sum(y[:, None, :] * hat_mod_b[:, :, None] % np.asarray(ps)[None, :, None], axis=0)
    ieee = (acc - v[None, :] * a_mod_b[:, None]) % np.asarray(ps)[:, None]
    dev_ok, ieee_ok = np.all(device == exact, axis=0), np.all(ieee == exact, axis=0)
    per_e = lambda ok: [e for i, e in enumerate(exps) if ok[2 * i] and ok[2 * i + 1]]
    nrand = p.n - len(probes)
    log(f"hps float64 conversion: random coefficients exact on device "
        f"{int(dev_ok[len(probes):].sum())}/{nrand}; near-half probes exact "
        f"at 2^-e for e in {per_e(dev_ok)} on device, {per_e(ieee_ok)} in host "
        f"IEEE float64; device == host IEEE everywhere: "
        f"{bool(np.all(device == ieee))}")
    assert dev_ok[len(probes):].all(), "float64 HPS conversion is not exact"


def query_phase(pl, name: str, plan, oracle) -> None:
    import jax
    from repro.engine.executor import run_via_plan
    bk = pl.bk
    bk.stats.reset()
    t0 = time.perf_counter()
    res = jax.block_until_ready(run_via_plan(pl, plan, verify=True))
    wall = time.perf_counter() - t0
    want = oracle(pl.db)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    log(f"{name}: wall {wall:.3f} s, ct-muls {bk.stats.mul}, rotations "
        f"{bk.stats.rotate}, refreshes {bk.stats.refresh}, process "
        f"peak_bytes_in_use so far {peak}, equals oracle: {res == want}")
    assert res == want, f"{name} decrypted {res} != oracle {want}"


def paper_params_phase():
    from repro.core.params import paper_params
    t0 = time.perf_counter()
    params = paper_params()
    log(f"paper_params: built in {time.perf_counter() - t0:.1f} s")
    return params


def lineitem_phase(bk, rows: int):
    from repro.engine import tpch
    t0 = time.perf_counter()
    db = tpch.load(bk, tpch.Scale(lineitem=rows), tables=["lineitem"])
    li = db.tables["lineitem"]
    log(f"lineitem: {li.nrows} rows, {li.nblocks} block(s) per column, "
        f"loaded in {time.perf_counter() - t0:.1f} s")
    return db


def one_chip() -> None:
    from repro.engine import queries, tpch
    from repro.engine.planner import Planner
    sgn_table_phase()
    params = paper_params_phase()
    kernel_phase(params)
    bk = backend_phase(params)
    multiply_hlo_phase(bk)
    op_times_phase(bk)
    hps_float64_phase(bk)
    db = lineitem_phase(bk, tpch.Scale().lineitem)
    query_phase(Planner(db, optimized=True), "Q1", queries.plan_q1(),
                queries.oracle_q1)
    query_phase(Planner(db, optimized=True), "Q6", queries.plan_q6(),
                queries.oracle_q6)


def four_chips() -> None:
    from repro.engine import queries
    from repro.engine.planner import Planner
    sgn_table_phase()
    bk = backend_phase(paper_params_phase())
    db = lineitem_phase(bk, 4 * bk.slots)
    pl = Planner(db, optimized=True, shards=4)
    data = show_placement(pl, "l_extendedprice")
    mesh_program_phase(pl, data)
    query_phase(pl, "Q6 shards=4", queries.plan_q6(), queries.oracle_q6)


def show_placement(pl, column: str):
    """Where each chip's lanes of one stacked column live."""
    from repro.engine.sharded import activate
    bk, li = pl.bk, pl.db.tables["lineitem"]
    assert pl.shard_ctx.mesh is not None, "no data mesh"
    with activate(bk, pl.shard_ctx):
        data = bk.stack_blocks(li.col(column).blocks).data
    shards = data.addressable_shards
    for shard in shards:
        lanes = shard.index[0]
        log(f"{column} lanes {lanes.start}:{lanes.stop} on {shard.device} "
            f"shape {tuple(shard.data.shape)}")
    assert len({s.device for s in shards}) == pl.shard_ctx.shards
    return data


def mesh_program_phase(pl, data) -> None:
    """The lane-split multiply on the data mesh: kernels inside, and no
    collective (each chip multiplies its own lanes)."""
    import re
    from repro.engine.sharded import activate
    bk = pl.bk
    with activate(bk, pl.shard_ctx):
        bk._home()
        ctx, rlk = bk.ctx, bk.keys.rlk
        t0 = time.perf_counter()
        text = ctx._lane_program(ctx._mul_j, 4, (True, True), True).lower(
            ctx.limb_q, ctx.limb_p, rlk.b, rlk.a, data, data).compile().as_text()
    counts = {op: len(re.findall(op, text)) for op in (
        "tpu_custom_call", "all-gather", "all-reduce", "collective-permute",
        "all-to-all")}
    log(f"lane-split multiply on the data mesh: compiled in "
        f"{time.perf_counter() - t0:.1f} s, {counts}")
    assert counts["tpu_custom_call"] > 0
    assert counts["all-gather"] == counts["all-reduce"] == 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip sharded Q6 path")
    args = ap.parse_args()
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax sees {devices[0].platform} devices; refusing",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}")
    t0 = time.perf_counter()
    four_chips() if args.chips == 4 else one_chip()
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

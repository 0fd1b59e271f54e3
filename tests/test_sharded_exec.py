"""Sharded scan execution (engine/sharded.py, DESIGN §4).

Parity contract: running a compiled QueryPlan with `shards=N` must be
*byte-identical* to the single-device path — decrypted results, OpStats,
noise trajectories and refresh schedules all match, because padding
lanes are additive identities the accounting never sees.  Verified on
the mock backend at a multi-block profile (n=64 so tiny lineitem spans
3 blocks and exercises uneven padding) and on real RNS-BFV ciphertexts
(micro domain).

Also covered here: the satellites that ride the sharded path — per-lane
noise vectors (partial refresh), fused broadcast_slots, the bounded
WorkloadCache LRU, and elastic re-sharding after straggler exclusion.
The real shard_map/psum collective runs only when the host exposes >= 2
devices (CI sets XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
import dataclasses
import types

import jax
import numpy as np
import pytest

from repro.core.noise import NoiseProfile
from repro.engine import ops, queries as Q, tpch
from repro.engine.backend import MockBackend
from repro.engine.executor import run_via_plan
from repro.engine.plan import Agg, And, Factor, JoinHop, Pred, QueryPlan, Translated
from repro.engine.planner import Planner
from repro.engine.schema import ColumnSpec, TableSchema
from repro.engine.sharded import (ShardContext, activate, make_shard_context,
                                  pad_to, sharded_fold)
from repro.engine.storage import Database
from repro.engine.workload import WorkloadCache
from repro.launch.mesh import make_scan_mesh
from repro.runtime.elastic import StragglerDetector, elastic_scan_plan

# Paper noise accounting (t=65537, 30 limbs) at 64 slots: tiny lineitem
# (192 rows) becomes 3 blocks, so shards=2 pads 3 -> 4 lanes.
MULTIBLOCK = NoiseProfile(n=64, t=65537, k=30)

COSTS = {"mul": 0.05, "mul_plain": 0.055, "mul_scalar": 0.002,
         "add": 0.0015, "rotate": 0.105, "refresh": 44.0}


@pytest.fixture(scope="module")
def mock_mb():
    return MockBackend(MULTIBLOCK)


@pytest.fixture(scope="module")
def db_mb(mock_mb):
    return tpch.load(mock_mb, tpch.Scale.tiny())


def _stats_dict(stats):
    """OpStats minus `dispatches`: the host runs a kernel program once
    per physical lane, so padding lanes and a mesh change that count."""
    d = dataclasses.asdict(stats)
    d.pop("dispatches")
    return d


def _same(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _same(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same(x, y)
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 1. Mock parity: sharded == single-device on every ported query x regime.
# ---------------------------------------------------------------------------

def _run_plan(db, qname, optimized, shards):
    plan = Q.QUERIES[qname][0]()
    pl = Planner(db, optimized=optimized,
                 shards=shards) if shards else Planner(db, optimized=optimized)
    bk = db.bk
    bk.stats.reset()
    got = run_via_plan(pl, plan)
    stats = bk.stats.clone()
    ledger = pl.shard_ctx.ledger_snapshot() if pl.shard_ctx else None
    return got, stats, ledger


@pytest.fixture(scope="module")
def parity_runs(db_mb):
    """(query, regime) -> single-device + sharded executions."""
    out = {}
    for qn in Q.PLAN_EXECUTABLE:
        for opt in (True, False):
            base, base_stats, _ = _run_plan(db_mb, qn, opt, None)
            shard, shard_stats, ledger = _run_plan(db_mb, qn, opt, 2)
            out[(qn, opt)] = (base, base_stats, shard, shard_stats, ledger)
    db_mb.bk.stats.reset()
    return out


@pytest.mark.parametrize("optimized", [True, False])
@pytest.mark.parametrize("qname", Q.PLAN_EXECUTABLE)
def test_mock_parity_decrypt_identical(parity_runs, db_mb, qname, optimized):
    base, _, shard, _, _ = parity_runs[(qname, optimized)]
    _same(base, shard)
    # and both still match the plaintext oracle
    _same(shard, Q.QUERIES[qname][2](db_mb))


@pytest.mark.parametrize("optimized", [True, False])
@pytest.mark.parametrize("qname", Q.PLAN_EXECUTABLE)
def test_mock_parity_stats_identical(parity_runs, qname, optimized):
    """Padding lanes never reach OpStats: identical op/noise accounting."""
    _, base_stats, _, shard_stats, _ = parity_runs[(qname, optimized)]
    assert _stats_dict(base_stats) == _stats_dict(shard_stats)


def test_mock_parity_four_shards(db_mb):
    """3 lineitem blocks pad to 4 at shards=4 (3 zero lanes)."""
    base, base_stats, _ = _run_plan(db_mb, "Q6", True, None)
    shard, shard_stats, ledger = _run_plan(db_mb, "Q6", True, 4)
    _same(base, shard)
    assert _stats_dict(base_stats) == _stats_dict(shard_stats)
    assert ledger["shards"] == 4 and ledger["folds"] > 0


def test_ledger_models_speedup(db_mb):
    """The same query priced at 1 vs 4 shards: distributed scan time
    divides, so modeled seconds strictly drop."""
    secs = {}
    for s in (1, 4):
        plan = Q.QUERIES["Q6"][0]()
        pl = Planner(db_mb, shards=s)
        run_via_plan(pl, plan)
        assert pl.shard_ctx.dist, "scan ops should be distributed"
        secs[s] = pl.shard_ctx.modeled_seconds(COSTS)
    assert secs[4] < secs[1]


# ---------------------------------------------------------------------------
# 2. BFV micro parity: real ciphertexts, custom small-domain plans.
# ---------------------------------------------------------------------------

def _bfv_db(bk):
    """3-block fact table (300 rows at n=128) + a 4-row parent, all
    values inside [0, t/2) for t=257."""
    rng = np.random.default_rng(5)
    n = 300
    fact = TableSchema("fact", [
        ColumnSpec("g", "int"), ColumnSpec("m", "int"),
        ColumnSpec("v", "int"), ColumnSpec("pk_ref", "int"),
    ])
    parent = TableSchema("parent", [
        ColumnSpec("pid", "int"), ColumnSpec("region", "int"),
    ])
    data = {
        "g": rng.integers(1, 4, n), "m": rng.integers(1, 3, n),
        "v": rng.integers(0, 50, n), "pk_ref": rng.integers(1, 5, n),
    }
    pdata = {"pid": np.arange(1, 5), "region": np.array([1, 2, 1, 2])}
    db = Database(bk)
    db.load_table(fact, data, n)
    db.load_table(parent, pdata, 4)
    return db, data, pdata


def _bfv_plans():
    grouped = QueryPlan(
        "g1", "fact",
        where=And((Pred("g", "in", (1, 2)), Pred("m", "=", 1))),
        group_by="g", group_domain=2,
        aggs=(Agg("sum", (Factor("v"),), "sv"), Agg("count", (), "ct")))
    hop = JoinHop(parent="parent", child="fact", fk="pk_ref")
    joined = QueryPlan(
        "j1", "fact",
        where=And((Translated(hop, Pred("region", "=", 1)),
                   Pred("m", "=", 2))),
        aggs=(Agg("sum", (Factor("v"),), "sv"),))
    filtered = QueryPlan(
        "f1", "fact", where=Pred("v", "<", 20),
        aggs=(Agg("sum", (Factor("v"),), "sv"), Agg("count", (), "ct")))
    return [grouped, joined, filtered]


def _bfv_oracle(plan, data, pdata):
    t = 257
    if plan.name == "g1":
        keep = data["m"] == 1
        return {v: {"sv": int(data["v"][keep & (data["g"] == v)].sum() % t),
                    "ct": int((keep & (data["g"] == v)).sum() % t)}
                for v in (1, 2)}
    if plan.name == "j1":
        pr = dict(zip(pdata["pid"], pdata["region"]))
        keep = np.array([pr[k] == 1 for k in data["pk_ref"]]) & (data["m"] == 2)
        return {"sv": int(data["v"][keep].sum() % t)}
    keep = data["v"] < 20
    return {"sv": int(data["v"][keep].sum() % t),
            "ct": int(keep.sum() % t)}


@pytest.mark.parametrize("pname", ["g1", "j1", "f1"])
def test_bfv_micro_sharded_parity(bfv_micro, pname):
    bk = bfv_micro
    db, data, pdata = _bfv_db(bk)
    plan = next(p for p in _bfv_plans() if p.name == pname)
    bk.stats.reset()
    base = run_via_plan(Planner(db), plan)
    base_stats = bk.stats.clone()
    bk.stats.reset()
    shard = run_via_plan(Planner(db, shards=2), plan)
    shard_stats = bk.stats.clone()
    _same(base, shard)
    _same(shard, _bfv_oracle(plan, data, pdata))
    assert _stats_dict(base_stats) == _stats_dict(shard_stats)


# ---------------------------------------------------------------------------
# 3. Padding invariants.
# ---------------------------------------------------------------------------

def test_pad_to():
    assert pad_to(3, 2) == 4
    assert pad_to(3, 4) == 4
    assert pad_to(8, 4) == 8
    assert pad_to(5, 8) == 8
    assert pad_to(3, 1) == 3      # shards=1: no padding
    assert pad_to(1, 8) == 1      # singletons never pad


def test_stack_pads_only_under_context(mock_mb):
    bk = mock_mb
    blocks = [bk.encrypt(np.full(bk.slots, i + 1)) for i in range(3)]
    plain = bk.stack_blocks(blocks)
    assert bk._nblocks_phys(plain) == 3 and bk._nblocks(plain) == 3
    with activate(bk, make_shard_context(2, mesh=None)):
        padded = bk.stack_blocks(blocks)
        assert bk._nblocks_phys(padded) == 4       # 3 -> 4 lanes
        assert bk._nblocks(padded) == 3            # live count unchanged
        # pads are additive identities: fold == unpadded fold
        f_pad = bk.fold_blocks(padded)
    f_plain = bk.fold_blocks(plain)
    np.testing.assert_array_equal(bk.decrypt(f_pad), bk.decrypt(f_plain))
    # unstack returns exactly the live blocks
    outs = bk.unstack_blocks(padded)
    assert len(outs) == 3
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(bk.decrypt(o), bk.decrypt(blocks[i]))


def test_shard_context_validates():
    with pytest.raises(ValueError):
        ShardContext(0)


# ---------------------------------------------------------------------------
# 4 + 5. Per-lane noise vectors: partial refresh / ensure_levels.
# ---------------------------------------------------------------------------

def _burned_pair(bk):
    """(fresh, nearly-exhausted) same-plaintext pair."""
    fresh = bk.encrypt(np.full(bk.slots, 2))
    hot = bk.encrypt(np.full(bk.slots, 3))
    while bk.levels_left(hot) > 0:
        hot = bk.mul(hot, bk.encrypt(np.ones(bk.slots)))
    return fresh, hot


def test_partial_refresh_charges_exhausted_lane_only(mock_mb):
    bk = mock_mb
    fresh, hot = _burned_pair(bk)
    batch = bk.stack_blocks([fresh, hot])
    assert np.ndim(batch.noise) == 1          # heterogeneous -> vector
    bk.stats.reset()
    out = bk.mul(batch, batch)                # lane 1 must refresh first
    assert bk.stats.refresh == 1              # NOT 2: lane 0 still has room
    vals = [bk.decrypt(b) for b in bk.unstack_blocks(out)]
    np.testing.assert_array_equal(vals[0], np.full(bk.slots, 4))
    np.testing.assert_array_equal(vals[1], np.full(bk.slots, 9))
    bk.stats.reset()


def test_ensure_levels_refreshes_short_lanes_only(mock_mb):
    bk = mock_mb
    fresh, hot = _burned_pair(bk)
    batch = bk.stack_blocks([fresh, hot])
    per0 = np.asarray(batch.noise).copy()
    bk.stats.reset()
    bk.ensure_levels(batch, 3)
    assert bk.stats.refresh == 1
    # hot lane now fresh again; lane 0 was already fresh, so the packed
    # noise collapses back to the uniform scalar == lane 0's old value
    assert float(np.max(batch.noise)) == per0[0]
    assert bk.levels_left(batch) >= 3
    bk.stats.reset()


def test_pack_noises_scalar_when_uniform(mock_mb):
    bk = mock_mb
    blocks = [bk.encrypt(np.zeros(bk.slots)) for _ in range(3)]
    batch = bk.stack_blocks(blocks)
    assert np.ndim(batch.noise) == 0          # uniform stays scalar


# ---------------------------------------------------------------------------
# 6. Bounded WorkloadCache: LRU eviction + counters.
# ---------------------------------------------------------------------------

def _atom(i):
    return types.SimpleNamespace(key=("tbl", "c", i), table="tbl")


def test_lru_eviction_bound_and_counter(mock_mb):
    bk = mock_mb
    cache = WorkloadCache(max_entries=2)
    blocks = [bk.encrypt(np.zeros(bk.slots))]
    for i in range(4):
        cache.insert(bk, _atom(i), blocks)
    assert len(cache.entries) == 2
    assert cache.stats.evictions == 2
    assert not cache.contains(_atom(0).key) and not cache.contains(_atom(1).key)
    assert cache.contains(_atom(2).key) and cache.contains(_atom(3).key)


def test_lru_serve_refreshes_recency(mock_mb):
    bk = mock_mb
    cache = WorkloadCache(max_entries=2)
    blocks = [bk.encrypt(np.zeros(bk.slots))]
    cache.insert(bk, _atom(0), blocks)
    cache.insert(bk, _atom(1), blocks)
    assert cache.serve(bk, _atom(0), 1) is not None   # 0 becomes MRU
    cache.insert(bk, _atom(2), blocks)                # evicts 1, not 0
    assert cache.contains(_atom(0).key)
    assert not cache.contains(_atom(1).key)
    assert cache.stats.evictions == 1


def test_lru_fk_banks_bounded(mock_mb):
    bk = mock_mb
    cache = WorkloadCache(max_entries=1)
    bank = [[bk.encrypt(np.zeros(bk.slots))]]
    cache.fk_store(bk, "t", "fk_a", 4, bank)
    cache.fk_store(bk, "t", "fk_b", 4, bank)
    assert len(cache.fk_banks) == 1
    assert cache.stats.evictions == 1
    assert cache.fk_lookup(bk, "t", "fk_b", 4) is not None
    assert cache.fk_lookup(bk, "t", "fk_a", 4) is None


def test_unbounded_cache_never_evicts(mock_mb):
    bk = mock_mb
    cache = WorkloadCache()
    blocks = [bk.encrypt(np.zeros(bk.slots))]
    for i in range(8):
        cache.insert(bk, _atom(i), blocks)
    assert len(cache.entries) == 8 and cache.stats.evictions == 0


# ---------------------------------------------------------------------------
# 7. Fused broadcast_slots: one stacked launch, identical accounting.
# ---------------------------------------------------------------------------

def test_broadcast_slots_fused_parity(mock_mb):
    bk = mock_mb
    packed = bk.encrypt(np.arange(1, bk.slots + 1))
    idxs = [0, 3, 7, 11]
    bk.stats.reset()
    loop = [bk.broadcast_slot(packed, i) for i in idxs]
    loop_stats = bk.stats.clone()
    bk.stats.reset()
    fused = ops.broadcast_slots(bk, packed, idxs)
    fused_stats = bk.stats.clone()
    for l, f in zip(loop, fused):
        np.testing.assert_array_equal(bk.decrypt(l), bk.decrypt(f))
    # identical op-unit/noise accounting, strictly fewer launches
    for field in ("mul_plain", "rotate", "add", "refresh"):
        assert getattr(fused_stats, field) == getattr(loop_stats, field), field
    assert fused_stats.launches < loop_stats.launches
    bk.stats.reset()


def test_broadcast_slots_single_index_falls_back(mock_mb):
    bk = mock_mb
    packed = bk.encrypt(np.arange(bk.slots))
    [one] = ops.broadcast_slots(bk, packed, [5])
    np.testing.assert_array_equal(bk.decrypt(one), np.full(bk.slots, 5))


# ---------------------------------------------------------------------------
# 8. Elastic re-shard after straggler exclusion.
# ---------------------------------------------------------------------------

def test_elastic_scan_plan_powers_of_two():
    plan = elastic_scan_plan(8, [3])
    assert plan["shards"] == 4 and plan["workers_idle"] == 3
    assert 3 not in plan["workers"]
    plan = elastic_scan_plan(4, [])
    assert plan["shards"] == 4 and plan["workers"] == [0, 1, 2, 3]
    with pytest.raises(RuntimeError):
        elastic_scan_plan(2, [0, 1])


def test_straggler_exclusion_to_resharded_parity(db_mb):
    """Detector flags a slow worker -> elastic plan -> rerun at the
    survivor count with identical decrypted output."""
    det = StragglerDetector(threshold=2.0, patience=1)
    for step in range(3):
        for w in range(4):
            det.report(w, 10.0 if w == 3 else 1.0, now=float(step))
    excluded = det.evaluate(now=3.0)
    assert excluded == [3]
    plan = Q.QUERIES["Q6"][0]()
    pl = Planner(db_mb, shards=4)
    before = run_via_plan(pl, plan)
    pl.shard_ctx = pl.shard_ctx.reshard(excluded)
    assert pl.shard_ctx.shards == 2            # largest pow2 of 3 survivors
    after = run_via_plan(pl, plan)
    _same(before, after)


# ---------------------------------------------------------------------------
# 9. Real multi-device collectives (CI: forced 8 host devices).
# ---------------------------------------------------------------------------

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices (XLA_FLAGS)")


@multidevice
def test_sharded_fold_psum_matches_numpy():
    rng = np.random.default_rng(0)
    data = rng.integers(0, 1 << 30, (4, 2, 3, 16), dtype=np.int64)
    out = sharded_fold(jax.numpy.asarray(data), live=3, mesh=make_scan_mesh(2))
    np.testing.assert_array_equal(np.asarray(out), data[:3].sum(axis=0))
    # pads excluded: live=4 differs
    out4 = sharded_fold(jax.numpy.asarray(data), live=4, mesh=make_scan_mesh(2))
    assert not np.array_equal(np.asarray(out4), data[:3].sum(axis=0))


@multidevice
def test_bfv_fold_on_real_mesh_parity(bfv_micro):
    bk = bfv_micro
    vecs = [np.arange(bk.slots) % 7 + i for i in range(3)]
    blocks = [bk.encrypt(v) for v in vecs]
    base = bk.decrypt(bk.fold_blocks(bk.stack_blocks(blocks)))
    ctx = make_shard_context(2)
    assert ctx.mesh is not None
    with activate(bk, ctx):
        batch = bk.stack_blocks([bk.encrypt(v) for v in vecs])
        assert batch.nphys == 4 and batch.nblocks == 3
        got = bk.decrypt(bk.fold_blocks(batch))
    np.testing.assert_array_equal(got, base)
    np.testing.assert_array_equal(got, np.sum(vecs, axis=0) % bk.t)


@multidevice
def test_mock_query_with_real_mesh(db_mb):
    """The full plan path with a real mesh attached (mock data is numpy,
    so only the context/ledger layer sees the mesh)."""
    base, base_stats, _ = _run_plan(db_mb, "Q1", True, None)
    shard, shard_stats, ledger = _run_plan(db_mb, "Q1", True, 2)
    _same(base, shard)
    assert _stats_dict(base_stats) == _stats_dict(shard_stats)


# ---------------------------------------------------------------------------
# 10. Kernel-bearing programs run once per lane (core/bfv.py _lane_map):
#     batch results equal the one-ciphertext program applied lane by lane.
# ---------------------------------------------------------------------------

def test_lane_map_equals_per_block_programs(bfv_micro):
    import jax
    bk = bfv_micro
    ctx = bk.ctx
    blocks = [bk.encrypt(np.arange(bk.slots) % 11 + i) for i in range(3)]
    batch = ctx.stack_cts(blocks)
    got = ctx.mul(batch, batch, bk.keys.rlk).data
    for i, b in enumerate(blocks):
        np.testing.assert_array_equal(
            np.asarray(got[i]), np.asarray(ctx.mul(b, b, bk.keys.rlk).data))
    rot = ctx.rotate_rows(batch, 1, bk.keys.gks).data
    np.testing.assert_array_equal(
        np.asarray(rot[2]), np.asarray(ctx.rotate_rows(blocks[2], 1, bk.keys.gks).data))
    leaves, tree = jax.tree.flatten(ctx.limb_q)            # tables are leaves
    back = jax.tree.unflatten(tree, leaves)
    assert (back.backend, back.interpret, back.k) == (
        ctx.limb_q.backend, ctx.limb_q.interpret, ctx.limb_q.k)


@multidevice
def test_lane_map_on_data_mesh_equals_one_device(bfv_micro):
    """On a data mesh the kernel programs run under shard_map, each
    device mapping the one-ciphertext program over its own lanes: same
    residues as the one-device lane loop, results still split by lane,
    and a lone ciphertext runs replicated.  The inner product too, with
    lone and batched terms mixed."""
    bk = bfv_micro
    blocks = [bk.encrypt(np.arange(bk.slots) % 11 + i) for i in range(4)]
    one = bk.ctx.stack_cts(blocks)
    want_mul = np.asarray(bk.ctx.mul(one, blocks[1], bk.keys.rlk).data)
    want_rot = np.asarray(bk.ctx.rotate_rows(one, 3, bk.keys.gks).data)
    want_dec = bk.decrypt(blocks[2])
    want_pair = np.asarray(bk.ctx.mul_plain(bk.ctx.stack_cts(blocks[:2]),
                                            np.arange(bk.slots) % 5).data)
    dot_cs = [3, 0, bk.t - 1]
    want_dot = np.asarray(bk.ctx.dot_scalars(
        [one.data, one.data[::-1], blocks[1].data], dot_cs))
    with activate(bk, make_shard_context(2)):
        batch = bk.stack_blocks(blocks)                 # two lanes per device
        got_mul = bk.mul(batch, blocks[1]).data
        got_rot = bk.rotate(batch, 3).data
        got_dec = bk.decrypt(blocks[2])
        pair = bk.stack_blocks(blocks[:2])              # one lane per device
        got_pair = bk.ctx.mul_plain(pair, np.arange(bk.slots) % 5).data
        got_dot = bk.ctx.dot_scalars(
            [batch.data, batch.data[::-1], blocks[1].data], dot_cs)
        assert bk.ctx.mesh is not None
    assert len(got_mul.sharding.device_set) == 2
    assert not got_mul.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(got_mul), want_mul)
    np.testing.assert_array_equal(np.asarray(got_rot), want_rot)
    np.testing.assert_array_equal(got_dec, want_dec)
    np.testing.assert_array_equal(np.asarray(got_pair), want_pair)
    np.testing.assert_array_equal(np.asarray(got_dot), want_dot)

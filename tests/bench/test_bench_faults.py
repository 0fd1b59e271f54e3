"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (`harness.run_cell`: keys, encrypted table, closed loop, the check)
at a size a test run holds, once sound and once per fault the cells can
have:

- state_unchanged  a rotation returns its input unchanged;
- half_batch       aggregation leaves out the second half of the rows
                   (the row swap of the slot sum returns zero);
- answer_altered   a decrypted answer is off by one where it is made.

The cell runs on one chip, so there is no exchange between chips to
leave out here; tests/bench/test_bench_mesh.py leaves it out of the same
cell's configuration sharded over four devices.  li32k.q1-cold runs on
real BFV (n = 128, t = 65537).  The harness's other path, one Q6
re-issued on a shared planner whose mask cache the set-up query fills
(tests/bench/q6-dashboard.json, no cell), runs on the engine's mock
backend, which executes the same DAG on plaintext with the same
128-slot layout: a cold BFV Q6 takes minutes on a CPU.
"""
import copy
import os
import time

import numpy as np
import pytest

from bench import harness, querygen
from bench.run import load_cell

SEED = 2**33 + 17
FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def tiny(cell_name, **he):
    _, cell, cfg, mix = load_cell(cell_name)
    cfg = copy.deepcopy(cfg)
    cfg["he"].update(he)
    cfg.update(rows=128, parts=16, suppliers=8)
    return cell, cfg, dict(mix)


def correct(cell, cfg, mix) -> bool:
    _, _, checks, failed = harness.run_cell(cell, cfg, mix, SEED, 0.0, False,
                                            time.perf_counter())
    return failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())


def inject(monkeypatch, cls, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(cls, "rotate", lambda self, a, step: a)
    elif fault == "half_batch":
        monkeypatch.setattr(cls, "swap_rows", lambda self, a: self.sub(a, a))
    else:
        orig = cls.decrypt

        def altered(self, ct):
            out = np.array(orig(self, ct))
            out[..., 0] = (out[..., 0] + 1) % self.t
            return out
        monkeypatch.setattr(cls, "decrypt", altered)


@pytest.fixture(scope="module")
def bfv_q1():
    """li32k.q1-cold at n = 128 on one shared backend (keys reused)."""
    from repro.core.params import make_params
    from repro.engine.backend import BFVBackend
    cell, cfg, mix = tiny("li32k.q1-cold", n=128, k=22)
    mix["warmup_queries"] = 0
    he = cfg["he"]
    return cell, cfg, mix, BFVBackend(
        make_params(n=he["n"], t=he["t"], k=he["k"], qbits=he["qbits"]), seed=5)


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_q1_cold_bfv(bfv_q1, monkeypatch, fault):
    from repro.engine.backend import BFVBackend
    cell, cfg, mix, bk = bfv_q1
    monkeypatch.setattr(harness, "make_backend", lambda cfg, seed, phases: bk)
    if fault is not None:
        inject(monkeypatch, BFVBackend, fault)
    assert correct(cell, cfg, mix) is (fault is None)


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_q6_warm_mock(monkeypatch, fault):
    from repro.core.noise import NoiseProfile
    from repro.engine.backend import MockBackend
    cell, cfg, _ = tiny("li32k.q1-cold")
    cell = dict(cell, name="q6-dashboard", traffic="q6-dashboard")
    mix = querygen.load_mix(os.path.join(os.path.dirname(__file__),
                                         "q6-dashboard.json"))
    he = cfg["he"]
    profile = NoiseProfile(n=128, t=he["t"], k=he["k"], qbits=he["qbits"])
    monkeypatch.setattr(harness, "make_backend",
                        lambda cfg, seed, phases: MockBackend(profile))
    monkeypatch.setattr(harness, "stored_bytes", lambda db, table: 1)
    if fault is not None:
        inject(monkeypatch, MockBackend, fault)
    assert correct(cell, cfg, mix) is (fault is None)


def test_q1_cold_warmup_plan_leaves_nothing_to_compile(monkeypatch):
    """The cell's warm-up (Q1's predicate and grouping, one aggregate of
    each kind) runs every program and shape of the window's whole Q1:
    on a fresh backend, nothing compiles inside the window."""
    from repro.core.params import make_params
    from repro.engine.backend import BFVBackend
    cell, cfg, mix = tiny("li32k.q1-cold", n=128, k=22)
    assert mix["warmup_queries"] == 1 and mix["warmup_plan"] == "one_agg_per_kind"
    he = cfg["he"]
    bk = BFVBackend(make_params(n=he["n"], t=he["t"], k=he["k"], qbits=he["qbits"]),
                    seed=6)
    monkeypatch.setattr(harness, "make_backend", lambda cfg, seed, phases: bk)
    rec, _, checks, failed = harness.run_cell(cell, cfg, mix, SEED + 1, 0.0,
                                              False, time.perf_counter())
    assert failed == 0 and rec.queries == 1 and rec.ops["rotate"] == 66 * 7
    assert rec.window_compiles == []


def test_q1_cold_traced_run_feeds_the_readers(bfv_q1, monkeypatch):
    """The traced path end to end on the CPU's trace: every reader finds
    its number or returns nothing (the kernel ones: no Pallas on CPU)."""
    import json
    import os
    cell, cfg, mix, bk = bfv_q1
    monkeypatch.setattr(harness, "make_backend", lambda cfg, seed, phases: bk)
    rec, e2e, checks, failed = harness.run_cell(
        cell, cfg, mix, SEED, 0.0, True, time.perf_counter(),
        device_prefix="/host:CPU")
    assert failed == 0 and rec.queries == 1
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    got = {n: harness.read_metric(os.path.join(root, "bench", "metrics"), n, rec)
           for n in names}
    assert got["ct_muls_per_query"] == 543
    assert got["rotations_per_query"] == 66 * 7     # log2(64) hops + row swap
    assert got["admit_ms"] > 0 and 0 <= got["device_idle_share"] < 100
    assert got["kernel_busy_share"] is None and got["ntt_roofline"] is None
    assert got["dispatches_per_query"] == rec.ops["dispatches"] > 0
    assert got["keyswitch_ms"] is None          # a CPU trace names no scope
    assert rec.trace.span_s["nshedb.query"] > 0
    assert set(e2e) == {"query_s", "query_p95_s", "stored_bytes_per_row", "setup_s"}
    assert e2e["stored_bytes_per_row"] == 16 * 2 * 22 * 8   # 16 int64 columns

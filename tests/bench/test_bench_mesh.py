"""The harness on a data mesh: li32k.q1-cold's configuration with
`planner.shards = 4`, run by `harness.run_cell` over four blocks on four
devices, once sound and once with the exchange between chips left out.

The tier-1 suite sees one host device, so both runs go to one
subprocess with four forced CPU devices (real BFV at n = 128, k = 22;
keys shared by the two runs).  The fault replaces the block fold's psum
(`engine/sharded.py`) with each device's own lane sum: the answer then
holds one chip's rows and leaves out the other three chips' partial
sums.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCRIPT = r'''
import copy, functools, json, time
import jax, jax.numpy as jnp
from bench import harness
from bench.run import load_cell
from repro.core.params import make_params
from repro.engine import sharded
from repro.engine.backend import BFVBackend

_, cell, cfg, mix = load_cell("li32k.q1-cold")
cfg = copy.deepcopy(cfg)
cfg["he"].update(n=128, k=22)
cfg.update(rows=512, parts=16, suppliers=8, chips=4)
cfg["planner"]["shards"] = 4
cell = dict(cell, chips=4)
mix = dict(mix, warmup_queries=0)
he = cfg["he"]
bk = BFVBackend(make_params(n=he["n"], t=he["t"], k=he["k"], qbits=he["qbits"]),
                seed=5)
harness.make_backend = lambda cfg, seed, phases: bk
planners = []
build = harness.Client._planner
harness.Client._planner = lambda self: planners.append(build(self)) or planners[-1]


def run():
    planners.clear()
    rec, _, checks, failed = harness.run_cell(cell, cfg, mix, 2**33 + 29, 0.0,
                                              False, time.perf_counter())
    mesh = planners[-1].shard_ctx.mesh
    return {"correct": failed == 0 and all(c["value"] <= c["limit"]
                                           for c in checks.values()),
            "mesh_devices": 0 if mesh is None else int(mesh.devices.size),
            "dispatches": rec.ops["dispatches"], "queries": rec.queries,
            "checks": checks}


@functools.partial(jax.jit, static_argnames=("mesh",))
def lane_sum_only(data, weights, *, mesh):
    P = jax.sharding.PartitionSpec
    body = lambda d, w: jnp.sum(d * w[:, None, None, None], axis=0)
    return jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=P(), check_vma=False)(data, weights)


out = {"sound": run()}
sharded._fold_psum = lane_sum_only
out["fold_dropped"] = run()
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def mesh_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_q1_cold_is_correct_on_four_devices(mesh_runs):
    run = mesh_runs["sound"]
    assert run["mesh_devices"] == 4
    assert run["queries"] == 1 and run["dispatches"] > 0
    assert run["correct"], run["checks"]


def test_fold_without_the_exchange_is_not_correct(mesh_runs):
    run = mesh_runs["fold_dropped"]
    assert run["mesh_devices"] == 4
    assert not run["correct"]
    assert run["checks"]["wrong_values"]["value"] > 0

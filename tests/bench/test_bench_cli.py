"""bench/run.py refuses to run without a TPU, or with a configuration
laid out for another number of chips than its cell's, and prints no
result."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cwd, env_extra=None, workload="li32k.q1-cold"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_refuses_without_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "refusing" in r.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    has no program to run."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert _no_result(r.stdout)


def test_every_cell_names_existing_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    files = {c["name"]: c["file"] for c in b["configs"]}
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            cfg = json.load(f)
        assert cfg.get("chips", 1) == w["chips"], w["name"]
        assert cfg["planner"].get("shards", 1) == w["chips"], w["name"]
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("chips,stated,refused", [
    (4, {"chips": 4}, "planner.shards = 1"),          # a 4-chip cell, unsharded
    (4, {}, "chips = 1"),                             # li32k's file as it is
    (4, {"planner.shards": 2, "chips": 4}, "planner.shards = 2"),
    (4, {"planner.shards": 4, "chips": 1}, "chips = 1"),
    (1, {"planner.shards": 4, "chips": 4}, "chips = 4"),
    (4, {"planner.shards": 4, "chips": 4}, None),     # agrees: on to the chip check
])
def test_refuses_a_layout_for_other_chips_before_setup(tmp_path, chips, stated,
                                                       refused):
    """A cell whose configuration states other chips or shards than the
    cell asks for exits 2 with one line on stderr, before set-up."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, cell = bench["configs"][0], bench["workloads"][0]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["chips"] = stated.get("chips", cfg["chips"])
    if "planner.shards" in stated:
        cfg["planner"]["shards"] = stated["planner.shards"]
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "configs" / "layout.json").write_text(json.dumps(cfg))
    bench["configs"].append(dict(entry, name="layout",
                                 file="bench/configs/layout.json"))
    bench["workloads"].append(dict(cell, name="layout.cell", config="layout",
                                   chips=chips))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = _run(str(tmp_path), {"PYTHONPATH": ""}, workload="layout.cell")
    assert r.returncode == 2
    assert _no_result(r.stdout)
    lines = r.stderr.strip().splitlines()
    if refused is None:
        assert f"needs {chips} TPU chip(s)" in r.stderr
    else:
        assert len(lines) == 1 and refused in lines[0] and "refusing" in lines[0]

"""bench/run.py refuses to run without a TPU and prints no result."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "li32k.q1-cold",
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
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))

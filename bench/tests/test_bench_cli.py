"""The entry point's refusals, and imports that leave the TPU alone."""

import json
import os
import shutil
import subprocess
import sys

from bench.tests.helpers import ROOT


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("PYTHONPATH", None)
    env.update(extra)
    return env


def test_refuses_without_a_tpu_and_prints_no_result():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "train.stablelm2.r1",
                        "--seed", "3000000123", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 tpu" in p.stderr


def test_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "train.stablelm2.r1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_importing_the_benchmark_touches_no_backend():
    code = (
        "import sys; sys.path[:0] = ['src', '.']\n"
        "import bench.run, bench.drivers.train, bench.drivers.serve, bench.tools.control\n"
        "import bench.tools.rehearse, bench.tools.sweep, bench.core.trace\n"
        "from jax._src import xla_bridge as xb\n"
        "print(len(xb._backends))\n"
    )
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip().splitlines()[-1] == "0"


def test_every_cell_metric_and_file_is_found_by_name():
    sys.path[:0] = [str(ROOT)]
    from bench.core import harness as H

    bm = H.benchmark()
    for w in bm["workloads"]:
        cell = H.load("cells", w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert (ROOT / "bench" / "drivers" / f"{cell['driver']}.py").exists()
        assert H.load("traffic", w["traffic"])
    for c in bm["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()

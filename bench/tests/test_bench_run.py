"""``bench/run.py`` refuses to run where there is no chip, and prints no
result line then."""
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny


def _run(cwd, workload="infmnist_k50.fit"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("workload", ["infmnist_k50.fit",
                                      "infmnist_k50.predict"])
def test_no_tpu_no_result(workload):
    p = _run(bench_tiny.ROOT, workload)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(bench_tiny.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""Off a TPU the entry exits non-zero and prints no result, and so it
does in a directory that holds only BENCHMARK.json and the benchmark."""

import os
import shutil
import subprocess
import sys

from .conftest import ROOT


def run_entry(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "council64.catchup", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_exits_nonzero_without_a_tpu():
    p = run_entry(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    p = run_entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

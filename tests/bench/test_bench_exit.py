"""The benchmark refuses to measure what it cannot: on a CPU, and without
the program beside it, it exits non-zero and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench_tiny import ROOT


def _run(cwd, *extra_env):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(str(cwd), ".cache")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "exp2-fleet.periodic",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in ("bench", "tests/bench"):
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""``bench/run.py`` refuses to measure anything but a chip: on a machine
where JAX finds only the CPU it exits non-zero and prints no result,
in the repository and in a directory that holds only the benchmark."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron15.count",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_only_machine_gets_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


@pytest.mark.parametrize("what", ["benchmark_only"])
def test_benchmark_alone_gets_no_result(tmp_path, what):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copytree(ROOT / "tests" / "bench", tmp_path / "tests" / "bench")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""

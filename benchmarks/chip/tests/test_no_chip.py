"""Without a TPU the benchmark fails and prints no result; and it needs
the program, not only its own files."""

import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
ARGS = ["--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(root, workload="mixtral.serve.steady"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "run.py"), "--workload",
         workload, *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(proc):
    last = proc.stdout.strip().splitlines()[-1:] if proc.stdout.strip() else []
    return not any(line.startswith("{") for line in last)


@pytest.mark.parametrize("workload", ["mixtral.serve.steady", "mixtral.train.ep4.skewed"])
def test_refuses_without_a_tpu(workload):
    proc = _run(ROOT, workload)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "benchmarks", "chip"), tmp_path / "benchmarks" / "chip",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert _no_result(proc)

"""The real command refuses to run without a TPU, and without the program
beside it, and prints no result."""
import os
import shutil
import subprocess
import sys

from chipbench import harness

ARGS = ["--workload", "train.lm100m", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py")] + ARGS,
        cwd=root, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu():
    p = _run(harness.ROOT)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""

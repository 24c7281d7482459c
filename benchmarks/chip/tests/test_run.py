"""run.py refuses a machine with no TPU, and a checkout with no program."""
import os
import shutil
import subprocess
import sys

from chip import harness as H

ARGS = ["--workload", "base-fleet", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu():
    p = _run(H.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_needs_the_program(tmp_path):
    shutil.copy(H.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(H.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""

"""The command refuses to run without a TPU or without the system."""

import os
import shutil
import subprocess
import sys

from chipbench import spec

ARGS = ["--workload", spec.load_benchmark()["workloads"][0]["name"],
        "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_exits_without_a_tpu():
    proc = _run(spec.ROOT)
    _no_result(proc)
    assert "TPU" in proc.stderr


def test_exits_without_the_system(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    _no_result(proc)

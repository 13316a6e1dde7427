"""The command refuses to measure where it cannot: without a TPU, and in
a directory that holds the benchmark but not the program."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import run

ARGS = ["--workload", "commodity.dense", "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_without_a_tpu_it_exits_before_measuring():
    proc = _run(run.ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert _no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_alone_cannot_run(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(run.ROOT, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)

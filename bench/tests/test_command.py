"""The command prints no result and exits non-zero without a chip, and in a
directory that holds only the benchmark's own files."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
CELL = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def _run(root: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "2147483659",
           "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

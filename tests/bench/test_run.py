"""The command refuses to run where there is no chip: on the CPU it exits
with a code other than 0 and prints no result line; so it does in a copy
that holds only BENCHMARK.json and the benchmark's own directories."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ring-train-deepseek7b-cais",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj, line


def test_no_chip_no_result():
    proc = _run(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no accelerator" in proc.stderr
    _no_result(proc)


def test_benchmark_files_alone_do_not_run(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)

"""Dry-run smoke subset (deliverable e): a fast sample of cells must lower +
compile on the production meshes. The full 80-cell sweep runs via
``python -m repro.launch.dryrun`` (see EXPERIMENTS.md §Dry-run); this test
keeps the machinery honest in CI without the 45-minute sweep."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).parent.parent

CELLS = [
    ("whisper-tiny", "decode_32k", "single"),
    ("whisper-tiny", "train_4k", "multi"),
    ("gemma3-1b", "long_500k", "single"),
    ("mamba2-130m", "decode_32k", "multi"),
]


@pytest.mark.parametrize("arch,shape,mesh", CELLS)
def test_dryrun_cell(arch, shape, mesh, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)   # dryrun.py sets its own 512-device flag
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=str(REPO))
    sys.stdout.write(proc.stdout[-2000:])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    rec = json.load(open(next(tmp_path.glob("*.json"))))
    assert rec["status"] == "ok"
    assert rec["hlo_analysis"]["flops"] > 0
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
    # memory must fit a v5e chip (16 GB HBM)
    total = rec["memory"]["total_hbm_bytes"]
    assert total < 16 * 1024**3, f"does not fit HBM: {total/2**30:.1f} GiB"

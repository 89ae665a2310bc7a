"""Runs the 8-virtual-device correctness suite in a subprocess so the main
pytest process keeps exactly one device (the dry-run owns device-count
overrides; see the assignment's XLA_FLAGS note)."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

HERE = pathlib.Path(__file__).parent
REPO = HERE.parent


def test_main_process_single_device():
    assert len(jax.devices()) == 1


@pytest.mark.slow
@pytest.mark.parametrize("part", ["rings", "period", "split", "model"])
def test_multidevice_suite(part):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, str(HERE / "multidev_checks.py"), part],
        capture_output=True, text=True, env=env, timeout=1200)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "multi-device checks failed"
    assert "ALL OK" in proc.stdout

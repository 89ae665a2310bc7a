"""Leaving out the exchange between chips makes the ring cell's
``correct`` come out false: the cell at smoke size on four virtual CPU
devices, once as it is and once with every ring permute returning what it
was sent (``ring_fault.py``, in a process of its own)."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent


def _ring(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(HERE / "ring_fault.py"),
                           *flags], capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_ring_without_exchange_is_not_correct():
    sound = _ring()
    assert sound["correct"], sound["checks"]
    broken = _ring("--no-exchange")
    assert not broken["correct"], broken["checks"]

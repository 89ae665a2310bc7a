"""Runs the ring cell at smoke size on four virtual CPU devices, with the
exchange between devices left out when ``--no-exchange`` is given (every
ring permute returns what it was sent), and prints the result line.
``test_ring_fault.py`` starts it in a process of its own, since the device
count is fixed when JAX starts."""
from __future__ import annotations

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from smoke_cells import run_smoke  # noqa: E402


def main() -> None:
    import jax

    assert len(jax.devices()) == 4, jax.devices()
    if "--no-exchange" in sys.argv:
        jax.lax.ppermute = lambda x, axis_name, perm: x
    line = run_smoke("ring-train-deepseek7b-cais", seconds=0.5)
    print(json.dumps(line))


if __name__ == "__main__":
    main()

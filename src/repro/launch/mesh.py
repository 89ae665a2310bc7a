"""Mesh construction.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (smoke tests and benches must see 1 device; only
the dry-run forces 512 virtual hosts)."""
from __future__ import annotations

import jax

from repro.sharding import DATA_AXIS, MODEL_AXIS, POD_AXIS, make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data × model). Multi-pod: 2 pods =
    512 chips with cross-pod DP on the `pod` axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod \
        else (DATA_AXIS, MODEL_AXIS)
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4):
    """Small mesh for the 8-virtual-device test suite."""
    return make_mesh((data, model), (DATA_AXIS, MODEL_AXIS))


def make_host_mesh():
    """Tensor parallelism over every device this process sees:
    ``data=1 × model=len(jax.devices())`` (one chip: 1×1; a four-chip
    v5e host: the TP ring over all four)."""
    return make_mesh((1, len(jax.devices())), (DATA_AXIS, MODEL_AXIS))


MESHES = {"none": lambda: None, "debug": make_debug_mesh,
          "host": make_host_mesh}

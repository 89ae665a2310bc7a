"""Mesh / sharding conventions for the whole framework.

Axis naming (DESIGN.md §6):
  * ``pod``   — cross-pod data parallelism (only on the multi-pod mesh)
  * ``data``  — in-pod data parallelism (+ context parallelism for batch-1)
  * ``model`` — tensor / sequence / expert parallelism (high-bandwidth ICI)

Model code never touches a mesh directly: it calls :func:`shard` with a
logical :class:`jax.sharding.PartitionSpec`. When no mesh is active (CPU smoke
tests, single device) the call is a no-op, so the same model runs everywhere.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"

# Hierarchical (2D) tensor parallelism: the model axis factors into a fast
# intra-node ring × a slow inter-node axis (docs/topology.md). A shard index
# along the composite axis is ``i_in * tp_out + i_out`` — ``tp_in`` major,
# matching jax's tuple-PartitionSpec semantics for ``("tp_in", "tp_out")``.
TP_IN_AXIS = "tp_in"
TP_OUT_AXIS = "tp_out"
TP_AXES_2D = (TP_IN_AXIS, TP_OUT_AXIS)


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    activations with sharding constraints and lets the compiler propagate
    the rest, which ``jax.make_mesh``'s default explicit axes would
    refuse."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_tp_mesh(tp_in: int, tp_out: int, dp: int = 1) -> Mesh:
    """A hierarchical-TP mesh: ``data × tp_in × tp_out`` with the model axis
    factored into the fast intra-node ring (``tp_in``) × the slow inter-node
    axis (``tp_out``). ``tp_out == 1`` still builds the 2D mesh (useful for
    degenerate-factorization parity tests); callers wanting the flat ring use
    ``make_mesh((dp, tp), ("data", "model"))`` as before."""
    return make_mesh((dp, tp_in, tp_out), (DATA_AXIS,) + TP_AXES_2D)


def tp_axes(mesh: Optional[Mesh]):
    """The TP axis entry for PartitionSpecs / collective calls on ``mesh``:
    the flat ``"model"`` string on 1D meshes, the composite
    ``("tp_in", "tp_out")`` tuple on hierarchical meshes (tp_in major)."""
    if mesh is not None and TP_IN_AXIS in mesh.axis_names \
            and TP_OUT_AXIS in mesh.axis_names:
        return TP_AXES_2D
    return MODEL_AXIS


def shard_map_axis_index(axis):
    """Flattened device index along ``axis`` from inside shard_map. For a
    composite tuple the first member is major (index = i0·n1·… + i1·… + …),
    consistent with jax's tuple-PartitionSpec shard order."""
    if isinstance(axis, (tuple, list)):
        idx = jax.lax.axis_index(axis[0])
        for a in axis[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axis)


# All repo code calls shard_map through this name.
shard_map = jax.shard_map

# Batch dims shard over every data-parallel axis present on the mesh.
BATCH_AXES = (POD_AXIS, DATA_AXIS)

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def _filter_spec(mesh: Mesh, spec: P) -> P:
    """Drop axis names that the active mesh does not have (e.g. ``pod`` on the
    single-pod mesh) so one logical spec serves every mesh."""
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(keep(e) for e in spec))


def shard(x, *spec_entries):
    """``with_sharding_constraint`` against the active mesh; no-op without one.

    ``shard(x, ("pod","data"), None, "model")`` pins batch to the DP axes and
    the last dim to the TP axis.
    """
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    spec = _filter_spec(mesh, P(*spec_entries))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *spec_entries) -> NamedSharding:
    return NamedSharding(mesh, _filter_spec(mesh, P(*spec_entries)))


def batch_spec(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    """(batch, seq, ...) sharding: batch over DP axes, rest replicated."""
    return named_sharding(mesh, BATCH_AXES, *([None] * extra_dims))


def axis_size(mesh: Optional[Mesh], name: str) -> int:
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def dp_size(mesh: Optional[Mesh]) -> int:
    return axis_size(mesh, POD_AXIS) * axis_size(mesh, DATA_AXIS)


def tp_size(mesh: Optional[Mesh]) -> int:
    """Total TP degree — the flat model axis, or the product of the 2D
    factors on a hierarchical mesh (the two are mutually exclusive)."""
    return axis_size(mesh, MODEL_AXIS) * \
        axis_size(mesh, TP_IN_AXIS) * axis_size(mesh, TP_OUT_AXIS)

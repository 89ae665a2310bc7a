"""Weights made from ``--seed``, in the layout of the program's parameters.

The layout (every leaf's path and shape) is read from the program's
``model.init`` by ``jax.eval_shape``, which runs nothing. The values are
this file's: each leaf is drawn from a key folded from the seed and the
leaf's path, so a leaf's values depend on the seed and its path alone.
The reference regenerates them with the same function and never reads the
program's arrays.

- norm scales: 0.1 · N(0, 1); the program's RMSNorm multiplies by
  ``1 + scale``, so the weights sit near 1 as trained ones do;
- the token embedding: N(0, 1), so the residual stream starts at unit
  scale;
- every other matrix: truncated N(0, 1) on [-2, 2] over sqrt(fan-in),
  where fan-in is the second-last axis (the last is the output)."""
from __future__ import annotations

import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative integer below 2**64: the low 32
    bits make the key, the high 32 bits are folded in."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf(key, name: str, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if "norm" in name:
        x = 0.1 * jax.random.normal(k, shape, jnp.float32)
    elif name == "embed":
        x = jax.random.normal(k, shape, jnp.float32)
    else:
        fan_in = shape[-2]
        x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) \
            / math.sqrt(fan_in)
    return x.astype(dtype)


def make_params(layout: Any, key):
    """Parameters with the paths, shapes and dtypes of ``layout`` (a tree
    of ``ShapeDtypeStruct``), drawn from ``key``. Jit it, with the shardings
    the program wants, to make the weights on the device in one call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)
    return jax.tree_util.tree_unflatten(
        treedef, [_leaf(key, path_name(p), l.shape, l.dtype)
                  for p, l in leaves])

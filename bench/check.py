"""The comparison that decides ``correct``.

Training: the timed path's first steps against the reference's, by
- ``loss``: the largest gap between the two losses of one step, over the
  reference's loss;
- ``grad``: the first gradient as the optimizer gets it (clipped), leaf by
  leaf: the gap between the two norms over the larger of the reference's
  norm of that leaf and of the median leaf;
- ``update``: the same for the parameters' change over the checked steps,
  over the leaves whose reference gradient is at least a thousandth of the
  median leaf's (a leaf with no gradient moves by weight decay and
  round-off alone).
A layer-stacked leaf counts as one leaf per layer.

Serving: ``gap``, the widest amount by which a served token's reference
logit lies below the reference's best logit at that position.

Each number is held to its limit in ``limits/<cell>.json``."""
from __future__ import annotations

import math
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import path_name

LAYER_STACKS = ("stack/periods/",)


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Norm of every leaf; layer-stacked leaves give one norm per layer.
    Jit-able."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = path_name(path)
        x = leaf.astype(jnp.float32)
        if name.startswith(LAYER_STACKS) and x.ndim >= 2:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def to_host(norms: Dict[str, Any]) -> Dict[str, float]:
    flat = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            flat[name] = float(v)
        else:
            for i, x in enumerate(v):
                flat[f"{name}[{i}]"] = float(x)
    return flat


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                names: List[str]) -> float:
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
               for n in names)


def train_numbers(prog: Dict[str, Any],
                  ref: Dict[str, Any]) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``loss`` (one per checked step),
    ``grad`` and ``update`` (leaf name -> norm)."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference leaves differ")
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]) or not math.isfinite(loss):
        loss = math.inf
    names = sorted(ref["grad"])
    gmed = float(np.median([ref["grad"][n] for n in names]))
    moved = [n for n in names if ref["grad"][n] >= 1e-3 * gmed]
    return {"loss": loss,
            "grad": _worst_leaf(prog["grad"], ref["grad"], names),
            "update": _worst_leaf(prog["update"], ref["update"], moved)}


def verdict(numbers: Dict[str, float], limits: Dict[str, Any]):
    """``(correct, checks)``: every number beside its limit; a number that
    is not finite fails."""
    checks = {}
    ok = True
    for name, spec in limits["limits"].items():
        value = float(numbers[name])
        passed = math.isfinite(value) and value <= spec["limit"]
        ok = ok and passed
        checks[name] = {"value": value, "limit": spec["limit"]}
    return ok, checks


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)

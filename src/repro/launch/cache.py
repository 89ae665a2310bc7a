"""Where JAX keeps its persistent compilation cache.

A compiled program is looked up by a key that includes the cache's
directory, so a directory that moves never hits. The launchers and
``chip_smoke.py`` therefore call :func:`init_compile_cache` before their
first compile, and it picks one fixed place: ``$JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, so nothing
else is set in code), otherwise ``.jax_cache`` at the root of this
checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call it before the process compiles anything: JAX decides on its first
    compile whether a cache is in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

"""Jit'd public wrappers for the Pallas kernels.

The kernels lower natively for the TPU. ``interpret=True`` runs the kernel
body in the Pallas interpreter instead, which is how the tests check them
against ``ref.py`` on the CPU; nothing turns it on implicitly. No model path
calls these kernels today: the models use their pure-jnp paths.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import matmul_ln as _ml


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def matmul(a, b, *, bm: int = 256, bn: int = 256, bk: int = 512,
           out_dtype=None, interpret: bool = False):
    return _mm.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret,
                      out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "eps", "out_dtype",
                                             "interpret"))
def matmul_rmsnorm(a, b, scale, *, bm: int = 128, bk: int = 512,
                   eps: float = 1e-6, out_dtype=None,
                   interpret: bool = False):
    return _ml.matmul_rmsnorm(a, b, scale, bm=bm, bk=bk, eps=eps,
                              interpret=interpret, out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv", "scale",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bkv: int = 256, scale=None, interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv,
                               scale=scale, interpret=interpret)

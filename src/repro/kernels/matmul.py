"""Blocked MXU matmul Pallas kernel (TPU target; validated interpret=True).

Tiling: grid (M/bm, N/bn, K/bk) with (bm, bk)·(bk, bn) tiles staged in VMEM
and a float32 VMEM accumulator — MXU-aligned block shapes (multiples of the
128×128 systolic tile; bf16 inputs accumulate in f32 as the MXU does).
This is the partial-GEMM building block the CAIS ring schedules consume
(one call per arriving activation chunk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    """One (i, j, k) grid step: acc += a_tile @ b_tile; flush at last k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# Mosaic's tiling rule: the last two dims of a block are multiples of
# (8, 128) unless they span the whole array dim.
SUBLANE, LANE = 8, 128


def block_divisor(dim: int, want: int, align: int, *, shape=None) -> int:
    """Block length along one array dim of extent ``dim``: the whole dim
    when it fits in ``max(want, align)``, else the largest multiple of
    ``align`` that divides ``dim`` and is at most ``max(want, align)``.
    Raises ``ValueError`` naming ``shape`` when no such block exists."""
    cap = max(want, align)
    if dim <= cap:
        return dim
    for b in range(cap - cap % align, 0, -align):
        if dim % b == 0:
            return b
    raise ValueError(
        f"no block of at most {cap} that is a multiple of {align} divides "
        f"dim {dim} of shape {tuple(shape) if shape else (dim,)}; pad the "
        f"array to a multiple of {align}")


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256, bn: int = 256,
           bk: int = 512, interpret: bool = False, out_dtype=None):
    """a: (M, K) @ b: (K, N) -> (M, N). Block sizes are clipped to
    (8, 128)-aligned divisors of the problem shape (``block_divisor``);
    defaults keep the VMEM working set
    (bm·bk + bk·bn tiles bf16 + bm·bn f32 accumulator ≈ 0.5 MB) well under
    the ~128 MB/core budget while filling the MXU (≥128 in every dim)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm = block_divisor(M, bm, SUBLANE, shape=a.shape)
    bk = block_divisor(K, bk, LANE, shape=a.shape)
    bn = block_divisor(N, bn, LANE, shape=b.shape)
    n_k = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)

"""Fused GEMM + RMSNorm-epilogue Pallas kernel (TPU target).

The paper's L1–L4 sub-layers chain GEMM → LN → GEMM; in the CAIS pipeline the
LN runs sequence-parallel on the reduce-scattered shard. This kernel fuses
the normalization into the producing GEMM's epilogue so the normalized
activation never round-trips to HBM.

Tiling: grid (M/bm, K/bk) with the FULL N dimension resident per block
(norm needs the whole feature row; bm·N f32 ≈ 128·8192·4 = 4 MB — fits
VMEM for every assigned arch's d_model/d_ff).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import LANE, SUBLANE, block_divisor


def _matmul_ln_kernel(a_ref, b_ref, scale_ref, o_ref, acc_ref, *,
                      n_k: int, eps: float):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        z = acc_ref[...]                                    # (bm, N) f32
        var = jnp.mean(z * z, axis=-1, keepdims=True)
        zn = z * jax.lax.rsqrt(var + eps)
        zn = zn * (1.0 + scale_ref[...].astype(jnp.float32))
        o_ref[...] = zn.astype(o_ref.dtype)


def matmul_rmsnorm(a: jnp.ndarray, b: jnp.ndarray, scale: jnp.ndarray, *,
                   bm: int = 128, bk: int = 512, eps: float = 1e-6,
                   interpret: bool = False, out_dtype=None):
    """rmsnorm(a @ b) * (1 + scale). a: (M, K); b: (K, N); scale: (N,)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and scale.shape == (N,)
    out_dtype = out_dtype or a.dtype
    bm = block_divisor(M, bm, SUBLANE, shape=a.shape)
    bk = block_divisor(K, bk, LANE, shape=a.shape)
    n_k = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_ln_kernel, n_k=n_k, eps=eps),
        grid=(M // bm, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
            pl.BlockSpec((N,), lambda i, k: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
        interpret=interpret,
    )(a, b, scale)

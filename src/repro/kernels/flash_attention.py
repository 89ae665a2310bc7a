"""Blocked causal flash attention Pallas kernel (TPU target).

Online-softmax over KV blocks with running (m, l, o) state in VMEM — the
compute hot-spot of the 32k prefill shapes. Grid: (batch·heads, Sq/bq); the
kv loop is the innermost grid dimension so K/V tiles stream HBM→VMEM while
the (bq, d) accumulator stays resident. Causal masking skips fully-masked
KV blocks via the block index comparison (the mask never materializes at
(S, S)).

Supports GQA by folding the query-group into the batch·heads grid axis
(callers pass q heads with their kv head's K/V).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import SUBLANE, block_divisor

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, bq: int, bkv: int, scale: float, causal: bool):
    qi = pl.program_id(1)   # query block index
    ki = pl.program_id(2)   # kv block index

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[0]                                  # (bq, d)
        k = k_ref[0]                                  # (bkv, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1)[:, None]          # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bkv)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # skip kv blocks strictly above the diagonal
        @pl.when(ki * bkv <= qi * bq + bq - 1)
        def _():
            body()
    else:
        body()

    @pl.when(ki == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 256, bkv: int = 256,
                    scale=None, interpret: bool = False):
    """q, k, v: (BH, S, d) — batch and heads pre-folded. Returns (BH, S, d).

    VMEM working set per step: q(bq·d) + k,v(bkv·d) + acc(bq·d f32)
    ≈ 0.7 MB at defaults with d=128."""
    BH, Sq, d = q.shape
    _, Skv, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = block_divisor(Sq, bq, SUBLANE, shape=q.shape)
    bkv = block_divisor(Skv, bkv, SUBLANE, shape=k.shape)
    n_kv = Skv // bkv

    return pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=n_kv, bq=bq, bkv=bkv,
                          scale=scale, causal=causal),
        grid=(BH, Sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)

"""Jit'd public wrappers for the Pallas kernels.

``causal_flash_attention`` wraps JAX's bundled splash-attention TPU kernel
with a causal mask (a forward kernel and one fused dq/dk/dv backward
kernel, each skipping fully masked blocks; the backward keeps the per-row
log-sum-exp, never the probabilities). The training period's attention
core calls it on the TPU (``core/tp.py::_attention_core_fn``). The repo's
own ``matmul``, ``matmul_rmsnorm`` and forward-only ``flash_attention``
kernels are on no model path.

The kernels lower natively for the TPU. ``interpret=True`` runs the kernel
body in the Pallas interpreter instead, which is how the tests check them
against ``ref.py`` and the XLA attention core on the CPU; nothing turns it
on implicitly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import \
    splash_attention_kernel as _splash
from jax.experimental.pallas.ops.tpu.splash_attention import \
    splash_attention_mask as _splash_mask

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import matmul_ln as _ml
from repro.kernels.matmul import LANE, block_divisor

# Longest sequence block of the causal flash kernel (queries and keys), and
# the key block each score tile is computed over inside it.
FLASH_BLOCK = 1024
FLASH_COMPUTE_BLOCK = 512


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


def flash_block(seq_len: int) -> Optional[int]:
    """The causal flash kernel's sequence block for ``seq_len``: a multiple
    of the 128-lane tile that divides it, at most ``FLASH_BLOCK``
    (``block_divisor``); None where ``seq_len`` is not a multiple of 128."""
    if seq_len % LANE:
        return None
    return block_divisor(seq_len, FLASH_BLOCK, LANE)


@functools.lru_cache(maxsize=None)
def _causal_splash(seq_len: int, heads: int, interpret: bool):
    """The splash kernel for ``heads`` causal heads of ``seq_len``, its
    blocks from ``flash_block``. Its block-sparse mask tables are built
    once, as concrete arrays even when first asked for inside a trace."""
    b = flash_block(seq_len)
    c = block_divisor(b, FLASH_COMPUTE_BLOCK, LANE)
    blocks = _splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=c, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=c, use_fused_bwd_kernel=True)
    mask = _splash_mask.MultiHeadMask(
        [_splash_mask.CausalMask((seq_len, seq_len))] * heads)
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(mask, block_sizes=blocks,
                                       head_shards=1, q_seq_shards=1,
                                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def causal_flash_attention(q, k, v, *, interpret: bool = False):
    """Causal softmax attention of ``(B, S, H, dh)`` q/k/v (MHA, positions
    ``arange(S)``) with the ``1/sqrt(dh)`` scale, differentiable through
    the kernel's own backward. Scores, softmax and accumulation are
    float32, PV included. The kernel takes no scale: q is scaled and cast
    to k's dtype here, so a caller that passes q in float32 rounds it once,
    as a bf16 q is rounded once on the XLA core. ``S`` must have a
    ``flash_block``; ``dh`` must be a multiple of 128."""
    _, S, H, dh = q.shape
    if flash_block(S) is None or dh % LANE:
        raise ValueError(f"causal flash attention needs S and dh multiples "
                         f"of {LANE}; got shape {tuple(q.shape)}")
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(dh))).astype(k.dtype)
    heads_major = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    o = jax.vmap(_causal_splash(S, H, interpret))(
        heads_major(q), heads_major(k), heads_major(v))
    return heads_major(o)

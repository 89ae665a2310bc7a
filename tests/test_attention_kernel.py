"""The training period's causal attention core on the Pallas flash kernel
(``kernels.ops.causal_flash_attention``, JAX's bundled splash-attention
TPU kernel with its own backward): parity with ``models.attention.attention_core`` for the
output and dq/dk/dv, the rule that picks the kernel or the XLA fallback in
``core/tp.py::_attention_core_fn``, and one ``sp_period`` forward and
gradient on a four-device ring. The kernel runs in the Pallas interpreter
on the CPU; these say nothing about its speed."""
from __future__ import annotations

import functools
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import tp
from repro.kernels import ops
from repro.models.attention import attention_core

HERE = pathlib.Path(__file__).resolve().parent


def _qkvg(shape, dtype):
    ks = jax.random.split(jax.random.key(0), 4)
    return [jax.random.normal(k, shape, jnp.float32).astype(dtype)
            for k in ks]


def _xla_core(q, k, v):
    pos = jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2])
    return attention_core(q, k, v, q_positions=pos, kv_positions=pos,
                          causal=True)


def _fwd_bwd(core, q, k, v, g):
    o, vjp = jax.vjp(core, q, k, v)
    return [np.asarray(x.astype(jnp.float32)) for x in (o, *vjp(g))]


# float32 pins the same function; bfloat16 the bf16-level rounding of the
# operands on both paths (the kernel rounds q once more, after its scale,
# and multiplies PV in float32 where the XLA core casts p to bfloat16).
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_flash_matches_xla_core_forward_and_grads(dtype):
    q, k, v, g = _qkvg((2, 512, 2, 128), jnp.dtype(dtype))
    want = _fwd_bwd(_xla_core, q, k, v, g)
    got = _fwd_bwd(functools.partial(ops.causal_flash_attention,
                                     interpret=True), q, k, v, g)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        scale = np.max(np.abs(b))
        np.testing.assert_allclose(a, b, atol=TOL[dtype] * scale, rtol=0,
                                   err_msg=name)


def test_flash_block_from_sequence_length():
    assert ops.flash_block(4096) == ops.FLASH_BLOCK
    assert ops.flash_block(256) == 256
    assert ops.flash_block(1536) == 768
    assert ops.flash_block(200) is None
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.causal_flash_attention(*_qkvg((1, 256, 1, 64), jnp.bfloat16)[:3])


def _scopes(jaxpr) -> set:
    """Every name-stack scope an equation of ``jaxpr`` (or of a jaxpr it
    calls) was traced under."""
    out = set()
    for eqn in jaxpr.eqns:
        out.update(str(eqn.source_info.name_stack).split("/"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _scopes(sub)
    return out


CASES = {
    # name: (on the TPU?, S, heads, kv heads, head dim, window, prefix_len,
    #        runs the kernel?)
    "causal_mha": (True, 256, 2, 2, 128, 0, 0, True),
    "window": (True, 256, 2, 2, 128, 64, 0, False),
    "prefix_lm": (True, 256, 2, 2, 128, 0, 16, False),
    "cpu_backend": (False, 256, 2, 2, 128, 0, 0, False),
    "ragged_seq": (True, 200, 2, 2, 128, 0, 0, False),
    "head_dim_64": (True, 256, 2, 2, 64, 0, 0, False),
    "gqa": (True, 256, 4, 2, 128, 0, 0, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_core_dispatch_rule(case, monkeypatch):
    """The period's core closure runs the kernel (``attn.flash``, a
    ``pallas_call``) only for causal MHA on the TPU with a blockable
    sequence and a 128-lane head size; otherwise the XLA core
    (``attn.xla``)."""
    tpu, S, H, Hkv, dh, window, prefix_len, flash = CASES[case]
    if tpu:  # the tests run on the CPU: stand in for the TPU backend
        monkeypatch.setattr(tp, "_on_tpu", lambda: True)
    cfg = get_arch("deepseek-7b").smoke().scaled(
        num_heads=H, num_kv_heads=Hkv, head_dim=dh)
    core = tp._attention_core_fn(cfg, 1, window=window,
                                 prefix_len=prefix_len)
    q = jnp.zeros((1, S, H * dh), jnp.bfloat16)
    kv = jnp.zeros((1, S, Hkv * dh), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(core)(q, kv, kv)
    scopes = _scopes(jaxpr.jaxpr)
    assert ("attn.flash" in scopes) == flash, scopes
    assert ("attn.xla" in scopes) == (not flash), scopes
    assert ("pallas_call" in str(jaxpr)) == flash


def test_sp_period_on_four_device_ring_flash_vs_fallback():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(HERE / "flash_ring_check.py")],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["flash_scopes"] == [False, True], res
    assert res["xla_scopes"] == [True, False], res
    assert res["leaves"] > 10 and res["max_rel_gap"] < 1e-5, res

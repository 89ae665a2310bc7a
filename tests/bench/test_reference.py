"""The plain float32 reference agrees with the program's model on the same
weights at smoke widths on the CPU: hidden states, logits and loss, for the
MHA (deepseek-7b) and GQA (internlm2) configurations."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, weights
from bench.reference import model as ref
from smoke_cells import config_file, smoke_config


@pytest.mark.parametrize("config", ["deepseek-7b-tp4", "internlm2-1.8b"])
def test_reference_matches_program_in_float32(config):
    from repro.models import build_model
    from repro.runtime import Runtime

    cfg = smoke_config(config_file(config))
    cfg["rms_norm_eps"] = 1e-6          # the program's fixed epsilon
    arch = program.arch_of(cfg)
    model = build_model(arch, Runtime(compute_dtype="float32", remat=False,
                                      loss_chunk=16))
    key = weights.seed_key(2 ** 33 + 1)
    layout = jax.eval_shape(model.init, key)
    params = weights.make_params(layout, key)
    toks = jax.random.randint(jax.random.key(1), (2, 33), 1, 256)
    tokens, labels = toks[:, :-1], toks[:, 1:]
    dm = ref.dims_of(cfg)

    with jax.default_matmul_precision("highest"):
        h_prog, _ = model.forward(params, tokens)
        z_prog = model.logits(params, h_prog)
        l_prog = model.loss(params, {"tokens": tokens, "labels": labels})
    h_ref = ref.hidden(params, tokens, dm)
    z_ref = ref.logits(params, h_ref)
    l_ref = ref.loss(params, tokens, labels, jnp.ones(labels.shape), dm,
                     chunk=8)
    np.testing.assert_allclose(h_prog, h_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(z_prog, z_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(l_prog, l_ref, rtol=1e-6)


def test_reference_attention_blocks_agree():
    """Query blocks and the causal mask give what one block gives."""
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (1, 16, 4, 8))
    k = jax.random.normal(k2, (1, 16, 2, 8))
    v = jax.random.normal(k3, (1, 16, 2, 8))
    whole = ref.attention(q, k, v, False, q_block=16)
    blocks = ref.attention(q, k, v, False, q_block=4)
    np.testing.assert_allclose(whole, blocks, rtol=1e-6, atol=1e-6)
    # position 0 sees only itself: its output is its own value head
    np.testing.assert_allclose(whole[0, 0, :8], v[0, 0, 0], rtol=1e-6)


def test_float8_control_is_coarser():
    x = jax.random.normal(jax.random.key(3), (64, 64))
    err = float(jnp.max(jnp.abs(ref.fp8(x) - x)) / jnp.max(jnp.abs(x)))
    assert 1e-3 < err < 0.07

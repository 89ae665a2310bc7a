"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle.

Every call passes ``interpret=True`` itself: the wrappers lower natively
unless told otherwise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-1)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N", [
    (128, 128, 128), (256, 512, 128), (64, 384, 96), (32, 32, 32),
    (512, 128, 256), (128, 1024, 64),
])
def test_matmul_sweep(M, K, N, dtype):
    a = _rand(jax.random.key(0), (M, K), dtype)
    b = _rand(jax.random.key(1), (K, N), dtype)
    got = ops.matmul(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 32), (128, 64, 256),
                                      (256, 256, 512)])
def test_matmul_block_shapes(bm, bn, bk):
    a = _rand(jax.random.key(2), (256, 512), jnp.float32)
    b = _rand(jax.random.key(3), (512, 128), jnp.float32)
    got = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul_ref(a, b)),
                               **TOL[jnp.float32])


@given(m=st.sampled_from([16, 64, 128]), k=st.sampled_from([32, 128, 320]),
       n=st.sampled_from([16, 48, 128]))
@settings(max_examples=12, deadline=None)
def test_matmul_property(m, k, n):
    a = _rand(jax.random.key(m * k), (m, k), jnp.float32)
    b = _rand(jax.random.key(k * n + 1), (k, n), jnp.float32)
    got = ops.matmul(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul_ref(a, b)),
                               **TOL[jnp.float32])


# ---------------------------------------------------------------------------
# fused matmul + rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (64, 512, 384),
                                   (256, 128, 64)])
def test_matmul_rmsnorm_sweep(M, K, N, dtype):
    a = _rand(jax.random.key(0), (M, K), dtype)
    b = _rand(jax.random.key(1), (K, N), dtype)
    scale = _rand(jax.random.key(2), (N,), jnp.float32) * 0.1
    got = ops.matmul_rmsnorm(a, b, scale, interpret=True)
    want = ref.matmul_rmsnorm_ref(a, b, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_matmul_rmsnorm_matches_model_norm():
    """The kernel's epilogue must equal the model's apply_norm(rmsnorm)."""
    from repro.models.layers import apply_norm
    a = _rand(jax.random.key(0), (32, 64), jnp.float32)
    b = _rand(jax.random.key(1), (64, 48), jnp.float32)
    scale = _rand(jax.random.key(2), (48,), jnp.float32) * 0.1
    got = ops.matmul_rmsnorm(a, b, scale, interpret=True)
    want = apply_norm("rmsnorm", {"scale": scale}, a @ b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("BH,S,d", [(2, 128, 64), (4, 256, 32), (1, 512, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(BH, S, d, dtype, causal):
    ks = jax.random.split(jax.random.key(S + d), 3)
    q = _rand(ks[0], (BH, S, d), dtype)
    k = _rand(ks[1], (BH, S, d), dtype)
    v = _rand(ks[2], (BH, S, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, bq=64, bkv=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_matches_model_attention_core():
    """Kernel vs the model zoo's chunked attention core (same oracle)."""
    from repro.models.attention import attention_core
    B, S, H, d = 2, 128, 2, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], (B, S, H, d), jnp.float32)
    k = _rand(ks[1], (B, S, H, d), jnp.float32)
    v = _rand(ks[2], (B, S, H, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = attention_core(q, k, v, q_positions=pos, kv_positions=pos,
                          causal=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    got = ops.flash_attention(qf, kf, vf, causal=True, bq=32, bkv=32,
                              interpret=True)
    got = got.reshape(B, H, S, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-4)


@given(s=st.sampled_from([64, 128, 320]), d=st.sampled_from([32, 64]),
       bq=st.sampled_from([32, 64]), bkv=st.sampled_from([32, 64]))
@settings(max_examples=10, deadline=None)
def test_flash_block_shape_property(s, d, bq, bkv):
    ks = jax.random.split(jax.random.key(s * d + bq), 3)
    q, k, v = (_rand(kk, (1, s, d), jnp.float32) for kk in ks)
    got = ops.flash_attention(q, k, v, causal=True, bq=bq, bkv=bkv,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# block sizing: (8, 128)-aligned divisors or the whole dim, else a clear error
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dim,want,align,block", [
    (4096, 256, 8, 256), (96, 256, 128, 96), (128, 32, 128, 128),
    (320, 64, 8, 64), (384, 512, 128, 384), (1152, 512, 128, 384),
    (6912, 512, 128, 384),
])
def test_block_divisor_aligned(dim, want, align, block):
    from repro.kernels.matmul import block_divisor
    assert block_divisor(dim, want, align) == block


def test_block_divisor_rejects_unaligned_dim():
    from repro.kernels.matmul import block_divisor
    with pytest.raises(ValueError, match=r"dim 4100 of shape \(4100, 4096\)"):
        block_divisor(4100, 256, 8, shape=(4100, 4096))

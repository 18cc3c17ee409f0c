"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp ref.py oracles (interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.tiled_matmul import matmul, matmul_ref
from repro.kernels.winograd import conv3x3_ref, conv3x3_winograd


@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 2, 2, 128, 32), (2, 4, 2, 256, 64), (1, 8, 1, 128, 128),
    (1, 4, 4, 384, 64),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_shapes(b, h, kv, s, d, causal, window):
    q = jax.random.normal(jax.random.key(1), (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (b, kv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (b, kv, s, d), jnp.float32)
    out = flash_attention(q, k, v, causal, window, 0.0, 128, 128)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_refuses_unmasked_kv_padding():
    """Non-causal attention cannot mask padded KV blocks: it raises rather
    than answer with something other than the kernel."""
    q = jax.random.normal(jax.random.key(1), (1, 2, 200, 32), jnp.float32)
    with pytest.raises(ValueError, match="multiple of block_kv"):
        flash_attention(q, q, q, False, 0, 0.0, 128, 128)
    out = flash_attention(q, q, q, True, 0, 0.0, 128, 128)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_ref(q, q, q, causal=True)),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    b, h, kv, s, d = 1, 4, 2, 256, 64
    q = jax.random.normal(jax.random.key(1), (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.key(2), (b, kv, s, d), dtype)
    v = jax.random.normal(jax.random.key(3), (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, True, 0, 0.0, 128, 128)
    ref = attention_ref(q, k, v, causal=True)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_softcap():
    b, h, kv, s, d = 1, 2, 2, 128, 32
    q = jax.random.normal(jax.random.key(1), (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (b, kv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (b, kv, s, d), jnp.float32)
    out = flash_attention(q, k, v, True, 0, 30.0, 128, 128)
    ref = attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_grad_matches_ref():
    b, h, kv, s, d = 1, 2, 1, 128, 32
    q = jax.random.normal(jax.random.key(1), (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (b, kv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (b, kv, s, d), jnp.float32)
    g1 = jax.grad(lambda q_: flash_attention(q_, k, v, True, 0, 0.0,
                                             128, 128).sum())(q)
    g2 = jax.grad(lambda q_: attention_ref(q_, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (200, 300, 150),
                                   (64, 512, 32), (257, 129, 65)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_matmul(m, k, n, dtype):
    a = jax.random.normal(jax.random.key(4), (m, k), dtype)
    b = jax.random.normal(jax.random.key(5), (k, n), dtype)
    out = matmul(a, b)
    ref = matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_tiled_matmul_block_sweep():
    a = jax.random.normal(jax.random.key(4), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.key(5), (256, 256), jnp.float32)
    ref = matmul_ref(a, b)
    for bm, bn, bk in [(64, 64, 64), (128, 128, 64), (128, 64, 128)]:
        out = matmul(a, b, block_m=bm, block_n=bn, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,hw,cin,cout", [(1, 8, 4, 8), (2, 14, 8, 16),
                                           (1, 13, 3, 5)])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_winograd_conv(b, hw, cin, cout, padding):
    x = jax.random.normal(jax.random.key(6), (b, hw, hw, cin), jnp.float32)
    w = jax.random.normal(jax.random.key(7), (3, 3, cin, cout), jnp.float32)
    out = conv3x3_winograd(x, w, padding)
    ref = conv3x3_ref(x, w, padding)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

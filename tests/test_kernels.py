"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Every Pallas kernel runs in interpret mode (CPU container); the oracle in
repro.kernels.ref is ground truth.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# sr_quantize


@pytest.mark.parametrize("shape", [(7,), (128,), (33, 65), (4, 3, 50), (256, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("wl,fl", [(8, 4), (4, 2), (16, 8), (2, 0)])
def test_sr_quantize_matches_ref(shape, dtype, wl, fl):
    k1, k2 = jax.random.split(KEY)
    x = (jax.random.normal(k1, shape, jnp.float32) * 3).astype(dtype)
    u = jax.random.uniform(k2, shape, jnp.float32)
    got = ops.sr_quantize(x, u, wl, fl, use_pallas=True)
    want = ref.ref_sr_quantize(x, u, wl, fl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sr_quantize_on_grid():
    """Output values land exactly on the ⟨WL,FL⟩ grid and inside its range."""
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (4096,)) * 10
    u = jax.random.uniform(k2, x.shape)
    q = ops.sr_quantize(x, u, 8, 4, use_pallas=True)
    scaled = np.asarray(q) * 16
    np.testing.assert_array_equal(scaled, np.round(scaled))
    assert scaled.min() >= -128 and scaled.max() <= 127


# ---------------------------------------------------------------------------
# fxp_matmul / int8_matmul


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (64, 128, 96), (100, 70, 50),
                                   (256, 512, 256),
                                   # primes past the default blocks: partial
                                   # boundary blocks on M and K, tail-masked
                                   (509, 1031, 127)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fxp_matmul_matches_ref(m, k, n, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    s = jnp.float32(1 / 64)
    got = ops.fxp_matmul(x, wq, s, use_pallas=True)
    want = ref.ref_fxp_matmul(x, wq, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2)


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (128, 256, 128), (48, 72, 36),
                                   (509, 1031, 127)])
def test_int8_matmul_matches_ref(m, k, n):
    k1, k2 = jax.random.split(KEY)
    xq = jax.random.randint(k1, (m, k), -128, 128, jnp.int8)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    got = ops.int8_matmul(xq, wq, jnp.float32(0.02), jnp.float32(0.3),
                          use_pallas=True)
    want = ref.ref_int8_matmul(xq, wq, jnp.float32(0.02), jnp.float32(0.3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_int8_matmul_exact_integer_accumulation():
    """int32 accumulation must be exact (no float rounding of products)."""
    xq = jnp.full((8, 1024), 127, jnp.int8)
    wq = jnp.full((1024, 8), 127, jnp.int8)
    got = ops.int8_matmul(xq, wq, jnp.float32(1.0), jnp.float32(1.0),
                          use_pallas=True)
    assert float(got[0, 0]) == 127 * 127 * 1024


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("sq,skv", [(128, 128), (64, 128), (1, 128), (96, 96),
                                    # prime seq dims: partial boundary
                                    # blocks in both grid dims (bq=bk=32)
                                    (127, 127), (131, 257)])
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2), (6, 2), (8, 1)])
def test_flash_attention_matches_ref(sq, skv, h, hkv):
    k1, k2, k3 = jax.random.split(KEY, 3)
    d = 64
    q = jax.random.normal(k1, (2, sq, h, d), jnp.float32)
    k = jax.random.normal(k2, (2, skv, hkv, d), jnp.float32)
    v = jax.random.normal(k3, (2, skv, hkv, d), jnp.float32)
    got = ops.attention(q, k, v, causal=True, use_pallas=True, bq=32, bk=32)
    want = ref.ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


# 8 and 16 lie inside one 32-block; 80 spans several, 200 the whole sequence
@pytest.mark.parametrize("window", [8, 16, 64, 80, 200])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_flash_attention_window_softcap(window, softcap):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(k2, (1, 128, 4, 64), jnp.float32)
    v = jax.random.normal(k3, (1, 128, 4, 64), jnp.float32)
    got = ops.attention(q, k, v, causal=True, window=window, softcap=softcap,
                        use_pallas=True, bq=32, bk=32)
    want = ref.ref_attention(q, k, v, causal=True, window=window,
                             softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_bf16():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (1, 64, 2, 128), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 64, 2, 128), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 64, 2, 128), jnp.bfloat16)
    got = ops.attention(q, k, v, causal=True, use_pallas=True, bq=32, bk=32)
    want = ref.ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=3e-2,
                               atol=3e-2)

"""Pallas causal-FLARE chunk kernel vs the jnp factored/exact references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flare_stream import flare_causal, flare_causal_ref
from repro.kernels.ops import flare_causal_fused

KEY = jax.random.PRNGKey(11)


def _qkv(b, h, n, m, d, scale=0.5):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (h, m, d)) * scale
    k = jax.random.normal(ks[1], (b, h, n, d)) * scale
    v = jax.random.normal(ks[2], (b, h, n, d))
    return q, k, v


@pytest.mark.parametrize("b,h,n,m,d,tile", [
    (1, 2, 64, 16, 8, 16),
    (2, 1, 128, 32, 16, 32),
    (1, 1, 96, 8, 8, 32),   # n not a multiple of the default tile
])
def test_kernel_matches_oracle(b, h, n, m, d, tile):
    q, k, v = _qkv(b, h, n, m, d)
    y_k = flare_causal_fused(q, k, v, tile=tile)
    y_ref = flare_causal_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=2e-5)


def test_kernel_matches_factored_jnp_path():
    q, k, v = _qkv(1, 2, 64, 16, 8, scale=1.5)
    y_k = flare_causal_fused(q, k, v, tile=16)
    y_j = flare_causal(q, k, v, chunk_size=16, mode="factored")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j), atol=2e-5)


def test_kernel_tile_invariance():
    q, k, v = _qkv(1, 1, 64, 8, 8)
    y16 = flare_causal_fused(q, k, v, tile=16)
    y64 = flare_causal_fused(q, k, v, tile=64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64), atol=2e-5)


def test_kernel_bf16():
    q, k, v = _qkv(1, 2, 64, 16, 8)
    y_k = flare_causal_fused(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                             v.astype(jnp.bfloat16), tile=16)
    y_ref = flare_causal_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_ref),
                               atol=3e-2, rtol=3e-2)

"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatch import MixerPlan
from repro.core.flare import flare_mixer
from repro.kernels import ref
from repro.kernels.flare_packed import flare_mixer_packed
from repro.kernels.ops import flash_attention

KEY = jax.random.PRNGKey(2)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,n,m,d", [
    (1, 1, 64, 16, 8),
    (2, 3, 128, 32, 16),
    (1, 2, 256, 64, 4),     # paper regime: tiny head dim
    (2, 1, 96, 8, 32),      # N not a multiple of the default tile
])
def test_flare_kernel_sweep(b, h, n, m, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = (jax.random.normal(ks[0], (h, m, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, h, n, d)) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, n, d)).astype(dtype)
    y = flare_mixer_packed(q, k, v, block_n=32)
    y_ref = flare_mixer(q, k, v, policy=MixerPlan("sdpa"))
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 24)])
@pytest.mark.parametrize("sq,skv,d", [(64, 64, 16), (128, 64, 8), (96, 96, 32)])
def test_flash_kernel_sweep(sq, skv, d, causal, window, dtype):
    b, h = 2, 2
    ks = jax.random.split(KEY, 3)
    q = (jax.random.normal(ks[0], (b, h, sq, d))).astype(dtype)
    k = (jax.random.normal(ks[1], (b, h, skv, d))).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, skv, d)).astype(dtype)
    scale = 1.0 / np.sqrt(d)
    o = flash_attention(q, k, v, scale=scale, causal=causal, window=window,
                        block_q=32, block_kv=32)
    o_ref = ref.flash_attention_ref(
        q.reshape(b * h, sq, d), k.reshape(b * h, skv, d), v.reshape(b * h, skv, d),
        scale=scale, causal=causal, window=window).reshape(b, h, sq, d)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
                               **_tol(dtype))


def test_lane_padding_is_exact():
    """The packed wrapper zero-pads pack*D to 128 lanes (D=8 at pack=1) —
    must be exactly invisible."""
    b, h, n, m, d = 1, 2, 64, 16, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (h, m, d)) * 0.5
    k = jax.random.normal(ks[1], (b, h, n, d)) * 0.5
    v = jax.random.normal(ks[2], (b, h, n, d))
    y1 = flare_mixer_packed(q, k, v, pack=1, block_n=32)
    y2 = flare_mixer(q, k, v)
    np.testing.assert_allclose(y1, y2, atol=1e-5)
    assert y1.shape[-1] == d  # padding sliced back off


def test_flash_fully_masked_rows():
    """Windowed attention where some rows see zero keys must not NaN."""
    b, h, s, d = 1, 1, 32, 8
    q = jax.random.normal(KEY, (b, h, s, d))
    k = jax.random.normal(KEY, (b, h, s, d))
    v = jax.random.normal(KEY, (b, h, s, d))
    o = flash_attention(q, k, v, scale=0.3, causal=True, window=1, block_q=8, block_kv=8)
    assert bool(jnp.isfinite(o).all())

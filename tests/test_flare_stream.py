"""Causal/streaming FLARE (DESIGN.md §3.1): equivalences + stability."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flare_stream import (
    flare_causal,
    flare_causal_ref,
    stream_append,
    stream_chunk,
    stream_init,
)

KEY = jax.random.PRNGKey(1)


def _qkv(b=2, h=3, n=32, m=8, d=8, scale=0.5):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (h, m, d)) * scale
    k = jax.random.normal(ks[1], (b, h, n, d)) * scale
    v = jax.random.normal(ks[2], (b, h, n, d))
    return q, k, v


def test_chunked_equals_ref():
    q, k, v = _qkv()
    y = flare_causal(q, k, v, chunk_size=8)
    y_ref = flare_causal_ref(q, k, v)
    np.testing.assert_allclose(y, y_ref, atol=1e-5)


def test_chunk_size_invariance():
    q, k, v = _qkv(n=32)
    y8 = flare_causal(q, k, v, chunk_size=8)
    y16 = flare_causal(q, k, v, chunk_size=16)
    y32 = flare_causal(q, k, v, chunk_size=32)
    np.testing.assert_allclose(y8, y16, atol=1e-5)
    np.testing.assert_allclose(y8, y32, atol=1e-5)


def test_append_loop_equals_chunked():
    """Token-by-token serving path == chunked training path."""
    q, k, v = _qkv(n=16)
    b, h, n, d = k.shape
    m = q.shape[1]
    state = stream_init(b, h, m, d)
    outs = []
    for t in range(n):
        state, y = stream_append(state, q, k[:, :, t], v[:, :, t])
        outs.append(y)
    y_loop = jnp.stack(outs, axis=2)
    y_chunk = flare_causal(q, k, v, chunk_size=8)
    np.testing.assert_allclose(y_loop, y_chunk, atol=1e-5)


def test_prefix_causality_exact_path():
    """Output at t must not depend on tokens > t — even under adversarial
    future values (the exact path's guarantee)."""
    q, k, v = _qkv(n=16)
    y_full = flare_causal(q, k, v, chunk_size=8, mode="exact")
    k2 = k.at[:, :, 12:].set(99.0)
    v2 = v.at[:, :, 12:].set(-99.0)
    y_pre = flare_causal(q, k2, v2, chunk_size=8, mode="exact")
    np.testing.assert_allclose(y_full[:, :, :12], y_pre[:, :, :12], atol=1e-5)


def test_prefix_causality_factored_path():
    """The factored path is causal within its bounded-score contract
    (future scores within ~85 nats of the running max)."""
    q, k, v = _qkv(n=16)
    y_full = flare_causal(q, k, v, chunk_size=8, mode="factored")
    k2 = k.at[:, :, 12:].set(4.0)   # large-but-realistic future change
    v2 = v.at[:, :, 12:].set(-4.0)
    y_pre = flare_causal(q, k2, v2, chunk_size=8, mode="factored")
    np.testing.assert_allclose(y_full[:, :, :12], y_pre[:, :, :12], atol=1e-5)


def test_factored_equals_exact_realistic():
    q, k, v = _qkv(n=32, scale=1.5)
    y_f = flare_causal(q, k, v, chunk_size=8, mode="factored")
    y_e = flare_causal(q, k, v, chunk_size=8, mode="exact")
    np.testing.assert_allclose(y_f, y_e, atol=1e-5)


def test_state_carries_across_chunks():
    q, k, v = _qkv(n=32)
    b, h, n, d = k.shape
    m = q.shape[1]
    s1 = stream_init(b, h, m, d)
    s1, y1 = stream_chunk(s1, q, k[:, :, :16], v[:, :, :16])
    s1, y2 = stream_chunk(s1, q, k[:, :, 16:], v[:, :, 16:])
    y_two = jnp.concatenate([y1, y2], axis=2)
    y_one = flare_causal(q, k, v, chunk_size=32)
    np.testing.assert_allclose(y_two, y_one, atol=1e-5)


def test_500k_style_stability():
    """Long-stream numerical stability: many appends with large scores."""
    q, k, v = _qkv(n=256, scale=4.0)
    y = flare_causal(q, k, v, chunk_size=64)
    assert bool(jnp.isfinite(y).all())


def test_state_size_constant():
    """The decode state is O(M*D) per head — independent of tokens seen."""
    q, k, v = _qkv(n=64)
    b, h, n, d = k.shape
    m = q.shape[1]
    state = stream_init(b, h, m, d)
    sizes0 = [x.size for x in state]
    state, _ = stream_chunk(state, q, k, v)
    assert [x.size for x in state] == sizes0

"""FLARE operator invariants (paper §3.2, Eq. 7-9) — unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.dispatch import MixerPlan
from repro.core.flare import (
    flare_block,
    flare_dense_operator,
    flare_layer,
    flare_mixer,
    init_flare_block,
    init_flare_layer,
    sdpa,
)

KEY = jax.random.PRNGKey(0)


def _qkv(h=4, m=8, n=37, d=16, b=2, scale=0.5, key=KEY):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (h, m, d)) * scale
    k = jax.random.normal(kk, (b, h, n, d)) * scale
    v = jax.random.normal(kv, (b, h, n, d))
    return q, k, v


class TestOperatorEquivalence:
    def test_sdpa_equals_materialized(self):
        """Fig. 3 (two SDPA calls) == Fig. 7 (materialized weights)."""
        q, k, v = _qkv()
        y1 = flare_mixer(q, k, v, policy=MixerPlan("sdpa"))
        y2 = flare_mixer(q, k, v, policy=MixerPlan("materialized"))
        np.testing.assert_allclose(y1, y2, atol=1e-5)

    def test_mixer_equals_dense_operator(self):
        """Y = W V with W = W_dec @ W_enc (Eq. 7/9)."""
        q, k, v = _qkv(b=1)
        w = flare_dense_operator(q, k[0])
        y_dense = jnp.einsum("hnk,hkd->hnd", w, v[0])
        y = flare_mixer(q, k, v)[0]
        np.testing.assert_allclose(y_dense, y, atol=1e-5)

    def test_scale_is_one(self):
        """Paper uses s=1, not 1/sqrt(D): doubling q must change outputs in
        the un-normalized way (guards against an accidental 1/sqrt(D))."""
        q, k, v = _qkv()
        y1 = flare_mixer(q, k, v)
        y2 = flare_mixer(2.0 * q, k, v)
        assert not np.allclose(y1, y2, atol=1e-4)

    def test_sdpa_matches_manual_softmax(self):
        q, k, v = _qkv(b=1)
        out = sdpa(q, k[0], v[0], scale=1.0)  # q broadcasts over heads' batch
        s = jnp.einsum("hmd,hnd->hmn", q, k[0]).astype(jnp.float32)
        ref = jnp.einsum("hmn,hnd->hmd", jax.nn.softmax(s, -1), v[0])
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestLowRankStructure:
    def test_rank_at_most_m(self):
        q, k, _ = _qkv(m=8, n=64)
        w = np.asarray(flare_dense_operator(q, k[0]))
        for h in range(w.shape[0]):
            assert np.linalg.matrix_rank(w[h], tol=1e-5) <= 8

    def test_w_row_stochastic(self):
        """W = W_dec W_enc with both factors row-stochastic => W rows sum to 1."""
        q, k, _ = _qkv()
        w = np.asarray(flare_dense_operator(q, k[0]))
        np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-5)
        assert (w >= -1e-7).all()

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 6), st.integers(2, 24), st.integers(2, 48))
    def test_rank_bound_property(self, h, m, n):
        d = 8
        key = jax.random.fold_in(KEY, h * 1000 + m * 10 + n)
        kq, kk = jax.random.split(key)
        q = jax.random.normal(kq, (h, m, d))
        k = jax.random.normal(kk, (h, n, d))
        w = np.asarray(flare_dense_operator(q, k))
        for hh in range(h):
            assert np.linalg.matrix_rank(w[hh], tol=1e-5) <= min(m, n)


class TestPermutationEquivariance:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_mixer_permutation_equivariant(self, seed):
        """FLARE makes no token-ordering assumption (paper §5.3)."""
        q, k, v = _qkv(n=23, key=jax.random.PRNGKey(seed))
        perm = jax.random.permutation(jax.random.PRNGKey(seed + 1), 23)
        y = flare_mixer(q, k, v)
        y_perm = flare_mixer(q, k[:, :, perm], v[:, :, perm])
        np.testing.assert_allclose(y[:, :, perm], y_perm, atol=1e-5)

    def test_layer_permutation_equivariant(self):
        p = init_flare_layer(KEY, 32, 4, 8)
        x = jax.random.normal(KEY, (2, 19, 32))
        perm = jax.random.permutation(jax.random.PRNGKey(7), 19)
        y = flare_layer(p, x)
        y_perm = flare_layer(p, x[:, perm])
        np.testing.assert_allclose(y[:, perm], y_perm, atol=2e-5)


class TestBlock:
    def test_block_shapes_and_finite(self):
        p = init_flare_block(KEY, 32, 4, 8)
        x = jax.random.normal(KEY, (2, 37, 32))
        y = flare_block(p, x)
        assert y.shape == x.shape
        assert bool(jnp.isfinite(y).all())

    def test_block_grads_finite(self):
        p = init_flare_block(KEY, 32, 4, 8)
        x = jax.random.normal(KEY, (2, 16, 32))
        g = jax.grad(lambda pp: jnp.sum(flare_block(pp, x) ** 2))(p)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.isfinite(leaf).all())

    def test_head_latent_independence(self):
        """Perturbing head h's latent slice must not change other heads'
        mixer outputs (head-wise independent pathways)."""
        q, k, v = _qkv(h=4)
        y = flare_mixer(q, k, v)
        q2 = q.at[2].add(1.0)
        y2 = flare_mixer(q2, k, v)
        np.testing.assert_allclose(y[:, [0, 1, 3]], y2[:, [0, 1, 3]], atol=1e-6)
        assert not np.allclose(y[:, 2], y2[:, 2], atol=1e-3)

    def test_bf16_stability_large_scores(self):
        """Beyond-paper fix: max-subtracted softmax survives large logits."""
        q, k, v = _qkv(scale=8.0)
        y = flare_mixer(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
        assert bool(jnp.isfinite(y.astype(jnp.float32)).all())

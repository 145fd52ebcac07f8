"""The benchmark's one traffic generator. A traffic mix is a JSON file under
``bench/traffic/``; its ``kind`` picks the generator below and the rest of
the file gives its parameters. Every generator is a function of the mix and
``--seed`` only.

``darcy``: training batches of solved Darcy problems on a square grid,
made on the device in one call (adapted from the program's
``data/pde_data.py``): a log-normal permeability ``a = exp(mu + s g)``
(``g`` a Gaussian random field with spectral decay ``|k|^-alpha``, ``s`` the
mix's ``log_a_std``), ``-div(a grad u) = 1`` with zero boundary values
solved by conjugate gradients; inputs ``(x, y, a)``, target ``u`` times the
mix's fixed ``target_scale`` (one scale for the whole data set, as a
trainer normalizes by data-set statistics, so the problems keep their
physical amplitudes). The level ``mu`` spans ``log_a_mean`` evenly across
the rows of every batch (the same levels in every batch and seed), in an
order drawn from the seed: permeability levels of real formations differ
by orders of magnitude, and ``u`` scales as ``1/exp(mu)``.
"""
from __future__ import annotations

import numpy as np


def key_from_seed(seed: int):
    """A threefry key from any whole number (JAX's own PRNGKey takes 32
    bits only)."""
    import jax

    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32), impl="threefry2x32")


# ------------------------------------------------------------------ darcy

def _grf(key, n, alpha):
    import jax
    import jax.numpy as jnp

    kx = jnp.fft.fftfreq(n)[:, None]
    ky = jnp.fft.fftfreq(n)[None, :]
    k2 = kx * kx + ky * ky
    filt = jnp.where(k2 == 0, 0.0, (k2 + 1e-6) ** (-alpha / 2.0))
    field = jnp.real(jnp.fft.ifft2(jnp.fft.fft2(jax.random.normal(key, (n, n))) * filt))
    return field / jnp.maximum(jnp.std(field), 1e-9)


def _darcy_op(u, a):
    import jax.numpy as jnp

    n = u.shape[0]
    up = jnp.pad(u, 1)
    ap = jnp.pad(a, 1, mode="edge")
    c = ap[1:-1, 1:-1]
    du = (0.5 * (c + ap[2:, 1:-1]) * (up[2:, 1:-1] - u)
          + 0.5 * (c + ap[:-2, 1:-1]) * (up[:-2, 1:-1] - u)
          + 0.5 * (c + ap[1:-1, 2:]) * (up[1:-1, 2:] - u)
          + 0.5 * (c + ap[1:-1, :-2]) * (up[1:-1, :-2] - u))
    return -du * (n + 1) ** 2


def _darcy_one(key, mu, grid, cg_iters, alpha, log_a_std, target_scale):
    import jax
    import jax.numpy as jnp

    a = jnp.exp(mu + log_a_std * _grf(key, grid, alpha))
    f = jnp.ones((grid, grid))

    def body(carry, _):
        u, r, p, rs = carry
        ap = _darcy_op(p, a)
        al = rs / jnp.maximum(jnp.sum(p * ap), 1e-30)
        u, r = u + al * p, r - al * ap
        rs2 = jnp.sum(r * r)
        return (u, r, r + (rs2 / jnp.maximum(rs, 1e-30)) * p, rs2), None

    (u, _, _, _), _ = jax.lax.scan(body, (jnp.zeros_like(f), f, f, jnp.sum(f * f)),
                                   None, length=cg_iters)
    xs = (jnp.arange(grid) + 0.5) / grid
    xx, yy = jnp.meshgrid(xs, xs, indexing="ij")
    x = jnp.stack([xx, yy, a], axis=-1).reshape(-1, 3)
    return x, target_scale * u.reshape(-1, 1)


def darcy_batches(mix: dict, key) -> list:
    """``mix["distinct_batches"]`` batches {"x": [B, N, 3], "y": [B, N, 1]}
    (float32, on the device) from a JAX key, all rows different."""
    import functools

    import jax
    import jax.numpy as jnp

    nb, b = mix["distinct_batches"], mix["batch"]
    static = (nb, b, mix["grid"], mix["cg_iters"], float(mix["alpha"]),
              float(mix["log_a_std"]), float(mix["target_scale"]),
              tuple(float(v) for v in mix["log_a_mean"]))

    @functools.partial(jax.jit, static_argnums=1)
    def make(key, static):
        nb, b, grid, iters, alpha, std, scale, (lo, hi) = static
        k_field, k_order = jax.random.split(key)
        levels = jnp.linspace(lo, hi, b)
        mu = jax.vmap(lambda k: jax.random.permutation(k, levels))(
            jax.random.split(k_order, nb)).reshape(-1)
        return jax.vmap(lambda k, m: _darcy_one(k, m, grid, iters, alpha, std, scale))(
            jax.random.split(k_field, nb * b), mu)

    x, y = make(key, static)
    n = x.shape[1]
    x, y = x.reshape(nb, b, n, 3), y.reshape(nb, b, n, 1)
    return [{"x": x[i], "y": y[i]} for i in range(nb)]

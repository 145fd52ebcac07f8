"""PDE surrogate models: the paper's FLARE surrogate + Table-1 baselines.

All models share the same input/output projections (paper D.3 holds these
consistent "to facilitate an equitable comparison of their point-to-point
communication schemes"):

    in:  ResMLP(L=2, C_in -> C)          out: LN + ResMLP(L=2, C -> C_out)

Token mixers compared (benchmarks/bench_table1_pde.py):
  - flare:        B x FLARE blocks (the paper)
  - vanilla:      pre-LN multi-head self-attention + GELU MLP (ratio 4)
  - perceiver:    one encode cross-attn -> B latent self-attn blocks ->
                  one decode cross-attn (PerceiverIO-lite)
  - linformer:    learned [M, N] K/V down-projections (fixed N)
  - transolver:   physics-attention slices (soft assignment -> latent
                  self-attn -> de-slicing), Transolver-lite w/o conv
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.flare import flare_block, init_flare_block, sdpa
from repro.models.transformer import _remat
from repro.nn.modules import (
    dense,
    init_dense,
    init_gelu_mlp,
    gelu_mlp,
    init_layernorm,
    init_resmlp,
    layernorm,
    resmlp,
    truncated_normal_init,
)


# ---------------------------------------------------------------------------
# Shared scaffold
# ---------------------------------------------------------------------------


def init_surrogate(key, mixer: str, *, in_dim: int, out_dim: int, dim: int,
                   num_blocks: int, num_heads: int, num_latents: int,
                   param_dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, num_blocks + 3)
    block_init = {
        "flare": lambda k: init_flare_block(k, dim, num_heads, num_latents, param_dtype=param_dtype),
        "vanilla": lambda k: init_vanilla_block(k, dim, num_heads, param_dtype=param_dtype),
        "linformer": lambda k: init_linformer_block(k, dim, num_heads, num_latents, param_dtype=param_dtype),
        "transolver": lambda k: init_transolver_block(k, dim, num_heads, num_latents, param_dtype=param_dtype),
    }
    params = {
        "in_proj": init_resmlp(keys[0], in_dim, dim, dim, 2, param_dtype=param_dtype),
        "out_norm": init_layernorm(dim, param_dtype=param_dtype),
        "out_proj": init_resmlp(keys[1], dim, dim, out_dim, 2, param_dtype=param_dtype),
    }
    if mixer == "perceiver":
        params["perceiver"] = init_perceiver(keys[2], dim, num_heads, num_latents,
                                             num_blocks, param_dtype=param_dtype)
    else:
        params["blocks"] = [block_init[mixer](keys[2 + i]) for i in range(num_blocks)]
    return params


def surrogate_forward(params: dict, x: jax.Array, *, mixer: str = "flare",
                      num_heads: int = 8, policy=None, impl=None,
                      remat: str = "none") -> jax.Array:
    """x: [B, N, F_in] point features -> [B, N, F_out].

    ``policy`` is a MixerPolicy or — the get_model path — the MixerPlan
    resolved once at model build; None falls back to the ambient policy
    stack. ``impl`` is the deprecated legacy string spelling. ``remat`` is
    the config's per-block rematerialization mode (full | dots | none)."""
    h = resmlp(params["in_proj"], x)
    if mixer == "perceiver":
        h = perceiver_forward(params["perceiver"], h, num_heads)
    else:
        apply = {
            "flare": lambda p, y: flare_block(p, y, policy=policy, impl=impl),
            "vanilla": lambda p, y: vanilla_block(p, y, num_heads),
            "linformer": lambda p, y: linformer_block(p, y, num_heads),
            "transolver": lambda p, y: transolver_block(p, y, num_heads),
        }[mixer]
        apply = _remat(apply, remat)
        for bp in params["blocks"]:
            h = apply(bp, h)
    h = layernorm(params["out_norm"], h)
    return resmlp(params["out_proj"], h)


def relative_l2(pred: jax.Array, target: jax.Array) -> jax.Array:
    """Paper Eq. 21/22, averaged over the batch."""
    num = jnp.sqrt(jnp.sum(jnp.square(pred - target), axis=(-2, -1)))
    den = jnp.sqrt(jnp.sum(jnp.square(target), axis=(-2, -1)))
    return jnp.mean(num / jnp.maximum(den, 1e-12))


def surrogate_loss(params, batch, *, mixer: str = "flare", num_heads: int = 8,
                   policy=None, impl=None, remat: str = "none"):
    from repro.core.policy import mixer_policy

    # the loss is the differentiated entry point: the requires_grad scope
    # keeps bare (plan-less) calls off forward-only mixers; build-time plans
    # were already resolved under requires_grad=True in get_model
    with mixer_policy(requires_grad=True):
        pred = surrogate_forward(params, batch["x"], mixer=mixer,
                                 num_heads=num_heads, policy=policy, impl=impl,
                                 remat=remat)
    return relative_l2(pred, batch["y"])


# ---------------------------------------------------------------------------
# Vanilla transformer block (pre-LN MHA + GELU MLP, ratio 4)
# ---------------------------------------------------------------------------


def init_vanilla_block(key, dim, num_heads, *, param_dtype=jnp.float32) -> dict:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "ln1": init_layernorm(dim, param_dtype=param_dtype),
        "wq": init_dense(k1, dim, dim, use_bias=True, param_dtype=param_dtype),
        "wk": init_dense(k2, dim, dim, use_bias=True, param_dtype=param_dtype),
        "wv": init_dense(k3, dim, dim, use_bias=True, param_dtype=param_dtype),
        "wo": init_dense(k4, dim, dim, use_bias=True, param_dtype=param_dtype),
        "ln2": init_layernorm(dim, param_dtype=param_dtype),
        "mlp": init_gelu_mlp(k5, dim, 4 * dim, param_dtype=param_dtype),
    }


def _mh(x, h):
    b, n, c = x.shape
    return x.reshape(b, n, h, c // h).transpose(0, 2, 1, 3)


def _unmh(x):
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def vanilla_block(p: dict, x: jax.Array, num_heads: int) -> jax.Array:
    h = num_heads
    y = layernorm(p["ln1"], x)
    q, k, v = (_mh(dense(p[w], y), h) for w in ("wq", "wk", "wv"))
    d = q.shape[-1]
    a = sdpa(q, k, v, scale=1.0 / math.sqrt(d))
    x = x + dense(p["wo"], _unmh(a))
    return x + gelu_mlp(p["mlp"], layernorm(p["ln2"], x))


# ---------------------------------------------------------------------------
# PerceiverIO-lite
# ---------------------------------------------------------------------------


def init_perceiver(key, dim, num_heads, num_latents, num_blocks, *, param_dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, num_blocks + 4)
    return {
        "latents": truncated_normal_init(1.0 / math.sqrt(dim))(keys[0], (num_latents, dim), param_dtype),
        "enc": init_vanilla_block(keys[1], dim, num_heads, param_dtype=param_dtype),
        "latent_blocks": [init_vanilla_block(keys[2 + i], dim, num_heads, param_dtype=param_dtype)
                          for i in range(num_blocks)],
        "dec": init_vanilla_block(keys[-1], dim, num_heads, param_dtype=param_dtype),
    }


def _cross(p, q_in, kv_in, num_heads):
    h = num_heads
    q = _mh(dense(p["wq"], layernorm(p["ln1"], q_in)), h)
    k = _mh(dense(p["wk"], layernorm(p["ln1"], kv_in)), h)
    v = _mh(dense(p["wv"], layernorm(p["ln1"], kv_in)), h)
    d = q.shape[-1]
    a = sdpa(q, k, v, scale=1.0 / math.sqrt(d))
    return q_in + dense(p["wo"], _unmh(a))


def perceiver_forward(p: dict, x: jax.Array, num_heads: int) -> jax.Array:
    b = x.shape[0]
    z = jnp.broadcast_to(p["latents"].astype(x.dtype), (b,) + p["latents"].shape)
    z = _cross(p["enc"], z, x, num_heads)  # encode: latents attend to inputs
    for bp in p["latent_blocks"]:
        z = vanilla_block(bp, z, num_heads)
    return _cross(p["dec"], x, z, num_heads)  # decode: inputs attend to latents


# ---------------------------------------------------------------------------
# Linformer-lite: learned [M, N] projections on K/V (fixed N)
# ---------------------------------------------------------------------------


def init_linformer_block(key, dim, num_heads, num_latents, *, param_dtype=jnp.float32,
                         max_tokens: int = 16384) -> dict:
    p = init_vanilla_block(key, dim, num_heads, param_dtype=param_dtype)
    kp = jax.random.fold_in(key, 7)
    p["proj_e"] = (jax.random.normal(kp, (max_tokens, num_latents), jnp.float32)
                   / math.sqrt(max_tokens)).astype(param_dtype)
    return p


def linformer_block(p: dict, x: jax.Array, num_heads: int) -> jax.Array:
    h = num_heads
    y = layernorm(p["ln1"], x)
    n = y.shape[1]
    e = p["proj_e"][:n].astype(y.dtype)  # [N, M] — the O(N*M) parameter cost
    q = _mh(dense(p["wq"], y), h)
    k = _mh(dense(p["wk"], y), h)
    v = _mh(dense(p["wv"], y), h)
    k = jnp.einsum("nm,bhnd->bhmd", e, k)
    v = jnp.einsum("nm,bhnd->bhmd", e, v)
    d = q.shape[-1]
    a = sdpa(q, k, v, scale=1.0 / math.sqrt(d))
    x = x + dense(p["wo"], _unmh(a))
    return x + gelu_mlp(p["mlp"], layernorm(p["ln2"], x))


# ---------------------------------------------------------------------------
# Transolver-lite (physics attention, w/o conv): soft slices shared across heads
# ---------------------------------------------------------------------------


def init_transolver_block(key, dim, num_heads, num_slices, *, param_dtype=jnp.float32) -> dict:
    p = init_vanilla_block(key, dim, num_heads, param_dtype=param_dtype)
    ks = jax.random.fold_in(key, 11)
    p["slice_proj"] = init_dense(ks, dim, num_slices, use_bias=True, param_dtype=param_dtype)
    return p


def transolver_block(p: dict, x: jax.Array, num_heads: int) -> jax.Array:
    h = num_heads
    y = layernorm(p["ln1"], x)
    # soft assignment of points to slices (shared across heads — the paper's
    # Fig. 6 footnote: Transolver uses the same projection weights per head)
    w = jax.nn.softmax(dense(p["slice_proj"], y).astype(jnp.float32), axis=-1)  # [B, N, S]
    wsum = jnp.maximum(w.sum(axis=1, keepdims=True), 1e-9)
    tokens = jnp.einsum("bns,bnc->bsc", (w / wsum).astype(y.dtype), y)  # slice tokens
    q, k, v = (_mh(dense(p[m], tokens), h) for m in ("wq", "wk", "wv"))
    d = q.shape[-1]
    a = sdpa(q, k, v, scale=1.0 / math.sqrt(d))  # latent self-attention over slices
    tokens = dense(p["wo"], _unmh(a))
    y = jnp.einsum("bns,bsc->bnc", w.astype(y.dtype), tokens)  # de-slice
    x = x + y
    return x + gelu_mlp(p["mlp"], layernorm(p["ln2"], x))

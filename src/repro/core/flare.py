"""FLARE: Fast Low-rank Attention Routing Engine — faithful reproduction.

The operator (paper §3.2, Fig. 1/3):

    Z_h = SDPA(Q_h, K_h, V_h, scale=1)   # encode:  [M,D] latents gather N tokens
    Y_h = SDPA(K_h, Q_h, Z_h, scale=1)   # decode:  latents scatter back to N

which induces the explicit rank-<=M input-space mixing operator

    Y_h = (softmax(K_h Q_h^T) @ softmax(Q_h K_h^T)) @ V_h = W_h V_h.

Layout convention is torch-style [B, H, N, D]; latent queries are learned
parameters of shape [H, M, D] (the paper's Q in R^{M x C} split along the
feature dim so each head owns a disjoint latent slice).

Implementations are mixer *backends* resolved through the plan-first policy
API in repro.core.policy (DESIGN.md §10/§13): ``policy`` may be a
:class:`~repro.core.policy.MixerPolicy` (backend preference order, grad
requirement, dtype, autotune opt-in), a pre-resolved
:class:`~repro.core.dispatch.MixerPlan` (the build-time product of
``resolve_policy`` — what model forwards receive), or ``None`` to use the
ambient policy stack (``with mixer_policy(...):``).

Softmax statistics are fp32 with max subtraction (beyond-paper stability fix;
mathematically identical — see DESIGN.md §9).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn.modules import (
    dense,
    init_dense,
    init_layernorm,
    init_resmlp,
    layernorm,
    resmlp,
    truncated_normal_init,
)

# ---------------------------------------------------------------------------
# SDPA (scaled dot-product attention) — the only mixing primitive FLARE uses.
# ---------------------------------------------------------------------------


def sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float = 1.0,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """softmax(q k^T * scale) v with fp32 softmax. q: [..., S, D], k/v: [..., T, D]."""
    scores = jnp.einsum("...sd,...td->...st", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...st,...td->...sd", w.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# The FLARE token-mixing operator (paper Fig. 3).
# ---------------------------------------------------------------------------


def flare_mixer(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    policy=None,
) -> jax.Array:
    """Multi-head FLARE token mixing.

    Args:
      q: [H, M, D] learned latent queries (head-wise independent slices).
      k: [B, H, N, D] keys from the deep ResMLP projection.
      v: [B, H, N, D] values from the deep ResMLP projection.
      policy: a MixerPolicy, a pre-resolved MixerPlan, or None to use the
        ambient policy stack (``with mixer_policy(...)``). Whether this call
        must be differentiable is the policy's ``requires_grad`` field.

    Returns:
      y: [B, H, N, D].
    """
    from repro.core.dispatch import MixerShape
    from repro.core.policy import resolve_policy, run_plan

    plan = resolve_policy(policy, MixerShape.from_qkv(q, k), k.dtype)
    return run_plan(plan, q, k, v)


def _flare_mixer_materialized(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Paper Fig. 7: explicitly materializes W_enc [M,N] and W_dec [N,M]."""
    scores = jnp.einsum("hmd,bhnd->bhmn", q, k).astype(jnp.float32)  # [B,H,M,N]
    w_enc = jax.nn.softmax(scores, axis=-1)  # rows over N
    w_dec = jax.nn.softmax(scores, axis=-2)  # rows over M (decode view: [n, m])
    z = jnp.einsum("bhmn,bhnd->bhmd", w_enc.astype(v.dtype), v)
    return jnp.einsum("bhmn,bhmd->bhnd", w_dec.astype(v.dtype), z)


def flare_dense_operator(q: jax.Array, k: jax.Array) -> jax.Array:
    """The induced dense communication matrix W_h = W_dec @ W_enc (Eq. 9).

    q: [H, M, D], k: [H, N, D] (single example) -> W: [H, N, N], rank <= M.
    For analysis/tests only — O(N^2) memory.
    """
    scores = jnp.einsum("hmd,hnd->hmn", q, k).astype(jnp.float32)
    w_enc = jax.nn.softmax(scores, axis=-1)  # [H, M, N]
    # w_dec is indexed [h, m, n] with softmax over m, i.e. its [n, m]
    # transpose is the decode matrix; the einsum below contracts m directly:
    # W[n, k] = sum_m W_dec[n, m] * W_enc[m, k].
    w_dec = jax.nn.softmax(scores, axis=-2)
    return jnp.einsum("hmn,hmk->hnk", w_dec, w_enc)


# ---------------------------------------------------------------------------
# FLARE layer: ResMLP K/V projections + mixer + output linear (paper App. B.2)
# ---------------------------------------------------------------------------


def init_flare_layer(
    key,
    dim: int,
    num_heads: int,
    num_latents: int,
    *,
    kv_proj_layers: int = 3,
    param_dtype=jnp.float32,
) -> dict:
    if dim % num_heads:
        raise ValueError(f"dim {dim} not divisible by heads {num_heads}")
    head_dim = dim // num_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        # Latent queries Q in R^{M x C}, stored pre-split per head: [H, M, D].
        "q_latent": truncated_normal_init(1.0 / math.sqrt(head_dim))(
            kq, (num_heads, num_latents, head_dim), param_dtype
        ),
        "k_proj": init_resmlp(kk, dim, dim, dim, kv_proj_layers, param_dtype=param_dtype),
        "v_proj": init_resmlp(kv, dim, dim, dim, kv_proj_layers, param_dtype=param_dtype),
        "out_proj": init_dense(ko, dim, dim, use_bias=True, param_dtype=param_dtype),
    }


def _split_heads(x: jax.Array, num_heads: int) -> jax.Array:
    b, n, c = x.shape
    return x.reshape(b, n, num_heads, c // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def flare_layer(params: dict, x: jax.Array, *, policy=None) -> jax.Array:
    """x: [B, N, C] -> [B, N, C]. ``policy``: MixerPolicy | MixerPlan | None
    (ambient stack)."""
    num_heads = params["q_latent"].shape[0]
    k = _split_heads(resmlp(params["k_proj"], x), num_heads)
    v = _split_heads(resmlp(params["v_proj"], x), num_heads)
    y = flare_mixer(params["q_latent"].astype(x.dtype), k, v, policy=policy)
    return dense(params["out_proj"], _merge_heads(y))


# ---------------------------------------------------------------------------
# FLARE block (paper Eq. 10): pre-norm mixer + pre-norm ResMLP.
# ---------------------------------------------------------------------------


def init_flare_block(
    key,
    dim: int,
    num_heads: int,
    num_latents: int,
    *,
    kv_proj_layers: int = 3,
    mlp_layers: int = 3,
    param_dtype=jnp.float32,
) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_layernorm(dim, param_dtype=param_dtype),
        "mixer": init_flare_layer(
            k1, dim, num_heads, num_latents,
            kv_proj_layers=kv_proj_layers, param_dtype=param_dtype,
        ),
        "ln2": init_layernorm(dim, param_dtype=param_dtype),
        "mlp": init_resmlp(k2, dim, dim, dim, mlp_layers, param_dtype=param_dtype),
    }


def flare_block(params: dict, x: jax.Array, *, policy=None) -> jax.Array:
    x = x + flare_layer(params["mixer"], layernorm(params["ln1"], x), policy=policy)
    x = x + resmlp(params["mlp"], layernorm(params["ln2"], x))
    return x

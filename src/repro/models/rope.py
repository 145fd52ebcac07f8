"""Rotary position embeddings: standard RoPE, YaRN-scaled RoPE (DeepSeek-V2's
``rope_scaling``) and Qwen2-VL M-RoPE."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the rotating half-dims: [head_dim // 2]."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1`` (1 when the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(head_dim: int, theta: float, yarn) -> Tuple[int, int]:
    """The rotary dims between which YaRN blends interpolated and original
    frequencies: dims that turn more than ``beta_fast`` times over the
    original context keep theirs, fewer than ``beta_slow`` are divided by
    the factor. (10, 23) for DeepSeek-V2's 64 rotary dims."""
    def dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))) / (2 * math.log(theta))

    low = math.floor(dim(yarn.beta_fast))
    high = math.ceil(dim(yarn.beta_slow))
    return max(low, 0), min(high, head_dim - 1)


def yarn_frequencies(head_dim: int, theta: float, yarn) -> jax.Array:
    """YaRN inverse frequencies [head_dim // 2]: ``freq / factor`` past the
    correction range, ``freq`` before it, a linear ramp between."""
    extra = rope_frequencies(head_dim, theta)
    inter = extra / yarn.factor
    low, high = yarn_correction_range(head_dim, theta, yarn)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                yarn=None) -> jax.Array:
    """positions [..., S] -> angles [..., S, head_dim//2] (fp32); YaRN
    frequencies when ``yarn`` (a ``YarnConfig``) is given."""
    inv = (rope_frequencies(head_dim, theta) if yarn is None
           else yarn_frequencies(head_dim, theta, yarn))
    return positions.astype(jnp.float32)[..., None] * inv


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate pairs (x[2i], x[2i+1]). x: [..., S, D], angles: [..., S, D//2].

    Uses the interleaved-pair convention; internally consistent across the
    whole repo (cache + query use the same convention).
    """
    x32 = x.astype(jnp.float32)
    x1 = x32[..., 0::2]
    x2 = x32[..., 1::2]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    # broadcast angles over head axis if x is [..., H, S, D] and angles [..., S, D//2]
    if x1.ndim == angles.ndim + 1:
        cos = cos[..., None, :, :]
        sin = sin[..., None, :, :]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def mrope_angles(
    positions: jax.Array,  # [3, ..., S] (temporal, height, width) position ids
    head_dim: int,
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the head_dim//2 frequency slots are split
    into three sections driven by (t, h, w) positions respectively.

    sections are in half-dim units and must sum to head_dim // 2.
    Returns angles [..., S, head_dim//2].
    """
    if sum(sections) != head_dim // 2:
        raise ValueError(f"M-RoPE sections {sections} must sum to {head_dim // 2}")
    inv = rope_frequencies(head_dim, theta)  # [D/2]
    ang = positions.astype(jnp.float32)[..., None] * inv  # [3, ..., S, D/2]
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang[i, ..., start : start + sec])
        start += sec
    return jnp.concatenate(parts, axis=-1)


def text_positions(batch: int, seq: int, *, offset: int = 0) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32) + offset, (batch, seq))


def text_mrope_positions(batch: int, seq: int, *, offset: int = 0) -> jax.Array:
    """For pure text, all three M-RoPE position streams coincide."""
    p = text_positions(batch, seq, offset=offset)
    return jnp.broadcast_to(p, (3, batch, seq))

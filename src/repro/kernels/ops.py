"""Jit'd dispatch wrappers around the Pallas kernels.

Responsibilities:
  - flatten [B, H, ...] -> [G, ...] group layout the kernels expect,
  - pad D to the 128-lane boundary (exact: zero columns do not change
    q.k scores, and padded output columns are sliced away),
  - pad the sequence dims UP to the tile boundary instead of shrinking
    tiles (the old ``while n % bn: bn //= 2`` collapsed to 1-wide tiles for
    odd/prime N — exactly the unstructured-mesh sizes the paper targets).
    Padding is exact: the attention kernel masks padded softmax columns
    (``kv_valid``) and padded output rows are sliced away; the causal
    kernel needs no mask because padded trailing tokens only influence
    positions after themselves (DESIGN.md §11),
  - choose interpret mode automatically off-TPU so tests/benchmarks run on
    CPU, while TPU gets the compiled kernels.

Tile sizes are parameters (threaded from the backend registry's plan, which
consults the autotune cache — repro.backends); the defaults here are only
the last-resort heuristic for direct calls.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.attention import flash_attention_pallas
from repro.kernels.flare_causal import flare_causal_chunk_pallas

LANE = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_lanes(x: jax.Array) -> jax.Array:
    d = x.shape[-1]
    pad = (-d) % LANE
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``multiple``."""
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flatten_groups(x: jax.Array) -> jax.Array:
    b, h, n, d = x.shape
    return x.reshape(b * h, n, d)


def flash_attention(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, H, Skv, D]
    v: jax.Array,  # [B, H, Skv, D]
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 256,
    block_kv: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = not _on_tpu()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    qg = _pad_to(_pad_lanes(_flatten_groups(q)), 1, bq)
    kg = _pad_to(_pad_lanes(_flatten_groups(k)), 1, bkv)
    vg = _pad_to(_pad_lanes(_flatten_groups(v)), 1, bkv)
    o = flash_attention_pallas(qg, kg, vg, scale=scale, causal=causal, window=window,
                               block_q=bq, block_kv=bkv, kv_valid=skv,
                               interpret=interpret)
    return o[:, :sq, :d].reshape(b, h, sq, d)


def flare_causal_fused(
    q: jax.Array,  # [H, M, D]
    k: jax.Array,  # [B, H, N, D]
    v: jax.Array,  # [B, H, N, D]
    *,
    tile: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused causal FLARE (the flare_lm training mixer) via the Pallas
    factored-chunk kernel; semantics == core.flare_stream.flare_causal."""
    if interpret is None:
        interpret = not _on_tpu()
    b, h, n, d = k.shape
    tile = min(tile, n)
    qh = _pad_lanes(q.astype(k.dtype))   # [H, M, Dp] — indexed per head in-kernel
    # causal => padded trailing tokens cannot leak into real positions
    kg = _pad_to(_pad_lanes(_flatten_groups(k)), 1, tile)
    vg = _pad_to(_pad_lanes(_flatten_groups(v)), 1, tile)
    y = flare_causal_chunk_pallas(qh, kg, vg, tile=tile, interpret=interpret)
    return y[:, :n, :d].reshape(b, h, n, d)

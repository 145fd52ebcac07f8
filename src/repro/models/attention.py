"""Attention family: GQA (RoPE / M-RoPE, bias, sliding window) and MLA.

Three execution modes per layer:
  - train:   full sequence, causal (or bidirectional for encoders)
  - prefill: like train but also returns the populated KV cache
  - decode:  single new token against a fixed-capacity cache

SDPA dispatch (``attn_sdpa``):
  - "xla":     materialized scores (fine for short S)
  - "chunked": lax.scan over query blocks with online softmax — the XLA
               expression of FlashAttention; O(S * block) live memory. Used
               automatically for long sequences and by the 32k prefill cells.
  - "pallas":  fused TPU kernel (repro.kernels); validated via interpret=True.

NB: this ``impl`` vocabulary is the *attention*-kernel knob and is distinct
from FLARE mixer dispatch — mixers resolve through repro.core.policy
(MixerPolicy -> MixerPlan, DESIGN.md §13) and are no longer threaded through
the same kwarg as the attention impl.

Sliding-window decode uses a ring-buffer cache of size ``window`` — this is
what keeps mixtral's long_500k cache bounded.

MLA follows DeepSeek-V2: compressed c_kv cache (kv_lora_rank + rope dims) and
the *absorbed* decode path (W_uk folded into the query, W_uv applied after
attention over latents), so decode reads only the compressed cache.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import AttnConfig
from repro.models.rope import apply_rope, mrope_angles, rope_angles, yarn_mscale
from repro.nn.modules import dense, init_dense, init_rmsnorm, rmsnorm

# ---------------------------------------------------------------------------
# SDPA dispatch
# ---------------------------------------------------------------------------


def _causal_window_bias(sq: int, skv: int, *, causal: bool, window: Optional[int],
                        q_offset: int = 0) -> Optional[jax.Array]:
    """Additive fp32 bias [sq, skv] built from iota comparisons (XLA fuses it)."""
    if not causal and window is None:
        return None
    qi = jax.lax.broadcasted_iota(jnp.int32, (sq, skv), 0) + q_offset
    ki = jax.lax.broadcasted_iota(jnp.int32, (sq, skv), 1)
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def attn_sdpa(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, H, Skv, D]
    v: jax.Array,  # [B, H, Skv, D]
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    impl: str = "auto",
    chunk: int = 512,
) -> jax.Array:
    sq, skv = q.shape[-2], k.shape[-2]
    if impl == "auto":
        impl = "chunked" if (sq > 2048 and skv > 2048) else "xla"
    if impl == "pallas":
        from repro.kernels.ops import flash_attention

        return flash_attention(q, k, v, scale=scale, causal=causal, window=window)
    if impl == "xla":
        scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
        bias = _causal_window_bias(sq, skv, causal=causal, window=window, q_offset=q_offset)
        if bias is not None:
            scores = scores + bias
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", w.astype(v.dtype), v)
    if impl == "chunked":
        return _chunked_attention(q, k, v, scale=scale, causal=causal, window=window,
                                  q_offset=q_offset, chunk=chunk)
    raise ValueError(f"unknown attention impl {impl!r}")


def _chunked_attention(q, k, v, *, scale, causal, window, q_offset, chunk):
    """Flash-style online-softmax over query blocks, expressed in XLA.

    Scans query blocks; each block computes scores against the full K/V but
    the [chunk, Skv] score tile is the only large intermediate alive. Each
    block is rematerialized in the backward pass, so it too holds one
    block's scores rather than all of them.
    """
    b, h, sq, d = q.shape
    skv = k.shape[-2]
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nblocks = q.shape[-2] // chunk
    qb = q.reshape(b, h, nblocks, chunk, d).transpose(2, 0, 1, 3, 4)
    kv_idx = jax.lax.broadcasted_iota(jnp.int32, (1, skv), 1)

    def body(_, args):
        blk_i, qblk = args
        scores = jnp.einsum("bhsd,bhtd->bhst", qblk, k).astype(jnp.float32) * scale
        q_idx = blk_i * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) + q_offset
        ok = jnp.ones((chunk, skv), bool)
        if causal:
            ok &= kv_idx <= q_idx
        if window is not None:
            ok &= kv_idx > q_idx - window
        scores = jnp.where(ok, scores, -jnp.inf)
        m = jnp.max(scores, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)  # rows that are fully masked
        e = jnp.exp(scores - m)
        num = jnp.einsum("bhst,bhtd->bhsd", e.astype(v.dtype), v)
        den = jnp.sum(e, axis=-1, keepdims=True).astype(v.dtype)
        return None, num / jnp.maximum(den, 1e-30)

    _, out = jax.lax.scan(jax.checkpoint(body), None, (jnp.arange(nblocks), qb))
    dv = v.shape[-1]  # may differ from the q/k head dim (e.g. MLA)
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, nblocks * chunk, dv)
    return out[:, :, :sq]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array      # [B, Hkv, S_cap, D] (ring buffer when windowed)
    v: jax.Array      # [B, Hkv, S_cap, D]
    length: jax.Array  # [B] int32 — tokens seen so far, per sequence slot


def init_kv_cache(batch: int, cfg: AttnConfig, capacity: int, dtype=jnp.bfloat16) -> KVCache:
    cap = capacity if cfg.sliding_window is None else min(capacity, cfg.sliding_window)
    return KVCache(
        k=jnp.zeros((batch, cfg.num_kv_heads, cap, cfg.head_dim), dtype),
        v=jnp.zeros((batch, cfg.num_kv_heads, cap, cfg.head_dim), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def _per_slot(length: jax.Array, batch: int) -> jax.Array:
    """Normalize a cache length/position leaf to per-slot [B] (legacy caches
    carried one scalar for the whole wave)."""
    if length.ndim == 0:
        return jnp.broadcast_to(length, (batch,))
    return length


def decode_valid_mask(new_len: jax.Array, cap: int) -> jax.Array:
    """[B] lengths -> [B, 1, 1, cap] bool: cache rows visible to this decode
    step (index < min(length, cap), per sequence slot).

    This mask is ALSO what makes block-paged decode reads exact: a paged
    pool (serve.pool, DESIGN.md §4) gathers a slot's pages into the dense
    layout with garbage in yet-unwritten/unmapped positions, all of which
    sit at indices >= length and are discarded here. gqa/mla decode and the
    paged gather-decode kernel's reference share this single definition."""
    return (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, cap), 3)
            < jnp.minimum(new_len, cap)[:, None, None, None])


def init_gqa(key, cfg: AttnConfig, d_model: int, *, param_dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d_model, cfg.q_dim, use_bias=cfg.qkv_bias, param_dtype=param_dtype),
        "wk": init_dense(kk, d_model, cfg.kv_dim, use_bias=cfg.qkv_bias, param_dtype=param_dtype),
        "wv": init_dense(kv, d_model, cfg.kv_dim, use_bias=cfg.qkv_bias, param_dtype=param_dtype),
        "wo": init_dense(ko, cfg.q_dim, d_model, use_bias=False, param_dtype=param_dtype),
    }


def _heads(x, n):  # [B, S, n*D] -> [B, n, S, D]
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)


def _unheads(x):  # [B, n, S, D] -> [B, S, n*D]
    b, n, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, n * d)


def _expand_kv(k: jax.Array, groups: int) -> jax.Array:
    """[B, Hkv, S, D] -> [B, Hkv*groups, S, D] by repeat (GQA group expand)."""
    if groups == 1:
        return k
    b, hkv, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, hkv, groups, s, d)).reshape(b, hkv * groups, s, d)


def gqa_forward(
    params: dict,
    x: jax.Array,  # [B, S, C]
    cfg: AttnConfig,
    *,
    positions: jax.Array,  # [B, S] or [3, B, S] for M-RoPE
    causal: bool = True,
    impl: str = "auto",
    return_kv: bool = False,
):
    """Train / prefill path."""
    q = _heads(dense(params["wq"], x), cfg.num_heads)
    k = _heads(dense(params["wk"], x), cfg.num_kv_heads)
    v = _heads(dense(params["wv"], x), cfg.num_kv_heads)
    if cfg.mrope_sections is not None:
        ang = mrope_angles(positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
    else:
        ang = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, ang)
    k = apply_rope(k, ang)
    groups = cfg.num_heads // cfg.num_kv_heads
    out = attn_sdpa(
        q, _expand_kv(k, groups), _expand_kv(v, groups),
        scale=1.0 / math.sqrt(cfg.head_dim), causal=causal,
        window=cfg.sliding_window, impl=impl,
    )
    y = dense(params["wo"], _unheads(out))
    if return_kv:
        return y, (k, v)
    return y


def gqa_cache_attend(
    q: jax.Array,  # [B, H, 1, D] rope'd query for the new token
    k: jax.Array,  # [B, Hkv, 1, D] rope'd key
    v: jax.Array,  # [B, Hkv, 1, D]
    cache: KVCache,
    *,
    groups: int,
    head_dim: int,
):
    """Append the new token's K/V to the cache and attend q over the valid
    prefix — the decode cache hot path, shared by :func:`gqa_decode` and
    zamba's shared-attention block.

    Two cache representations route here:
      - dense ``[B, Hkv, cap, D]`` leaves: per-slot ring write
        (vmapped dynamic_update_slice) + masked SDPA over the capacity;
      - ``PagedTokenView`` handles (serve pool, kernel mode): the row is
        appended straight into block storage and the read runs the Pallas
        gather-decode kernel over the mapped pages (G = groups), never
        materializing a dense gather.
    """
    from repro.serve.pool.views import PagedTokenView

    b = q.shape[0]
    length = _per_slot(cache.length, b)
    new_len = length + 1

    if isinstance(cache.k, PagedTokenView):
        from repro.kernels.paged_attention import paged_attention

        kview = cache.k.append(k[:, :, 0])   # [B, Hkv, D] row
        vview = cache.v.append(v[:, :, 0])
        k_pages, k_scale = kview.pages()
        v_pages, v_scale = vview.pages()
        hkv = k_pages.shape[1]
        qk = q[:, :, 0].reshape(b, hkv, groups, head_dim).astype(jnp.float32)
        out = paged_attention(
            qk, k_pages, v_pages, kview.pt, new_len,
            scale=1.0 / math.sqrt(head_dim),
            k_scale=k_scale, v_scale=v_scale, out_dtype=q.dtype)
        out = out.reshape(b, hkv * groups, head_dim)[:, :, None, :]
        return out, KVCache(kview, vview, new_len)

    cap = cache.k.shape[2]
    slot = jnp.mod(length, cap)  # [B] ring position (== length when unwindowed)
    # per-slot write positions (slots run at different lengths under
    # continuous batching): vmap the row update over the batch axis
    upd = jax.vmap(lambda c, x_, s_: jax.lax.dynamic_update_slice(c, x_, (0, s_, 0)))
    new_k = upd(cache.k, k.astype(cache.k.dtype), slot)
    new_v = upd(cache.v, v.astype(cache.v.dtype), slot)

    # f32 scores/weights/value-dot with a post-dot scale multiply — the same
    # formulation the paged gather-decode kernel computes, so the kernel and
    # dense routes stay token-exact under greedy decode (pinned by tests)
    kk = _expand_kv(new_k, groups).astype(jnp.float32)
    vv = _expand_kv(new_v, groups).astype(jnp.float32)
    scores = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), kk)
    scores = scores * (1.0 / math.sqrt(head_dim))
    scores = jnp.where(decode_valid_mask(new_len, cap), scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", w, vv).astype(q.dtype)
    return out, KVCache(new_k, new_v, new_len)


def gqa_decode(
    params: dict,
    x: jax.Array,  # [B, 1, C] the new token
    cfg: AttnConfig,
    cache: KVCache,
    *,
    positions: jax.Array,  # [B, 1] or [3, B, 1] — absolute position of the new token
):
    """Single-token decode against a (possibly ring-buffered) cache."""
    q = _heads(dense(params["wq"], x), cfg.num_heads)  # [B, H, 1, D]
    k = _heads(dense(params["wk"], x), cfg.num_kv_heads)
    v = _heads(dense(params["wv"], x), cfg.num_kv_heads)
    if cfg.mrope_sections is not None:
        ang = mrope_angles(positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
    else:
        ang = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, ang)
    k = apply_rope(k, ang)

    groups = cfg.num_heads // cfg.num_kv_heads
    out, new_cache = gqa_cache_attend(q, k, v, cache, groups=groups,
                                      head_dim=cfg.head_dim)
    y = dense(params["wo"], _unheads(out))
    return y, new_cache


def prefill_kv_cache(k: jax.Array, v: jax.Array, cfg: AttnConfig, capacity: int,
                     lengths: jax.Array | None = None) -> KVCache:
    """Pack prefill K/V [B, Hkv, S, D] into a fresh cache of `capacity`.

    ``lengths`` [B]: true (un-padded) prompt lengths when S is a right-padded
    bucket. Rows past a sequence's length are garbage but stay invisible —
    the decode validity mask and write slot are driven by ``length``.
    """
    b, hkv, s, d = k.shape
    cap = capacity if cfg.sliding_window is None else min(capacity, cfg.sliding_window)
    length = jnp.full((b,), s, jnp.int32) if lengths is None else lengths
    if s >= cap:
        if lengths is None:
            return KVCache(k[:, :, s - cap:].astype(jnp.bfloat16),
                           v[:, :, s - cap:].astype(jnp.bfloat16), length)
        # keep the last `cap` REAL tokens of each row (right-padded bucket)
        start = jnp.clip(length - cap, 0, s - cap)
        sl = jax.vmap(lambda c, s_: jax.lax.dynamic_slice(c, (0, s_, 0), (hkv, cap, d)))
        return KVCache(sl(k, start).astype(jnp.bfloat16),
                       sl(v, start).astype(jnp.bfloat16), length)
    pad = ((0, 0), (0, 0), (0, cap - s), (0, 0))
    return KVCache(jnp.pad(k, pad).astype(jnp.bfloat16),
                   jnp.pad(v, pad).astype(jnp.bfloat16), length)


def gqa_extend(
    params: dict,
    x: jax.Array,  # [B, S, C] suffix tokens (right-padded bucket)
    cfg: AttnConfig,
    cache: KVCache,
    *,
    positions: jax.Array,  # [B, S] or [3, B, S] — absolute suffix positions
    offsets: jax.Array,    # [B] int32 — tokens already in the cache (prefix)
    lengths: jax.Array,    # [B] int32 — true suffix lengths (<= S)
):
    """Width-S prefill continuation against an existing cache (the prefix-
    cache suffix path, DESIGN.md §4 "Prefix cache"): append the suffix's
    rope'd K/V rows at positions ``offsets + i`` and attend each suffix
    query causally over prefix + suffix. The score math deliberately
    mirrors :func:`attn_sdpa`'s xla path OP FOR OP (bf16 score einsum ->
    f32 cast -> scale -> -inf mask -> softmax -> bf16 value einsum): the
    prefix-cache acceptance bar is BIT-identical greedy tokens vs a cold
    full prefill, and that only holds when every reduction matches the
    prefill's dtype staging exactly (masked lanes contribute exact zeros,
    so the capacity-vs-bucket axis length difference is rounding-neutral).
    Rows past ``lengths`` are bucket padding — their cache writes are
    discarded by the engine's masked scatter and no real query attends to
    them (the causal mask ends at ``offsets + i``). Unwindowed caches only:
    a ring buffer's prefix rows are not positionally stable."""
    q = _heads(dense(params["wq"], x), cfg.num_heads)  # [B, H, S, D]
    k = _heads(dense(params["wk"], x), cfg.num_kv_heads)
    v = _heads(dense(params["wv"], x), cfg.num_kv_heads)
    if cfg.mrope_sections is not None:
        ang = mrope_angles(positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
    else:
        ang = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, ang)
    k = apply_rope(k, ang)

    s = x.shape[1]
    cap = cache.k.shape[2]
    upd = jax.vmap(lambda c, x_, s_: jax.lax.dynamic_update_slice(c, x_, (0, s_, 0)))
    new_k = upd(cache.k, k.astype(cache.k.dtype), offsets)
    new_v = upd(cache.v, v.astype(cache.v.dtype), offsets)

    groups = cfg.num_heads // cfg.num_kv_heads
    kk = _expand_kv(new_k, groups)
    vv = _expand_kv(new_v, groups)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, kk).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(cfg.head_dim))
    ti = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, cap), 3)
    qi = offsets[:, None, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, s, 1), 2)
    scores = jnp.where(ti <= qi, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", w.astype(vv.dtype), vv)
    y = dense(params["wo"], _unheads(out))
    return y, KVCache(new_k, new_v, offsets + lengths)


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    c_kv: jax.Array    # [B, S_cap, kv_lora_rank]  compressed latents
    k_rope: jax.Array  # [B, S_cap, qk_rope_head_dim]  shared rotary key
    length: jax.Array  # [B] int32, per sequence slot


def init_mla_cache(batch: int, cfg: AttnConfig, capacity: int, dtype=jnp.bfloat16) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def init_mla(key, cfg: AttnConfig, d_model: int, *, param_dtype=jnp.float32) -> dict:
    m = cfg.mla
    h = cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    keys = jax.random.split(key, 8)
    params = {
        "w_dkv": init_dense(keys[0], d_model, m.kv_lora_rank, param_dtype=param_dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, param_dtype=param_dtype),
        "w_kr": init_dense(keys[1], d_model, m.qk_rope_head_dim, param_dtype=param_dtype),
        "w_uk": init_dense(keys[2], m.kv_lora_rank, h * m.qk_nope_head_dim, param_dtype=param_dtype),
        "w_uv": init_dense(keys[3], m.kv_lora_rank, h * m.v_head_dim, param_dtype=param_dtype),
        "w_o": init_dense(keys[4], h * m.v_head_dim, d_model, param_dtype=param_dtype),
    }
    if m.q_lora_rank:
        params["w_dq"] = init_dense(keys[5], d_model, m.q_lora_rank, param_dtype=param_dtype)
        params["q_norm"] = init_rmsnorm(m.q_lora_rank, param_dtype=param_dtype)
        params["w_uq"] = init_dense(keys[6], m.q_lora_rank, h * qk_dim, param_dtype=param_dtype)
    else:
        params["w_q"] = init_dense(keys[7], d_model, h * qk_dim, param_dtype=param_dtype)
    return params


def _mla_rope(x, positions, cfg: AttnConfig):
    """Rotate MLA's rotary dims, with YaRN frequencies and cos/sin scale
    where the config has ``rope_scaling``."""
    yarn = cfg.rope_scaling
    out = apply_rope(x, rope_angles(positions, cfg.mla.qk_rope_head_dim,
                                    cfg.rope_theta, yarn))
    if yarn is not None:
        mscale = (yarn_mscale(yarn.factor, yarn.mscale)
                  / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if mscale != 1.0:
            out = (out.astype(jnp.float32) * mscale).astype(x.dtype)
    return out


def mla_softmax_scale(cfg: AttnConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times mscale(factor, mscale_all_dim)^2
    under YaRN (0.11472 for DeepSeek-V2-Lite)."""
    m, yarn = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def _mla_queries(params, x, cfg: AttnConfig, positions):
    m = cfg.mla
    h = cfg.num_heads
    if m.q_lora_rank:
        q = dense(params["w_uq"], rmsnorm(params["q_norm"], dense(params["w_dq"], x)))
    else:
        q = dense(params["w_q"], x)
    q = _heads(q, h)  # [B, H, S, qk_nope + qk_rope]
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    return q_nope, _mla_rope(q_rope, positions, cfg)


def mla_forward(
    params: dict,
    x: jax.Array,
    cfg: AttnConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    impl: str = "auto",
    return_kv: bool = False,
):
    """Train / prefill: materializes per-head K/V from the latent (cheap at
    train time; the compressed cache is what serving stores)."""
    m = cfg.mla
    h = cfg.num_heads
    q_nope, q_rope = _mla_queries(params, x, cfg, positions)
    c_kv = rmsnorm(params["kv_norm"], dense(params["w_dkv"], x))  # [B, S, r]
    k_rope = _mla_rope(dense(params["w_kr"], x), positions, cfg)  # [B, S, rope] one head
    k_nope = _heads(dense(params["w_uk"], c_kv), h)  # [B, H, S, nope]
    v = _heads(dense(params["w_uv"], c_kv), h)       # [B, H, S, v_dim]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, None], k_nope.shape[:3] + (m.qk_rope_head_dim,))], axis=-1)
    with jax.named_scope("mla.attention"):
        out = attn_sdpa(q, k, v, scale=mla_softmax_scale(cfg), causal=causal,
                        window=None, impl=impl)
    y = dense(params["w_o"], _unheads(out))
    if return_kv:
        return y, (c_kv, k_rope)
    return y


def mla_decode(
    params: dict,
    x: jax.Array,  # [B, 1, C]
    cfg: AttnConfig,
    cache: MLACache,
    *,
    positions: jax.Array,  # [B, 1]
):
    """Absorbed decode: attention runs directly in the compressed latent space.

      score_t = q_nope^T W_uk c_t + q_rope^T k_rope_t
      out     = W_o W_uv (sum_t w_t c_t)

    so the per-step reads are O(S * (r + rope_dim)) — the MLA serving win.
    """
    m = cfg.mla
    h = cfg.num_heads
    q_nope, q_rope = _mla_queries(params, x, cfg, positions)  # [B,H,1,*]
    # Fold W_uk into the query: q_abs [B, H, 1, r]
    w_uk = params["w_uk"]["kernel"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bhsd,rhd->bhsr", q_nope, w_uk)

    c_new = rmsnorm(params["kv_norm"], dense(params["w_dkv"], x))  # [B, 1, r]
    kr_new = _mla_rope(dense(params["w_kr"], x), positions, cfg)

    b = x.shape[0]
    length = _per_slot(cache.length, b)
    new_len = length + 1
    scale = mla_softmax_scale(cfg)

    from repro.serve.pool.views import PagedTokenView

    if isinstance(cache.c_kv, PagedTokenView):
        # Kernel route (serve pool): the compressed latents double as K AND
        # V of the gather-decode kernel (H = 1 page head, G = the mla
        # heads), with the rotary score q_rope·k_rope riding the kernel's
        # second score term over the shared softmax.
        from repro.kernels.paged_attention import paged_attention

        cview = cache.c_kv.append(c_new[:, 0])    # [B, r] row
        krview = cache.k_rope.append(kr_new[:, 0])
        c_pages, c_scale = cview.pages()
        kr_pages, kr_scale = krview.pages()
        qa = q_abs[:, :, 0][:, None].astype(jnp.float32)   # [B, 1, H, r]
        qr = q_rope[:, :, 0][:, None].astype(jnp.float32)  # [B, 1, H, rope]
        ctx = paged_attention(
            qa, c_pages, c_pages, cview.pt, new_len, scale=scale,
            k_scale=c_scale, v_scale=c_scale,
            q2=qr, k2_pages=kr_pages, k2_scale=kr_scale,
            out_dtype=x.dtype)
        ctx = ctx[:, 0][:, :, None, :]  # [B, H, 1, r] latent context
        new_cache = MLACache(cview, krview, new_len)
    else:
        cap = cache.c_kv.shape[1]
        slot = jnp.mod(length, cap)  # [B]
        upd = jax.vmap(lambda c, x_, s_: jax.lax.dynamic_update_slice(c, x_, (s_, 0)))
        c_all = upd(cache.c_kv, c_new.astype(cache.c_kv.dtype), slot)
        kr_all = upd(cache.k_rope, kr_new.astype(cache.k_rope.dtype), slot)

        # f32 formulation matching the kernel route (see gqa_cache_attend)
        c32 = c_all.astype(jnp.float32)
        s_nope = jnp.einsum("bhsr,btr->bhst", q_abs.astype(jnp.float32), c32)
        s_rope = jnp.einsum("bhsd,btd->bhst", q_rope.astype(jnp.float32),
                            kr_all.astype(jnp.float32))
        scores = (s_nope + s_rope) * scale
        scores = jnp.where(decode_valid_mask(new_len, cap), scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhst,btr->bhsr", w, c32).astype(x.dtype)  # latent context
        new_cache = MLACache(c_all, kr_all, new_len)
    # Absorb W_uv on the way out: v_h = W_uv_h c  =>  out_h = ctx_h @ W_uv_h
    w_uv = params["w_uv"]["kernel"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhsr,rhd->bhsd", ctx, w_uv)
    y = dense(params["w_o"], _unheads(out))
    return y, new_cache


def mla_extend(
    params: dict,
    x: jax.Array,  # [B, S, C] suffix tokens (right-padded bucket)
    cfg: AttnConfig,
    cache: MLACache,
    *,
    positions: jax.Array,  # [B, S]
    offsets: jax.Array,    # [B] int32 — tokens already in the cache
    lengths: jax.Array,    # [B] int32 — true suffix lengths (<= S)
):
    """Width-S prefill continuation over the compressed-latent cache (the
    prefix-cache suffix path). Deliberately NOT the absorbed decode form:
    it mirrors :func:`mla_forward` op for op — decompress the (stored +
    appended) latents to per-head K/V with W_uk/W_uv, then run the exact
    :func:`attn_sdpa` xla dtype staging (bf16 score einsum -> f32 cast ->
    scale -> -inf mask -> softmax -> bf16 value einsum). The absorbed form
    is mathematically equal but contracts in a different order, and the
    acceptance bar here is BIT-identical greedy tokens vs a cold full
    prefill. See :func:`gqa_extend` for the padding/masking contract."""
    m = cfg.mla
    h = cfg.num_heads
    q_nope, q_rope = _mla_queries(params, x, cfg, positions)  # [B,H,S,*]

    c_new = rmsnorm(params["kv_norm"], dense(params["w_dkv"], x))  # [B, S, r]
    kr_new = _mla_rope(dense(params["w_kr"], x), positions, cfg)

    s = x.shape[1]
    cap = cache.c_kv.shape[1]
    upd = jax.vmap(lambda c, x_, s_: jax.lax.dynamic_update_slice(c, x_, (s_, 0)))
    c_all = upd(cache.c_kv, c_new.astype(cache.c_kv.dtype), offsets)
    kr_all = upd(cache.k_rope, kr_new.astype(cache.k_rope.dtype), offsets)

    k_nope = _heads(dense(params["w_uk"], c_all), h)  # [B, H, T, nope]
    v = _heads(dense(params["w_uv"], c_all), h)       # [B, H, T, v_dim]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, None],
                                  k_nope.shape[:3] + (m.qk_rope_head_dim,))],
        axis=-1)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * mla_softmax_scale(cfg)
    ti = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, cap), 3)
    qi = offsets[:, None, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, s, 1), 2)
    scores = jnp.where(ti <= qi, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", w.astype(v.dtype), v)
    y = dense(params["w_o"], _unheads(out))
    return y, MLACache(c_all, kr_all, offsets + lengths)


def prefill_mla_cache(c_kv: jax.Array, k_rope: jax.Array, capacity: int,
                      lengths: jax.Array | None = None) -> MLACache:
    b, s, r = c_kv.shape
    length = jnp.full((b,), s, jnp.int32) if lengths is None else lengths
    if s >= capacity:
        if lengths is None:
            return MLACache(c_kv[:, s - capacity:].astype(jnp.bfloat16),
                            k_rope[:, s - capacity:].astype(jnp.bfloat16), length)
        start = jnp.clip(length - capacity, 0, s - capacity)
        sl = lambda d_: jax.vmap(
            lambda c, s_: jax.lax.dynamic_slice(c, (s_, 0), (capacity, d_)))
        return MLACache(sl(r)(c_kv, start).astype(jnp.bfloat16),
                        sl(k_rope.shape[-1])(k_rope, start).astype(jnp.bfloat16),
                        length)
    return MLACache(
        jnp.pad(c_kv, ((0, 0), (0, capacity - s), (0, 0))).astype(jnp.bfloat16),
        jnp.pad(k_rope, ((0, 0), (0, capacity - s), (0, 0))).astype(jnp.bfloat16),
        length,
    )

"""Pallas gather-decode attention over block-paged K/V storage
(DESIGN.md §4 "Paged pool").

The serve-side paged pool (`repro.serve.pool`) stores token-axis cache
leaves as ``[num_blocks(+trash), H, block, D]`` physical pages addressed
through a per-slot page table. At high slot counts, decode throughput is
HBM-bound on cache reads (FlashAttention's IO framing — PAPERS.md): a
dense pool streams ``slots x capacity`` rows per step whether or not they
hold tokens, while this kernel DMAs **only the pages a slot has mapped**
— the page table and lengths ride in scalar-prefetch memory
(``pltpu.PrefetchScalarGridSpec``) so each grid step's BlockSpec index_map
picks the physical page to fetch, vLLM-style.

Schedule: grid ``(B, P)`` with the page dimension innermost; each step
fetches one physical page for all heads and loops over the heads inside,
with running (max, den, acc) flash scratch per head across pages. Rows
past ``lengths[b]`` are masked (the same validity contract as ``models.attention
.decode_valid_mask``, so garbage in partially written or still-unmapped
pages — which the pool points at the trash sink — is invisible).

The query axis G generalizes the consumer:
  - G = 1:  gqa/mla single-token decode reads (per-head query; gqa folds
    its query groups into G, mla its heads — the serving hot path,
    models.attention routes here when the cache leaf is a kernel view),
  - G = M:  the FLARE **encode** — M latent queries attending over the
    token set is exactly this kernel, which is how the ``paged`` mixer
    backend (repro.backends.paged) runs the encode stage straight off
    block-paged storage.

Two optional extensions serve the quantized pool and MLA:
  - ``k_scale``/``v_scale`` [NB, H, block]: per-token-row dequant scales
    (serve.pool.quant). Dequant happens *inside* the kernel — scores are
    ``(q k_int^T) * k_scale[t]`` and the value reduction folds ``v_scale``
    into the probabilities, so int8/fp8 pages are never materialized wide.
  - ``q2``/``k2_pages``(/``k2_scale``): a second additive score term,
    ``s += q2 k2^T`` — the MLA absorbed decode (q_abs·c + q_rope·k_rope
    over the same softmax, value = the latents themselves).

Every block spans whole trailing dims — ``(block, D)`` of a page,
``(H, block)`` of its scales, ``(G, D)`` of the queries — so Mosaic's
(8, 128) tiling rule holds for any head count, head dim, page size and
payload dtype with no padding (tests/test_tpu_compile.py compiles it at
qwen2_1_5b widths). CPU runs it in interpret mode (the parity tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _paged_kernel(pt_ref, len_ref, *refs, block, pages, heads, scale, has_ks,
                  has_vs, has_q2, has_k2s):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    ks_ref = next(it) if has_ks else None
    vs_ref = next(it) if has_vs else None
    q2_ref = next(it) if has_q2 else None
    k2_ref = next(it) if has_q2 else None
    k2s_ref = next(it) if has_k2s else None
    o_ref, max_scr, den_scr, acc_scr = next(it), next(it), next(it), next(it)
    # dtype mismatch (f32 decode queries over bf16/int8 pages) also needs
    # the cast-to-f32 dot path; plain same-dtype calls keep the original ops
    fused = has_ks or has_vs or has_q2 or q_ref.dtype != k_ref.dtype

    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        max_scr[...] = jnp.full_like(max_scr, NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the grid step holds one physical page for every head; heads are a
    # static loop so each page row is a plain [block, D] tile
    for hh in range(heads):
        q = q_ref[0, hh]            # [G, D]
        k = k_ref[0, hh]            # [block, D]
        v = v_ref[0, hh]
        if fused:
            # dequant-on-read path: payloads may be int8/fp8 rows, so the
            # dot runs in f32 and per-row scales fold in AFTER the
            # contraction (s[g,t] = (q·k_int)[g,t] * scale[t])
            s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if has_ks:
                s = s * ks_ref[0, pl.ds(hh, 1), :]
            if has_q2:
                s2 = jax.lax.dot_general(
                    q2_ref[0, hh].astype(jnp.float32),
                    k2_ref[0, hh].astype(jnp.float32),
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                if has_k2s:
                    s2 = s2 * k2s_ref[0, pl.ds(hh, 1), :]
                s = s + s2
        else:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [G, block]
        if scale != 1.0:
            # post-dot in f32 — the same op order as the jnp decode paths
            # (scores * scale), which is what keeps the routes token-exact
            s = s * scale
        # rows at global index >= lengths[b] are unwritten/garbage (incl.
        # the whole trash sink a not-yet-mapped page points at)
        tok = pi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = tok < len_ref[b]
        s = jnp.where(ok, s, NEG_INF)

        m_prev = max_scr[hh]                                   # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        den_scr[hh] = den_scr[hh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if fused:
            if has_vs:
                p = p * vs_ref[0, pl.ds(hh, 1), :]
            pv = jax.lax.dot_general(p, v.astype(jnp.float32),
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        acc_scr[hh] = acc_scr[hh] * alpha + pv
        max_scr[hh] = m_new

    @pl.when(pi == pages - 1)
    def _finish():
        for hh in range(heads):
            den = jnp.maximum(den_scr[hh], 1e-30)
            o_ref[0, hh] = (acc_scr[hh] / den).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,          # [B, H, G, D]
    k_pages: jax.Array,    # [NB, H, block, D] physical pages (+ trash row)
    v_pages: jax.Array,    # [NB, H, block, D]
    page_table: jax.Array,  # [B, P] int32 physical ids (trash for unmapped)
    lengths: jax.Array,    # [B] int32 valid tokens per lane
    *,
    scale: float = 1.0,
    k_scale: Optional[jax.Array] = None,   # [NB, H, block] f32 row scales
    v_scale: Optional[jax.Array] = None,   # [NB, H, block]
    q2: Optional[jax.Array] = None,        # [B, H, G, D2] second score term
    k2_pages: Optional[jax.Array] = None,  # [NB, H, block, D2]
    k2_scale: Optional[jax.Array] = None,  # [NB, H, block]
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Softmax(scale * (q k^T [+ q2 k2^T]) over the mapped, valid tokens) @ v,
    reading K/V page-by-page through the page table, dequantizing rows
    in-register when scales are given. Lanes with length 0 return 0.
    ``interpret`` defaults to True off TPU (the CPU test path)."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    page_table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    bsz, h, g, d = q.shape
    block = k_pages.shape[2]
    pages = page_table.shape[1]
    # every block spans whole trailing dims — (block, D) for pages,
    # (H, block) for scales — which is what Mosaic's (8, 128) tiling rule
    # accepts for any head count, block size and payload dtype
    whole = lambda dd: pl.BlockSpec((1, h, g, dd), lambda b, p, pt, ln: (b, 0, 0, 0))
    page_spec = lambda dd: pl.BlockSpec(
        (1, h, block, dd), lambda b, p, pt, ln: (pt[b, p], 0, 0, 0))
    row_spec = pl.BlockSpec((1, h, block), lambda b, p, pt, ln: (pt[b, p], 0, 0))
    in_specs = [whole(d), page_spec(d), page_spec(d)]
    operands = [q, k_pages, v_pages]
    if k_scale is not None:
        in_specs.append(row_spec)
        operands.append(k_scale)
    if v_scale is not None:
        in_specs.append(row_spec)
        operands.append(v_scale)
    if q2 is not None:
        d2 = q2.shape[-1]
        in_specs += [whole(d2), page_spec(d2)]
        operands += [q2, k2_pages]
        if k2_scale is not None:
            in_specs.append(row_spec)
            operands.append(k2_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, pages),
        in_specs=in_specs,
        out_specs=whole(d),
        scratch_shapes=[
            _vmem((h, g, 1), jnp.float32),
            _vmem((h, g, 1), jnp.float32),
            _vmem((h, g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block=block, pages=pages,
                               heads=h, scale=float(scale),
                               has_ks=k_scale is not None,
                               has_vs=v_scale is not None,
                               has_q2=q2 is not None,
                               has_k2s=k2_scale is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, g, d),
                                       out_dtype or v_pages.dtype),
        interpret=interpret,
    )(page_table, lengths, *operands)


def _gather_rows(pages, page_table):
    """[NB, H, block, ...] + [B, P] -> [B, H, P*block, ...]."""
    x = jnp.moveaxis(pages[page_table], 2, 1)  # [B, H, P, block, ...]
    bsz, h, p, blk = x.shape[:4]
    return x.reshape((bsz, h, p * blk) + x.shape[4:])


def paged_attention_ref(q, k_pages, v_pages, page_table, lengths, *,
                        scale: float = 1.0, k_scale=None, v_scale=None,
                        q2=None, k2_pages=None, k2_scale=None,
                        out_dtype=None) -> jax.Array:
    """jnp oracle: gather the dense view, mask index >= length, soft-max.
    Mirrors what the serve-side views.gather_leaf + decode read compute."""
    fused = k_scale is not None or v_scale is not None or q2 is not None
    k = _gather_rows(k_pages, page_table)  # [B, H, T, D]
    v = _gather_rows(v_pages, page_table)
    bsz, h, t, d = k.shape
    if fused:
        s = jnp.einsum("bhgd,bhtd->bhgt", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if k_scale is not None:
            s = s * _gather_rows(k_scale, page_table)[:, :, None, :]
        if q2 is not None:
            s2 = jnp.einsum("bhgd,bhtd->bhgt", q2.astype(jnp.float32),
                            _gather_rows(k2_pages, page_table)
                            .astype(jnp.float32)) * scale
            if k2_scale is not None:
                s2 = s2 * _gather_rows(k2_scale, page_table)[:, :, None, :]
            s = s + s2
    else:
        s = jnp.einsum("bhgd,bhtd->bhgt", q, k).astype(jnp.float32) * scale
    tok = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, t), 3)
    s = jnp.where(tok < lengths[:, None, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # all-masked lanes -> 0 like the kernel
    if fused:
        if v_scale is not None:
            w = w * _gather_rows(v_scale, page_table)[:, :, None, :]
        o = jnp.einsum("bhgt,bhtd->bhgd", w, v.astype(jnp.float32))
        return o.astype(out_dtype or q.dtype)
    o = jnp.einsum("bhgt,bhtd->bhgd", w.astype(v.dtype), v)
    return o if out_dtype is None else o.astype(out_dtype)

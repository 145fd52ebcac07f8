"""Gather/scatter adapters between block storage and dense cache layout
(DESIGN.md §4 "Paged pool").

A paged leaf lives in **storage layout** ``[num_blocks+1, *mid, block, D]``
(the ``+1`` is the trash sink block; ``(*mid, D)`` = the leaf's shape minus
its slot and token axes, original order preserved) and its per-row scales
in ``[num_blocks+1, *mid, block]``. The in-page token axis sits second to
last, so one page of one head is a whole ``[block, D]`` tile: the layout
the TPU kernel can fetch for any head count (Mosaic tiles the last two
dims of a block). These pure functions move tensors between that layout
and the dense leaf layout the model decode steps consume:

  - :func:`gather_leaf`    page table -> dense leaf (dequant on read)
  - :func:`scatter_blocks` prefill insert: a request's bucket, block-split
                           and quantized, into its mapped physical pages
  - :func:`scatter_token`  decode write-back: the single column decode
                           wrote, re-quantized, into (page, offset)

:class:`PagedCacheView` packages (pool state, page table, write positions)
as a pytree that can stand in for the dense caches argument of
``model.decode_step``: the model resolves it via :func:`resolve_cache_view`
— gather on entry, a write-back closure on exit — so decode *reads route
through the view adapter* with no change to the decode math. Idle lanes'
writes land in the trash block (their page-table rows are all-trash), and
garbage gathered from unmapped pages is invisible behind the decode
validity masks (index < length).

When ``PoolSpec.kernel`` is set (the engine flips it after its decode
``MixerPolicy`` resolution picks the ``paged`` backend), resolution takes
the **kernel route** instead: paged leaf positions resolve to
:class:`PagedTokenView` handles — block storage in kernel page layout plus
the shared page table and precomputed (page, offset) — and the attention
decode paths append the new token's row directly and hand the pages to
``kernels.paged_attention``. No dense gather is ever materialized, and the
write-back is one batched scatter per leaf keyed off the shared (page,
offset) rather than per-leaf recomputation.

Everything here is jit-traced; the static leaf bookkeeping rides in the
hashable :class:`PoolSpec` aux data.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.serve.pool.quant import QuantSpec, dequantize, quantize


# ---------------------------------------------------------------------------
# Static leaf bookkeeping
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedLeaf:
    """Static facts about one token-axis leaf."""

    slot_axis: int
    token_axis: int
    view: int            # dense token extent the model expects (== capacity)
    dtype: str           # dense-leaf dtype name (dequant target)

    @property
    def lead(self) -> int:
        """Leaf axes preceding the slot axis (e.g. a stacked-layer L) —
        these become scan axes, so the kernel layout moves them in front
        of the physical-page axis."""
        return sum(1 for i in range(self.slot_axis) if i != self.token_axis)


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    """Hashable pytree-aux description of a paged pool: which leaf (in
    flatten order) is dense vs paged, plus block geometry and quant mode."""

    treedef: Any                       # jax treedef of the full cache pytree
    roles: Tuple[Tuple[str, int], ...]  # per leaf: ("dense", i) | ("paged", j)
    paged: Tuple[PagedLeaf, ...]       # per paged leaf j
    dense_slot_axes: Tuple[Optional[int], ...]  # per dense leaf i
    block: int
    max_pages: int
    quant: QuantSpec
    kernel: bool = False  # resolve to PagedTokenView handles (Pallas decode)


# ---------------------------------------------------------------------------
# Layout transforms
# ---------------------------------------------------------------------------


def _perm(ndim: int, sax: int, tax: int):
    rest = [i for i in range(ndim) if i not in (sax, tax)]
    return [sax, tax] + rest


def to_pool_layout(leaf: jax.Array, sax: int, tax: int) -> jax.Array:
    """[..., S@sax, ..., T@tax, ...] -> [S, T, *rest]."""
    return leaf.transpose(_perm(leaf.ndim, sax, tax))


def from_pool_layout(x: jax.Array, sax: int, tax: int) -> jax.Array:
    """Inverse of :func:`to_pool_layout`."""
    perm = _perm(x.ndim, sax, tax)
    inv = [0] * len(perm)
    for i, p in enumerate(perm):
        inv[p] = i
    return x.transpose(inv)


def _page_rows(x: jax.Array, page: jax.Array, off: jax.Array,
               feature: bool = True) -> tuple:
    """Index of the token rows at (physical page, in-page offset) in
    storage ``x``: ``[NB+1, *mid, block, D]`` when ``feature`` (payload),
    ``[NB+1, *mid, block]`` otherwise (scales). ``x.at[idx]`` addresses
    ``[*page.shape, *mid(, D)]`` — the row layout ``quantize`` produces."""
    mid = x.ndim - 2 - int(feature)
    return ((page,) + (slice(None),) * mid + (off,)
            + ((slice(None),) if feature else ()))


def _pad_tokens(x: jax.Array, to: int) -> jax.Array:
    pad = to - x.shape[1]
    if pad <= 0:
        return x[:, :to]
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Leaf ops
# ---------------------------------------------------------------------------


def gather_leaf(data: jax.Array, scale: Optional[jax.Array], pt: jax.Array,
                meta: PagedLeaf, spec: PoolSpec) -> jax.Array:
    """Reconstruct a dense leaf for all slots from block storage.

    pt: [S, P] physical block ids (trash for unmapped — gathered garbage is
    behind the decode validity mask).
    """
    raw = jnp.moveaxis(data[pt], -2, 2)              # [S, P, block, *rest]
    sc = (jnp.moveaxis(scale[pt], -1, 2)             # [S, P, block, *rest[:-1]]
          if scale is not None else None)
    x = dequantize(spec.quant, raw, sc, jnp.dtype(meta.dtype))
    s, p, blk = x.shape[:3]
    x = x.reshape((s, p * blk) + x.shape[3:])[:, :meta.view]
    return from_pool_layout(x, meta.slot_axis, meta.token_axis)


def scatter_blocks(data: jax.Array, scale: Optional[jax.Array],
                   part_leaf: jax.Array, block_ids: jax.Array,
                   meta: PagedLeaf, spec: PoolSpec):
    """Prefill insert: write ``part_leaf``'s first ``P*block`` tokens (the
    request's bucket) into physical pages ``block_ids`` [G, P]."""
    g, npages = block_ids.shape
    y = to_pool_layout(part_leaf, meta.slot_axis, meta.token_axis)  # [G, view, *rest]
    y = _pad_tokens(y, npages * spec.block)
    y = y.reshape((g, npages, spec.block) + y.shape[2:])
    q, sc = quantize(spec.quant, y)
    # in-page token axis (2) to its storage position
    data = data.at[block_ids].set(jnp.moveaxis(q, 2, -2).astype(data.dtype))
    if scale is not None:
        scale = scale.at[block_ids].set(jnp.moveaxis(sc, 2, -1))
    return data, scale


def scatter_rows(data: jax.Array, scale: Optional[jax.Array],
                 part_leaf: jax.Array, pt: jax.Array, offsets: jax.Array,
                 lengths: jax.Array, width: int,
                 meta: PagedLeaf, spec: PoolSpec):
    """Suffix-prefill insert (DESIGN.md §4 "Prefix cache"): ``part_leaf``
    is a FULL-CAPACITY cache leaf (the extend paths return the whole
    updated cache, decode convention); slice each lane's ``width`` suffix
    rows starting at ``offsets[g]`` and write rows ``[offsets[g],
    offsets[g] + lengths[g])`` into the (page, in-page offset) targets its
    page-table row ``pt`` [G, P] names. Unlike :func:`scatter_blocks`,
    ONLY true rows land — padded bucket rows are routed to the trash sink
    — so a suffix can begin mid-block (the copy-on-write target) while the
    lane's earlier pages stay shared, read-only prefix blocks."""
    y = to_pool_layout(part_leaf, meta.slot_axis, meta.token_axis)  # [G, T, *rest]
    y = jax.vmap(
        lambda yy, o: jax.lax.dynamic_slice_in_dim(yy, o, width, 0)
    )(y, offsets)                                                   # [G, S, *rest]
    g, s = y.shape[:2]
    q, sc = quantize(spec.quant, y)
    pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]   # [G, S]
    pidx = jnp.minimum(pos // spec.block, spec.max_pages - 1)
    page = jnp.take_along_axis(pt, pidx, axis=1)
    off = pos % spec.block
    trash = data.shape[0] - 1
    valid = jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None]
    page = jnp.where(valid, page, trash)
    data = data.at[_page_rows(data, page, off)].set(q.astype(data.dtype))
    if scale is not None:
        scale = scale.at[_page_rows(scale, page, off, False)].set(sc)
    return data, scale


def token_page_off(pt: jax.Array, write_pos: jax.Array, block: int):
    """(physical page, in-page offset) of each slot's write position. ONE
    page table is shared across every leaf and layer, so the decode
    write-back computes this pair once and every leaf's scatter keys off
    it (the "batched scatter" of DESIGN.md §4's fused decode step)."""
    page = jnp.take_along_axis(pt, (write_pos // block)[:, None], axis=1)[:, 0]
    off = write_pos % block
    return page, off


def scatter_token_at(data: jax.Array, scale: Optional[jax.Array],
                     new_leaf: jax.Array, page: jax.Array, off: jax.Array,
                     write_pos: jax.Array, meta: PagedLeaf, spec: PoolSpec):
    """Decode write-back: extract the column decode wrote (position
    ``write_pos[s]`` per slot) and store it at the shared (page, offset).
    Idle slots' page-table rows are all-trash, so their writes land in
    the sink."""
    y = to_pool_layout(new_leaf, meta.slot_axis, meta.token_axis)  # [S, view, *rest]
    s = y.shape[0]
    idx = write_pos.reshape((s, 1) + (1,) * (y.ndim - 2))
    col = jnp.take_along_axis(y, jnp.broadcast_to(idx, (s, 1) + y.shape[2:]),
                              axis=1)[:, 0]                       # [S, *rest]
    q, sc = quantize(spec.quant, col)
    data = data.at[_page_rows(data, page, off)].set(q.astype(data.dtype))
    if scale is not None:
        scale = scale.at[_page_rows(scale, page, off, False)].set(sc)
    return data, scale


def scatter_token(data: jax.Array, scale: Optional[jax.Array],
                  new_leaf: jax.Array, pt: jax.Array, write_pos: jax.Array,
                  meta: PagedLeaf, spec: PoolSpec):
    """Single-leaf convenience over :func:`scatter_token_at`."""
    page, off = token_page_off(pt, write_pos, spec.block)
    return scatter_token_at(data, scale, new_leaf, page, off, write_pos,
                            meta, spec)


# ---------------------------------------------------------------------------
# Kernel-route leaf handle
# ---------------------------------------------------------------------------


class PagedTokenView:
    """A paged cache leaf in **kernel page layout**, standing in for the
    dense leaf inside the model's cache pytree when ``PoolSpec.kernel``.

    Children: storage ``data`` ``[*lead, NB+1, *mid, block, D]`` (lead axes —
    e.g. a stacked-layer L — moved in front so ``lax.scan`` over layers
    slices them like any other cache leaf), optional per-row ``scale``,
    the shared page table ``pt`` [S, P] and the precomputed write target
    ``(page, off)`` [S] — all broadcast across lead so a scan iteration
    reconstructs a per-layer view. The attention decode paths call
    :meth:`append` for the new token's row (the batched write-back: the
    single shared (page, off) keys every leaf's scatter) and hand
    :meth:`pages` + ``pt`` to ``kernels.paged_attention``; no dense gather
    is ever materialized.
    """

    def __init__(self, data, scale, pt, page, off, meta: PagedLeaf,
                 block: int, quant: QuantSpec):
        self.data = data
        self.scale = scale
        self.pt = pt
        self.page = page
        self.off = off
        self.meta = meta
        self.block = block
        self.quant = quant

    @property
    def dtype(self):
        return jnp.dtype(self.meta.dtype)

    def append(self, col: jax.Array) -> "PagedTokenView":
        """Write the new token's row ``col`` [S, *tail] (quantized) at each
        slot's (page, offset); idle slots hit the trash sink."""
        q, sc = quantize(self.quant, col)
        data = self.data.at[_page_rows(self.data, self.page, self.off)].set(
            q.astype(self.data.dtype))
        scale = self.scale
        if scale is not None:
            scale = scale.at[_page_rows(scale, self.page, self.off, False)].set(sc)
        return PagedTokenView(data, scale, self.pt, self.page, self.off,
                              self.meta, self.block, self.quant)

    def pages(self):
        """(data, scale) shaped for the Pallas kernel: data [NB, H, block,
        D] and scale [NB, H, block] — a featureless leaf (e.g. mla latent
        rows, tail = (D,)) gets a singleton head axis."""
        data, scale = self.data, self.scale
        if data.ndim == 3:
            data = data[:, None]
            if scale is not None:
                scale = scale[:, None]
        return data, scale


def _token_view_flatten(v: PagedTokenView):
    return (v.data, v.scale, v.pt, v.page, v.off), (v.meta, v.block, v.quant)


def _token_view_unflatten(aux, children):
    return PagedTokenView(*children, *aux)


jax.tree_util.register_pytree_node(PagedTokenView, _token_view_flatten,
                                   _token_view_unflatten)


# ---------------------------------------------------------------------------
# The decode-step view adapter
# ---------------------------------------------------------------------------


class PagedCacheView:
    """Stands in for the dense caches pytree in ``model.decode_step``.

    children: pool state (dense leaves + block storage + scales), the
    device page table [S, P] and per-slot write positions [S]; aux: the
    static :class:`PoolSpec`. The engine builds one per decode step; the
    model's decode entry resolves it (``resolve_cache_view``) into a dense
    gather + a write-back closure and returns the written-back view, whose
    ``.pool`` the engine carries forward.
    """

    def __init__(self, pool: dict, pt: jax.Array, write_pos: jax.Array,
                 spec: PoolSpec):
        self.pool = pool
        self.pt = pt
        self.write_pos = write_pos
        self.spec = spec

    def gather(self):
        """Dense caches pytree reconstructed from the pool."""
        spec = self.spec
        leaves = []
        for role, j in spec.roles:
            if role == "dense":
                leaves.append(self.pool["dense"][j])
            else:
                leaves.append(gather_leaf(self.pool["data"][j],
                                          self.pool["scale"][j], self.pt,
                                          spec.paged[j], spec))
        return jax.tree.unflatten(spec.treedef, leaves)

    def writeback(self, new_caches) -> "PagedCacheView":
        """Fold the decode-updated dense caches back into the pool: dense
        leaves replaced wholesale (exactly the dense engine's behaviour),
        paged leaves receive only the single written token column — one
        batched scatter per leaf keyed off the shared (page, offset) pair,
        computed once for the whole pytree."""
        spec = self.spec
        new_leaves = jax.tree.leaves(new_caches)
        dense = list(self.pool["dense"])
        data = list(self.pool["data"])
        scale = list(self.pool["scale"])
        page, off = token_page_off(self.pt, self.write_pos, spec.block)
        for leaf, (role, j) in zip(new_leaves, spec.roles):
            if role == "dense":
                dense[j] = leaf
            else:
                data[j], scale[j] = scatter_token_at(
                    data[j], scale[j], leaf, page, off, self.write_pos,
                    spec.paged[j], spec)
        pool = {"dense": tuple(dense), "data": tuple(data), "scale": tuple(scale)}
        return PagedCacheView(pool, self.pt, self.write_pos, spec)

    # -- kernel route (PoolSpec.kernel) -----------------------------------

    def kernel_caches(self):
        """Caches pytree with paged leaf positions holding
        :class:`PagedTokenView` handles in kernel page layout — the
        attention decode paths read pages through the Pallas kernel and
        append the new row in place, so no dense gather happens."""
        spec = self.spec
        page, off = token_page_off(self.pt, self.write_pos, spec.block)
        leaves = []
        for role, j in spec.roles:
            if role == "dense":
                leaves.append(self.pool["dense"][j])
                continue
            meta = spec.paged[j]
            data = self.pool["data"][j]
            scale = self.pool["scale"][j]
            lead = meta.lead
            if lead:
                src = tuple(range(1, 1 + lead))
                dst = tuple(range(lead))
                data = jnp.moveaxis(data, src, dst)
                if scale is not None:
                    scale = jnp.moveaxis(scale, src, dst)
            lead_shape = data.shape[:lead]
            pt = jnp.broadcast_to(self.pt, lead_shape + self.pt.shape)
            pg = jnp.broadcast_to(page, lead_shape + page.shape)
            of = jnp.broadcast_to(off, lead_shape + off.shape)
            leaves.append(PagedTokenView(data, scale, pt, pg, of, meta,
                                         spec.block, spec.quant))
        return jax.tree.unflatten(spec.treedef, leaves)

    def kernel_writeback(self, new_caches) -> "PagedCacheView":
        """Fold kernel-route caches back: paged leaves already hold the
        appended storage (``PagedTokenView.append`` wrote the row), so
        they just move back to canonical storage layout;
        dense leaves are replaced wholesale."""
        spec = self.spec
        is_view = lambda x: isinstance(x, PagedTokenView)
        new_leaves = jax.tree.leaves(new_caches, is_leaf=is_view)
        dense = list(self.pool["dense"])
        data = list(self.pool["data"])
        scale = list(self.pool["scale"])
        for leaf, (role, j) in zip(new_leaves, spec.roles):
            if role == "dense":
                dense[j] = leaf
                continue
            meta = spec.paged[j]
            lead = meta.lead
            d, s = leaf.data, leaf.scale
            if lead:
                src = tuple(range(lead))
                dst = tuple(range(1, 1 + lead))
                d = jnp.moveaxis(d, src, dst)
                if s is not None:
                    s = jnp.moveaxis(s, src, dst)
            data[j], scale[j] = d, s
        pool = {"dense": tuple(dense), "data": tuple(data), "scale": tuple(scale)}
        return PagedCacheView(pool, self.pt, self.write_pos, spec)


def _view_flatten(v: PagedCacheView):
    return (v.pool, v.pt, v.write_pos), v.spec


def _view_unflatten(spec, children):
    pool, pt, write_pos = children
    return PagedCacheView(pool, pt, write_pos, spec)


jax.tree_util.register_pytree_node(PagedCacheView, _view_flatten, _view_unflatten)


def resolve_cache_view(caches):
    """The decode-step entry hook: a ``PagedCacheView`` resolves to (cache
    pytree, write-back closure); anything else passes through untouched.
    Model decode steps call this once at the top so paged and dense pools
    share one decode implementation (DESIGN.md §4). ``PoolSpec.kernel``
    picks the route: kernel handles (Pallas gather-decode, in-place
    append) vs the jnp dense-gather fallback."""
    if isinstance(caches, PagedCacheView):
        if caches.spec.kernel:
            return caches.kernel_caches(), caches.kernel_writeback
        return caches.gather(), caches.writeback
    return caches, lambda c: c

"""`PagedModelCache` — the paged counterpart of ``serve.cache.ModelSlotCache``
(DESIGN.md §4 "Paged pool").

Discovery, like the dense pool, is family-agnostic and allocation-free:

  - the **slot axis** of every leaf comes from comparing ``jax.eval_shape``
    of ``init_fn`` at batch 1 vs 2 (exactly ``serve.cache.slot_axes``);
  - the **token axis** comes from comparing capacity C vs 2C — the axis
    whose extent tracks capacity is the one worth paging. Leaves with no
    such axis (FLARE stream state, rwkv/ssm recurrences, position/length
    vectors, windowed ring buffers whose extent is window-bounded) stay in
    a **dense per-slot pool**: they are already O(1) in capacity, which is
    FLARE's serving pitch — its whole state is a "dense leaf" here.

Token-axis leaves are stored block-granular in ``[num_blocks+1, *mid,
block, D]`` storage (``views.py`` layouts; the ``+1`` is the trash sink) and
share ONE page table per slot across every leaf and layer (vLLM-style: a
logical token block maps to the same physical id in each leaf's storage).
Pool capacity is therefore sized in **tokens** (``pool_tokens``), not
slots; admission stakes pages through ``blocks.BlockAllocator`` and the
engine appends pages as decode crosses block boundaries.

With ``shards > 1`` (DESIGN.md §15 "Mesh-parallel execution") storage is
laid out as ``shards`` contiguous partitions of ``shard_blocks + 1`` rows
each — every partition carrying its OWN trash sink row — so sharding dim 0
over the mesh hands each device exactly its partition, local trash
included. Page tables store GLOBAL row ids (``shard * (shard_blocks+1) +
local``); the engine's shard_map'd decode body subtracts the shard's
offset to localize them, while host-side prefill / copy-on-write keep
addressing the one global array under plain jit. ``shards=1`` is
bit-identical to the historical layout (ids, trash row, storage shape all
unchanged).

``init`` runs under ``jax.jit`` so the dense token-leaf allocations inside
``init_fn`` are dead-code-eliminated — the pool never materializes a
slots x capacity cache.
"""
from __future__ import annotations

import math
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.serve.cache import _slot_axis
from repro.serve.pool.blocks import BlockAllocator
from repro.serve.pool.quant import get_quant
from repro.serve.pool.views import (PagedLeaf, PoolSpec, gather_leaf,
                                    scatter_blocks, scatter_rows)


def _axis_or_none(small, big) -> Optional[int]:
    try:
        return _slot_axis(small, big)
    except ValueError:  # ambiguous (several axes moved) — leave it dense
        return None


class PagedModelCache:
    """Block-granular, optionally quantized pool over any family's
    ``init_caches(batch, capacity)`` pytree."""

    def __init__(self, init_fn: Callable[[int, int], Any], capacity: int, *,
                 pool_tokens: int, block: int = 16, quant: str = "none",
                 shards: int = 1):
        if pool_tokens < block:
            raise ValueError(f"pool_tokens={pool_tokens} < block={block}")
        self.init_fn = init_fn
        self.capacity = capacity
        self.block = block
        self.num_blocks = pool_tokens // block
        if shards < 1 or self.num_blocks % shards:
            raise ValueError(
                f"pool of {self.num_blocks} blocks not divisible into "
                f"{shards} shards — pick pool_tokens so blocks % shards == 0")
        self.shards = shards
        self.shard_blocks = self.num_blocks // shards
        self.quant = get_quant(quant)
        self.max_pages = -(-capacity // block)

        at_c = jax.eval_shape(lambda: init_fn(2, capacity))
        leaves_c, treedef = jax.tree.flatten(at_c)
        leaves_b1 = jax.tree.leaves(jax.eval_shape(lambda: init_fn(1, capacity)))
        leaves_2c = jax.tree.leaves(jax.eval_shape(lambda: init_fn(2, 2 * capacity)))

        roles: List = []
        paged: List[PagedLeaf] = []
        dense_axes: List[Optional[int]] = []
        self._rest_shapes: List[tuple] = []
        self._dense_shapes: List[Any] = []
        for s1, sc, s2c in zip(leaves_b1, leaves_c, leaves_2c):
            sax = _axis_or_none(s1, sc)
            tax = _axis_or_none(sc, s2c)
            # page only what is capacity-extent on a distinct axis of a
            # per-slot leaf; everything else is the dense per-slot part
            if sax is None or tax is None or tax == sax or sc.shape[tax] != capacity:
                roles.append(("dense", len(dense_axes)))
                dense_axes.append(sax)
                self._dense_shapes.append(sc)
            else:
                rest = tuple(sc.shape[i] for i in range(sc.ndim)
                             if i not in (sax, tax))
                roles.append(("paged", len(paged)))
                paged.append(PagedLeaf(slot_axis=sax, token_axis=tax,
                                       view=capacity, dtype=jnp.dtype(sc.dtype).name))
                self._rest_shapes.append(rest)
        self.spec = PoolSpec(
            treedef=treedef, roles=tuple(roles), paged=tuple(paged),
            dense_slot_axes=tuple(dense_axes), block=block,
            max_pages=self.max_pages, quant=self.quant)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @property
    def trash(self) -> int:
        # the LAST storage row — always a valid write sink for global
        # (plain-jit) ops; per-shard code must use trash_row(shard) so idle
        # writes land in the executing shard's local partition
        return self.num_blocks + self.shards - 1

    def trash_row(self, shard: int) -> int:
        """Global row id of ``shard``'s trash sink."""
        return shard * (self.shard_blocks + 1) + self.shard_blocks

    def global_offset(self, shard: int) -> int:
        """Global row id of ``shard``'s first block (local id 0)."""
        return shard * (self.shard_blocks + 1)

    def allocator(self) -> BlockAllocator:
        """One PER-SHARD allocator (ids are shard-local; the engine keeps
        one per shard and offsets ids by :meth:`global_offset` before they
        enter a page table). ``shards=1``: the historical global allocator."""
        return BlockAllocator(self.shard_blocks, self.block)

    def _dense_leaves(self, slots: int):
        leaves = jax.tree.leaves(self.init_fn(slots, self.capacity))
        return tuple(leaf for leaf, (role, _) in zip(leaves, self.spec.roles)
                     if role == "dense")

    def init(self, slots: int) -> dict:
        dense = jax.jit(self._dense_leaves, static_argnums=0)(slots)
        data, scale = [], []
        rows = self.num_blocks + self.shards  # shards x (shard_blocks + 1)
        for meta, rest in zip(self.spec.paged, self._rest_shapes):
            sd = self.quant.storage_dtype(meta.dtype)
            mid = rest[:-1]
            data.append(jnp.zeros((rows,) + mid + (self.block, rest[-1]), sd))
            scale.append(jnp.ones((rows,) + mid + (self.block,), jnp.float32)
                         if self.quant.scaled else None)
        return {"dense": dense, "data": tuple(data), "scale": tuple(scale)}

    def pool_pspecs(self, axes) -> dict:
        """PartitionSpec prefix tree for slot-sharding a pool over mesh
        ``axes`` (flattened — every axis shards the slot/storage dim): block
        storage and scales shard dim 0 (each device gets its partition incl.
        trash row), dense leaves shard their slot axis, slot-independent
        dense leaves replicate. Shaped to be passed directly as a shard_map
        in/out spec for the pool dict."""
        from jax.sharding import PartitionSpec as P

        el = axes[0] if len(axes) == 1 else tuple(axes)
        dense = tuple(P() if ax is None else P(*((None,) * ax), el)
                      for ax in self.spec.dense_slot_axes)
        return {"dense": dense, "data": P(el), "scale": P(el)}

    # ------------------------------------------------------------------
    # jit-side ops the engine compiles
    # ------------------------------------------------------------------
    def _scatter_dense(self, dense: tuple, parts: tuple, slots: jax.Array) -> tuple:
        out = []
        for p, q, ax in zip(dense, parts, self.spec.dense_slot_axes):
            if ax is None:
                out.append(p)
            else:
                idx = (slice(None),) * ax + (slots,)
                out.append(p.at[idx].set(q.astype(p.dtype)))
        return tuple(out)

    def make_prefill_into(self, prefill_fn: Callable[..., Any]):
        """Paged insertion prefill: run the family prefill on the request
        bucket, scatter dense leaves into the slot lanes and block-split the
        token leaves into the mapped physical pages ``block_ids`` [G, P]."""

        def prefill_into(params, batch, pool, slots, block_ids):
            logits, part = prefill_fn(params, batch, self.capacity)
            part_leaves = jax.tree.leaves(part)
            dense_parts, data, scale = [], list(pool["data"]), list(pool["scale"])
            for leaf, (role, j) in zip(part_leaves, self.spec.roles):
                if role == "dense":
                    dense_parts.append(leaf)
                else:
                    data[j], scale[j] = scatter_blocks(
                        data[j], scale[j], leaf, block_ids,
                        self.spec.paged[j], self.spec)
            dense = self._scatter_dense(pool["dense"], tuple(dense_parts), slots)
            return logits, {"dense": dense, "data": tuple(data),
                            "scale": tuple(scale)}

        return prefill_into

    def make_prefill_suffix(self, suffix_fn: Callable[..., Any]):
        """Suffix insertion prefill for prefix-cache hits (DESIGN.md §4
        "Prefix cache"): reconstruct each lane's cache *context* from block
        storage (the shared prefix pages its page-table row ``pt`` [G, P]
        maps, valid for the first ``offsets`` tokens), run the model's
        width-S cache-extend prefill on the distinct suffix, then
        masked-scatter ONLY the suffix rows ``[offset, offset + len)`` back
        into the lane's pages. Shared prefix blocks are read, never
        written: the engine's page layout guarantees every write position
        >= offset lands in a private (or copy-on-write) page.

        Dense context leaves need no history for gqa/mla — their only
        slot-dependent dense leaves are length/position vectors, which the
        context rebuilds as ``offsets`` broadcast to the leaf's shape."""

        def prefill_suffix_into(params, batch, pool, slots, pt):
            offsets = batch["offsets"]
            g = offsets.shape[0]
            leaves = []
            for role, j in self.spec.roles:
                if role == "paged":
                    leaves.append(gather_leaf(pool["data"][j], pool["scale"][j],
                                              pt, self.spec.paged[j], self.spec))
                    continue
                ref = self._dense_shapes[j]
                ax = self.spec.dense_slot_axes[j]
                if ax is None:  # slot-independent leaf: pass through
                    leaves.append(pool["dense"][j])
                    continue
                shape = tuple(g if i == ax else d
                              for i, d in enumerate(ref.shape))
                off = offsets.astype(ref.dtype).reshape(
                    tuple(g if i == ax else 1 for i in range(len(shape))))
                leaves.append(jnp.broadcast_to(off, shape))
            ctx = jax.tree.unflatten(self.spec.treedef, leaves)
            logits, part = suffix_fn(params, batch, ctx)
            part_leaves = jax.tree.leaves(part)
            dense_parts, data, scale = [], list(pool["data"]), list(pool["scale"])
            for leaf, (role, j) in zip(part_leaves, self.spec.roles):
                if role == "dense":
                    dense_parts.append(leaf)
                else:
                    data[j], scale[j] = scatter_rows(
                        data[j], scale[j], leaf, pt, offsets, batch["lengths"],
                        batch["tokens"].shape[1], self.spec.paged[j], self.spec)
            dense = self._scatter_dense(pool["dense"], tuple(dense_parts), slots)
            return logits, {"dense": dense, "data": tuple(data),
                            "scale": tuple(scale)}

        return prefill_suffix_into

    def copy_block(self, pool: dict, src: jax.Array, dst: jax.Array) -> dict:
        """Device-side copy of one physical block across every paged leaf
        (payload + scales) — the copy-on-write fault: a write landing in a
        refcount>1 block first duplicates it into a private page."""
        data = tuple(d.at[dst].set(d[src]) for d in pool["data"])
        scale = tuple(s.at[dst].set(s[src]) if s is not None else None
                      for s in pool["scale"])
        return {"dense": pool["dense"], "data": data, "scale": scale}

    def reset(self, pool: dict, slots: jax.Array) -> dict:
        """Retirement: dense leaves back to their init values (the same
        fresh-part insertion the dense pool uses — FlareState.m_max must
        return to -inf). Block storage needs no wipe: freed pages are
        re-mapped before they are ever readable again (prefill insert /
        append precede any read, and unmapped gathers sit behind the decode
        validity masks)."""
        fresh = self._dense_leaves(int(slots.shape[0]))
        return {"dense": self._scatter_dense(pool["dense"], fresh, slots),
                "data": pool["data"], "scale": pool["scale"]}

    # ------------------------------------------------------------------
    # accounting (bench / describe)
    # ------------------------------------------------------------------
    def token_bytes_paged(self) -> float:
        """Stored bytes per pooled token (payload + per-row scales),
        summed over every paged leaf (= every layer's K/V or latent row)."""
        total = 0.0
        for meta, rest in zip(self.spec.paged, self._rest_shapes):
            n = math.prod(rest)
            total += n * self.quant.storage_dtype(meta.dtype).itemsize
            if self.quant.scaled:
                total += math.prod(rest[:-1]) * 4
        return total

    def token_bytes_dense(self) -> float:
        """Bytes per token a dense (un-paged, un-quantized) pool stores."""
        return float(sum(math.prod(rest) * jnp.dtype(meta.dtype).itemsize
                         for meta, rest in zip(self.spec.paged, self._rest_shapes)))

    def slot_bytes_dense_leaves(self) -> float:
        """Per-slot bytes of the dense (non-token) part — FLARE's O(M)
        stream state, recurrent states, lengths."""
        total = 0.0
        for shape, ax in zip(self._dense_shapes, self.spec.dense_slot_axes):
            if ax is None:
                continue
            total += (shape.size // shape.shape[ax]) * jnp.dtype(shape.dtype).itemsize
        return total

    def pool_bytes(self) -> float:
        """Bytes held by block storage (excluding the trash sink row)."""
        return self.num_blocks * self.block * self.token_bytes_paged()

    def describe(self) -> str:
        shard = (f"{self.shards} shards x {self.shard_blocks} blocks, "
                 if self.shards > 1 else "")
        return (f"paged-pool[{len(self.spec.paged)} paged + "
                f"{len(self.spec.dense_slot_axes)} dense leaves, "
                f"{self.num_blocks}x{self.block}-token blocks (+trash), {shard}"
                f"quant={self.quant.name}, "
                f"{self.pool_bytes() / 1e6:.2f} MB storage, "
                f"{self.token_bytes_paged():.0f} B/token vs "
                f"{self.token_bytes_dense():.0f} dense, "
                f"{self.slot_bytes_dense_leaves() / 1e6:.3f} MB/slot dense part]")

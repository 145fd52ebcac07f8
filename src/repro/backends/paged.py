"""``paged`` backend: the FLARE mixer with its encode stage executed by the
block-paged gather-decode Pallas kernel (repro.kernels.paged_attention).

FLARE's encode — M latent queries soft-attending over the N tokens — is
exactly the paged kernel's G=M case, so the same kernel that serves the
slot pool's gqa/mla decode reads also runs the FLARE mixer straight off
block storage. Registered here against the MixerPolicy capability API it
is addressable with zero call-site changes (``MixerPolicy(backends=
("paged",))``); dense call sites page their K/V on the fly (identity page
table), the serving pool hands the kernel its real page table.

Bidirectional/forward-only: the decode stage (softmax over M latents per
token) is a cheap dense einsum — the O(N) HBM traffic is all in encode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dispatch import (
    Capabilities,
    MixerBackend,
    MixerPlan,
    MixerShape,
    register,
)

DEFAULT_BLOCK = 16


def _plan(shape: MixerShape, mesh, dtype) -> MixerPlan:
    return MixerPlan("paged", {"block": min(DEFAULT_BLOCK, shape.tokens)})


def pack_pages(x, block: int):
    """[B, H, N, D] -> ([B*P, H, block, D] pages, [B, P] identity page table).
    The on-the-fly paging dense call sites use; the serving pool already
    holds this layout."""
    b, h, n, d = x.shape
    p = -(-n // block)
    pad = p * block - n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pages = jnp.moveaxis(x.reshape(b, h, p, block, d), 2, 1)  # [B, P, H, block, D]
    pages = pages.reshape(b * p, h, block, d)
    pt = jnp.arange(b * p, dtype=jnp.int32).reshape(b, p)
    return pages, pt


def _run(plan: MixerPlan, q, k, v):
    from repro.kernels.paged_attention import paged_attention
    from repro.obs import scope

    b, h, n, d = k.shape
    m = q.shape[1]
    block = plan.params.get("block", DEFAULT_BLOCK)
    kp, pt = pack_pages(k, block)
    vp, _ = pack_pages(v, block)
    lengths = jnp.full((b,), n, jnp.int32)
    qb = jnp.broadcast_to(q.astype(k.dtype)[None], (b, h, m, d))
    # named_scope: the kernel launch shows up under this label in XLA
    # profiles (trace-time metadata only — OB001-legal inside jit)
    with scope("kernels.paged_attention"):
        z = paged_attention(qb, kp, vp, pt, lengths, scale=1.0)  # [B,H,M,D]
    # decode: per-token softmax over the M latents (paper Fig. 3, 2nd SDPA)
    s = jnp.einsum("hmd,bhnd->bhmn", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    w = jax.nn.softmax(s, axis=2)
    return jnp.einsum("bhmn,bhmd->bhnd", w.astype(z.dtype), z).astype(v.dtype)


def _score(shape: MixerShape, device: str) -> float:
    # the win is reading block-paged serving state without densifying.
    # latents == 1 is the decode-read signature (a single query row per
    # head against a long token axis) that only the serving engine's plan
    # resolution produces — score far above every dense backend there so
    # "auto" routes paged decode through the kernel. Dense mixer call
    # sites always carry M > 1 latents and fall back to the old
    # named-only scores, so they never see this backend by accident.
    if shape.latents == 1:
        return 40.0
    return 1.0 if device == "tpu" else 0.5


register(MixerBackend(
    name="paged",
    caps=Capabilities(bidirectional=True, causal=False,
                      device_kinds=("cpu", "tpu"),
                      dtypes=("float32", "bfloat16"), grads=False),
    plan=_plan,
    run=_run,
    score=_score,
    doc="FLARE encode via the block-paged gather-decode kernel (serve pool)",
))


# ---------------------------------------------------------------------------
# paged_shard: the same kernel route for SLOT-SHARDED pools (DESIGN.md §15).
# The batch/slot axis shards over every mesh axis flattened; each shard runs
# the paged kernel on its local slots with zero cross-shard communication —
# the serve engine's fused decode step adds the one all-gather (token ids)
# itself. Registered so the engine's mesh-aware decode-plan resolution has a
# scored, policy-addressable name, exactly like "paged" on one device.
# ---------------------------------------------------------------------------


def _mesh_size(mesh) -> int:
    out = 1
    for a in mesh.axis_names:
        out *= int(mesh.shape[a])
    return out


def _plan_shard(shape: MixerShape, mesh, dtype) -> MixerPlan:
    if mesh is None:
        raise ValueError(
            "backend 'paged_shard' needs a mesh — slot-sharded pools pass "
            "theirs via ServeEngine(mesh=...)")
    ndev = _mesh_size(mesh)
    if shape.batch % ndev:
        raise ValueError(
            f"paged_shard: batch/slot count {shape.batch} not divisible by "
            f"mesh size {ndev}")
    from repro.backends.packed_shard import mesh_shape_tag

    return MixerPlan("paged_shard", {
        "block": min(DEFAULT_BLOCK, shape.tokens),
        "mesh": mesh, "shard_axes": tuple(mesh.axis_names),
        "mesh_shape": mesh_shape_tag(mesh),
    })


def _run_shard(plan: MixerPlan, q, k, v):
    from jax.sharding import PartitionSpec as P

    mesh = plan.params["mesh"]
    ax = plan.params["shard_axes"]
    axe = ax[0] if len(ax) == 1 else tuple(ax)
    inner = MixerPlan("paged", {"block": plan.params.get("block", DEFAULT_BLOCK)})
    fn = jax.shard_map(
        lambda q_, k_, v_: _run(inner, q_, k_, v_),
        mesh=mesh,
        in_specs=(P(), P(axe, None, None, None), P(axe, None, None, None)),
        out_specs=P(axe, None, None, None),
        check_vma=False,  # no replication rule exists for pallas_call
    )
    return fn(q, k, v)


register(MixerBackend(
    name="paged_shard",
    caps=Capabilities(bidirectional=True, causal=False, sharded=True,
                      device_kinds=("cpu", "tpu"),
                      dtypes=("float32", "bfloat16"), grads=False),
    plan=_plan_shard,
    run=_run_shard,
    score=_score,    # same decode-read signature scoring as "paged"
    doc="slot-sharded paged kernel route: batch over the mesh, no "
        "cross-shard traffic in the read itself",
))

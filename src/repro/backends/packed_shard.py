"""``packed_shard`` backend: the packed-head fused kernel under shard_map
(kernels/flare_packed_shard.py, DESIGN.md §15).

The mesh-parallel training fast path: tokens shard over the sequence axes
(``"data"``), whole heads over the latent axes (``"model"`` — heads are
independent, so the model axis is collective-free), and the custom VJP runs
under shard_map with the latent statistics/grads psum'd across the sequence
shards. Eligible only with a mesh (``Capabilities.sharded``), so "auto"
never routes a single-device call here; with a mesh it outranks the
jnp-based ``seqparallel`` form wherever the shape divides the mesh.

The plan consults the autotune cache with the PER-SHARD problem shape and a
mesh/shard-shape key component, so a ``packed_shard`` tile winner can never
collide with (or shadow) a single-device ``packed`` entry for the same
global shape.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax

from repro.backends import autotune
from repro.core.dispatch import (
    Capabilities,
    MixerBackend,
    MixerPlan,
    MixerShape,
    register,
)


def default_axes(mesh) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """Bare-mesh axis split: heads over ``"model"`` when the mesh has one,
    tokens over everything else."""
    names = tuple(mesh.axis_names)
    lat = ("model",) if "model" in names else ()
    seq = tuple(a for a in names if a not in lat)
    return seq, lat


def mesh_shape_tag(mesh) -> str:
    """Comma-free ``axis<size>`` string recorded in plan params (and hence
    ``MixerPlan.describe()`` / benchmark rows), e.g. ``data2xmodel2``."""
    return "x".join(f"{a}{int(mesh.shape[a])}" for a in mesh.axis_names)


def _axes_size(mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(int(mesh.shape[a]) for a in axes) if axes else 1


def _runner(shape: MixerShape, dtype, mesh, seq, lat):
    """Autotuner timing callable: times the full sharded call on the mesh
    (global shapes — the per-shard slice is what the kernel sees)."""

    def run_once(params: dict) -> float:
        import time

        from repro.kernels.flare_packed_shard import flare_mixer_packed_shard

        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (shape.heads, shape.latents, shape.head_dim), dtype)
        k = jax.random.normal(kk, (shape.batch, shape.heads, shape.tokens, shape.head_dim), dtype)
        v = jax.random.normal(kv, (shape.batch, shape.heads, shape.tokens, shape.head_dim), dtype)
        fn = jax.jit(lambda q_, k_, v_: flare_mixer_packed_shard(
            q_, k_, v_, mesh=mesh, seq_axes=seq, lat_axes=lat,
            pack=params["pack"], block_n=params["block_n"]))
        jax.block_until_ready(fn(q, k, v))  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn(q, k, v))
        return (time.perf_counter() - t0) / 3

    return run_once


def build_shard_plan(shape: MixerShape, mesh, seq_axes, lat_axes,
                     dtype) -> MixerPlan:
    """Validate the shape against the axis split and freeze a plan. Raises
    ValueError on indivisible shapes so auto-resolution (and
    ``dispatch.sharded_plan``) can fall back to another sharded form."""
    seq = tuple(seq_axes)
    lat = tuple(lat_axes)
    lat_size = _axes_size(mesh, lat)
    seq_size = _axes_size(mesh, seq)
    if shape.heads % lat_size:
        raise ValueError(
            f"packed_shard: H={shape.heads} not divisible by lat_axes "
            f"{lat} (size {lat_size})")
    if shape.tokens % seq_size:
        raise ValueError(
            f"packed_shard: N={shape.tokens} not divisible by seq_axes "
            f"{seq} (size {seq_size})")
    local = MixerShape(batch=shape.batch, heads=shape.heads // lat_size,
                       tokens=shape.tokens // seq_size,
                       latents=shape.latents, head_dim=shape.head_dim)
    mesh_key = tuple(int(mesh.shape[a]) for a in mesh.axis_names)
    params = autotune.best_params(
        local, dtype, jax.default_backend(), kind="packed",
        runner=_runner(shape, dtype, mesh, seq, lat), mesh=mesh_key)
    return MixerPlan("packed_shard", {
        "mesh": mesh, "seq_axes": seq, "lat_axes": lat,
        "block_n": params["block_n"], "pack": params["pack"],
        "mesh_shape": mesh_shape_tag(mesh),
    })


def _plan(shape: MixerShape, mesh, dtype) -> MixerPlan:
    if mesh is None:
        raise ValueError(
            "backend 'packed_shard' needs a mesh — pass one to "
            "resolve_policy() or build a plan with dispatch.sharded_plan(mesh, "
            "seq_axes, lat_axes, shape=...)")
    seq, lat = default_axes(mesh)
    return build_shard_plan(shape, mesh, seq, lat, dtype)


def _run(plan: MixerPlan, q, k, v):
    from repro.kernels.flare_packed_shard import flare_mixer_packed_shard

    return flare_mixer_packed_shard(
        q, k, v, mesh=plan.params["mesh"],
        seq_axes=plan.params["seq_axes"], lat_axes=plan.params["lat_axes"],
        pack=plan.params.get("pack"), block_n=plan.params.get("block_n", 256))


register(MixerBackend(
    name="packed_shard",
    caps=Capabilities(bidirectional=True, sharded=True,
                      device_kinds=("cpu", "tpu"),
                      dtypes=("float32", "bfloat16"), grads=True),
    plan=_plan,
    run=_run,
    # with a mesh on TPU this is the training fast path; on CPU the kernels
    # run in interpret mode, so the jnp-based seqparallel form (score 5.0)
    # keeps winning "auto"+mesh there
    score=lambda shape, device: (
        (40.0 if shape.head_dim < 128 else 20.0) if device == "tpu" else 2.0),
    doc="mesh-parallel packed kernel: tokens over data, heads over model, "
        "psum'd latent stats/grads",
))

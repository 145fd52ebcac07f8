"""Sharded backends: shard_map sequence-parallel (1D) and seq x latent (2D)
FLARE. Both require a mesh in the plan — "auto" selects them only when
resolution is given a mesh; launch code obtains a plan from
:func:`repro.core.dispatch.sharded_plan`.
"""
from __future__ import annotations

import jax

from repro.core.dispatch import (
    Capabilities,
    MixerBackend,
    MixerPlan,
    MixerShape,
    register,
)


def _plan_sp(shape: MixerShape, mesh, dtype) -> MixerPlan:
    if mesh is None:
        raise ValueError(
            "backend 'seqparallel' needs a mesh — pass one to resolve_policy() "
            "or build a plan with dispatch.sharded_plan(mesh, seq_axes)")
    # default: shard the token dim over every mesh axis
    return MixerPlan("seqparallel", {"mesh": mesh,
                                     "seq_axes": tuple(mesh.axis_names)})


def _plan_sp2d(shape: MixerShape, mesh, dtype) -> MixerPlan:
    # the seq/lat axis split is a modelling decision this backend cannot
    # guess from a bare mesh — require an explicit plan
    raise ValueError(
        "backend 'seqlat' needs explicit seq/lat axes — build a plan with "
        "repro.core.dispatch.sharded_plan(mesh, seq_axes, lat_axes=...)")


def _run_sp(plan: MixerPlan, q, k, v):
    from jax.sharding import PartitionSpec as P

    from repro.core.flare_sp import flare_mixer_seqparallel

    mesh, seq_axes = plan.params["mesh"], plan.params["seq_axes"]
    axis_name = seq_axes if isinstance(seq_axes, str) else tuple(seq_axes)
    fn = jax.shard_map(
        lambda q_, k_, v_: flare_mixer_seqparallel(q_, k_, v_, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), P(None, None, axis_name, None), P(None, None, axis_name, None)),
        out_specs=P(None, None, axis_name, None),
    )
    return fn(q, k, v)


def _run_sp2d(plan: MixerPlan, q, k, v):
    from jax.sharding import PartitionSpec as P

    from repro.core.flare_sp import flare_mixer_seqlat

    mesh = plan.params["mesh"]
    seq_axes, lat_axes = plan.params["seq_axes"], plan.params["lat_axes"]
    fn = jax.shard_map(
        lambda q_, k_, v_: flare_mixer_seqlat(q_, k_, v_, seq_axis=seq_axes,
                                              lat_axis=lat_axes),
        mesh=mesh,
        in_specs=(P(None, lat_axes, None),
                  P(None, None, seq_axes, None),
                  P(None, None, seq_axes, None)),
        out_specs=P(None, None, seq_axes, None),
    )
    return fn(q, k, v)


register(MixerBackend(
    name="seqparallel",
    caps=Capabilities(bidirectional=True, sharded=True),
    plan=_plan_sp,
    run=_run_sp,
    # preferred under "auto"+mesh: its plan needs no seq/lat split decision
    score=lambda shape, device: 5.0,
    doc="tokens sharded over mesh axes; O(M*C) collectives/layer (DESIGN.md §2)",
))

register(MixerBackend(
    name="seqlat",
    caps=Capabilities(bidirectional=True, sharded=True),
    plan=_plan_sp2d,
    run=_run_sp2d,
    doc="2D: tokens over seq axes, latent slices over lat axes",
))

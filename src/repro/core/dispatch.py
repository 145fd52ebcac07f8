"""Typed mixer-backend registry + capability dispatch (DESIGN.md §10).

Every FLARE mixer implementation — the two-SDPA reference, the materialized
Fig.-7 fallback, the fused Pallas kernels, the shard_map sequence-parallel
forms and the causal/streaming paths — registers a :class:`MixerBackend`
describing *what it can do* (causal vs bidirectional contract, device kinds,
dtype constraints, whether it needs a mesh) and *how to run* (a ``plan``
builder that freezes shape/tile decisions, and a ``run`` callable).

This module is the registry lookup under :mod:`repro.core.policy`: callers
select a mixer with a ``MixerPolicy`` or a ``MixerPlan`` there, and
:func:`resolve` turns one preference entry into a ``(backend, plan)`` pair:

    "auto"                      -> best eligible backend for this device
    "sdpa" | "packed" | ...     -> that registered backend, by name
    MixerPlan                   -> pre-resolved plan, run as-is

:func:`sharded_plan` builds the plan for a mesh. Resolution happens at
Python level (trace time), so the chosen backend and its tile plan are
compile-time constants.

Backends live in :mod:`repro.backends`; importing that package populates the
registry (lazily triggered here so ``repro.core`` stays import-light).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MixerShape:
    """Static problem shape the resolver/planner sees at trace time."""

    batch: int
    heads: int
    tokens: int     # N
    latents: int    # M
    head_dim: int   # D

    @staticmethod
    def from_qkv(q: jax.Array, k: jax.Array) -> "MixerShape":
        return MixerShape(batch=k.shape[0], heads=k.shape[1], tokens=k.shape[2],
                          latents=q.shape[-2], head_dim=k.shape[-1])


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend is allowed to be selected for."""

    causal: bool = False           # satisfies the causal LM-mixer contract
    bidirectional: bool = True     # satisfies the set-mixer contract
    sharded: bool = False          # needs a Mesh + axis names (shard_map)
    device_kinds: tuple = ("cpu", "gpu", "tpu")
    dtypes: Optional[tuple] = None  # dtype names; None = any floating dtype
    grads: bool = True             # jax.grad works through run (a forward-only
                                   # Pallas kernel without a VJP sets False)


@dataclasses.dataclass(frozen=True)
class MixerPlan:
    """A resolved execution plan: backend name + frozen launch parameters.

    ``params`` holds whatever the backend's ``run`` needs beyond q/k/v —
    tile sizes for Pallas, mesh/axis names for sharded backends, chunk sizes
    for the causal paths. Plans are plain trace-time Python values.
    """

    backend: str
    params: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def describe(self) -> str:
        keys = ("block_m", "block_n", "block", "pack", "tile", "chunk_size",
                "seq_axes", "lat_axes", "mode", "quant", "mesh_shape")
        shown = {k: self.params[k] for k in keys if k in self.params}
        # ';'/'+'-separated so the string stays comma-free inside the 3-field
        # ``name,us_per_call,derived`` benchmark CSV contract
        fmt = lambda v: "+".join(map(str, v)) if isinstance(v, (tuple, list)) else str(v)
        inner = ";".join(f"{k}={fmt(v)}" for k, v in shown.items())
        return f"{self.backend}({inner})" if inner else self.backend


@dataclasses.dataclass(frozen=True)
class MixerBackend:
    name: str
    caps: Capabilities
    plan: Callable[[MixerShape, Optional[Any], Any], MixerPlan]
    run: Callable[..., jax.Array]  # run(plan, q, k, v, **kw) -> y
    # score(shape, device_kind) -> float; highest eligible score wins "auto".
    score: Callable[[MixerShape, str], float] = lambda shape, device: 0.0
    doc: str = ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}
_LOADED = False


def register(backend: MixerBackend) -> MixerBackend:
    _REGISTRY[backend.name] = backend
    return backend


def _ensure_loaded() -> None:
    global _LOADED
    if not _LOADED:
        importlib.import_module("repro.backends")
        _LOADED = True


def get_backend(name: str) -> MixerBackend:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown mixer backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def backends(*, causal: Optional[bool] = None, sharded: Optional[bool] = None):
    """List registered backends, optionally filtered by capability."""
    _ensure_loaded()
    out = []
    for b in _REGISTRY.values():
        if causal is not None and (b.caps.causal if causal else b.caps.bidirectional) is False:
            continue
        if sharded is not None and b.caps.sharded is not sharded:
            continue
        out.append(b)
    return sorted(out, key=lambda b: b.name)


def device_kind() -> str:
    return jax.default_backend()


def _dtype_ok(caps: Capabilities, dtype) -> bool:
    if caps.dtypes is None:
        return True
    return jnp.dtype(dtype).name in caps.dtypes


def eligible(backend: MixerBackend, *, causal: bool, dtype, device: Optional[str] = None,
             mesh=None, grad: bool = False) -> bool:
    device = device or device_kind()
    caps = backend.caps
    if causal and not caps.causal:
        return False
    if not causal and not caps.bidirectional:
        return False
    if caps.sharded and mesh is None:
        return False
    if not caps.sharded and mesh is not None:
        return False
    if device not in caps.device_kinds:
        return False
    if grad and not caps.grads:
        return False
    return _dtype_ok(caps, dtype)


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


def _check_contract(backend: MixerBackend, causal: bool, grad: bool = False) -> None:
    """Explicitly-named backends/plans still must satisfy the correctness
    contract: a bidirectional mixer on the causal path would silently leak
    future tokens, so that is an error, never a fallback."""
    if causal and not backend.caps.causal:
        raise ValueError(
            f"backend {backend.name!r} is not causal — using it as an LM mixer "
            "would leak future tokens (registered causal backends: "
            f"{[b.name for b in backends(causal=True)]})")
    if not causal and not backend.caps.bidirectional:
        raise ValueError(
            f"backend {backend.name!r} only implements the causal contract and "
            "cannot serve the bidirectional (set-mixer) path")
    if grad and not backend.caps.grads:
        raise ValueError(
            f"backend {backend.name!r} is forward-only (no VJP) and cannot "
            "serve a differentiated path; grad-capable backends: "
            f"{[b.name for b in _REGISTRY.values() if b.caps.grads]}")


def resolve(name, *, shape: MixerShape, dtype, mesh=None, causal: bool = False,
            grad: bool = False):
    """Turn ``"auto"``, a registered backend name or a ``MixerPlan`` into a
    ``(MixerBackend, MixerPlan)`` pair.

    ``grad=True`` marks a differentiated call site (training): ``"auto"``
    only considers grad-capable backends, and naming a forward-only backend
    is a hard error rather than a trace-time autodiff failure."""
    _ensure_loaded()
    if isinstance(name, MixerPlan):
        backend = get_backend(name.backend)
        _check_contract(backend, causal, grad)
        return backend, name
    if not isinstance(name, str):
        raise TypeError(
            f"resolve takes a backend name or a MixerPlan, got {type(name)!r}; "
            "select a mixer with MixerPolicy(backends=(...)) or build a mesh "
            "plan with dispatch.sharded_plan(mesh, ...)")
    if name == "auto":
        dev = device_kind()
        cands = [b for b in _REGISTRY.values()
                 if eligible(b, causal=causal, dtype=dtype, device=dev, mesh=mesh,
                             grad=grad)]
        if not cands:
            raise ValueError(
                f"no eligible mixer backend (causal={causal}, device={dev}, "
                f"dtype={jnp.dtype(dtype).name}, mesh={mesh is not None}, "
                f"grad={grad})")
        # highest score first; a backend whose plan rejects this shape
        # (e.g. a sharded form the shape does not divide over this mesh)
        # drops out and the next-best eligible backend takes the call
        cands.sort(key=lambda b: b.score(shape, dev), reverse=True)
        errors = []
        for backend in cands:
            try:
                return backend, backend.plan(shape, mesh, dtype)
            except ValueError as e:
                errors.append(f"{backend.name}: {e}")
        raise ValueError(
            "auto: every eligible backend rejected the shape at plan time:\n  "
            + "\n  ".join(errors))
    backend = get_backend(name)
    _check_contract(backend, causal, grad)
    return backend, backend.plan(shape, mesh, dtype)


def sharded_plan(mesh, seq_axes: Sequence[str] | str,
                 lat_axes: Sequence[str] | str = "model", *,
                 shape: Optional[MixerShape] = None, dtype=None,
                 prefer: Sequence[str] = ()) -> MixerPlan:
    """Pick the sharded FLARE form for a mesh: 1D sequence-parallel when the
    token dim already covers the mesh (including the ``lat_axes``), else the
    2D seq x latent form so the latent axis keeps ``lat_axes`` busy.

    With a ``shape``, the fused ``packed_shard`` kernel is tried first —
    always when ``prefer`` names it, and by default on TPU (where the fused
    kernel is the fast path; off-TPU it runs in interpret mode, so the
    jnp-based forms keep the default). An indivisible shape falls back to
    the jnp forms unless ``packed_shard`` was explicitly preferred.

    This is the single place the sharded-form decision lives (previously
    inlined in launch/specs.py).
    """
    seq = (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes)
    lat = (lat_axes,) if isinstance(lat_axes, str) else tuple(lat_axes)
    named = tuple(prefer or ())
    want_packed = "packed_shard" in named
    covered = all(a in seq for a in lat)
    if shape is not None and (
            want_packed or (not named and not covered and device_kind() == "tpu")):
        from repro.backends.packed_shard import build_shard_plan

        lat_eff = () if covered else lat
        seq_eff = tuple(a for a in seq if a not in lat_eff)
        try:
            return build_shard_plan(shape, mesh, seq_eff, lat_eff,
                                    dtype if dtype is not None else jnp.float32)
        except ValueError:
            if want_packed:
                raise
    if covered:
        return MixerPlan("seqparallel", {"mesh": mesh, "seq_axes": seq_axes})
    return MixerPlan("seqlat", {"mesh": mesh, "seq_axes": seq_axes,
                                "lat_axes": lat_axes})


# ---------------------------------------------------------------------------
# CLI: `python -m repro.core.dispatch --list` — the CI policy-resolution smoke
# ---------------------------------------------------------------------------


def _probe_mesh():
    """A minimal (1, 1) host mesh for the eligibility columns — one device
    suffices: eligibility is a capability question, not a placement one."""
    try:
        from repro.distributed.sharding import make_mesh

        return make_mesh((1, 1), ("data", "model"))
    except Exception:  # noqa: BLE001 — no devices at all; column shows "?"
        return None


def _policy_matrix():
    """Every registered backend x the four canonical policies (bidirectional/
    causal x infer/train): eligible on this device, or why not. Plus the two
    mesh columns: eligible-now (no mesh) vs eligible-with-mesh — the strict
    symmetry in :func:`eligible` means exactly one of them can be "yes"."""
    from repro.core.policy import MixerPolicy, resolve_policy

    shape = MixerShape(batch=1, heads=4, tokens=1024, latents=16, head_dim=8)
    policies = {
        "bidi/infer": (MixerPolicy(), False),
        "bidi/train": (MixerPolicy(requires_grad=True), False),
        "causal/infer": (MixerPolicy(), True),
        "causal/train": (MixerPolicy(requires_grad=True), True),
    }
    probe = _probe_mesh()
    rows = []
    for b in backends():
        cells = {}
        for label, (pol, causal) in policies.items():
            try:
                plan = resolve_policy(pol.with_(backends=(b.name,)), shape,
                                      jnp.float32, causal=causal)
                ok = eligible(b, causal=causal, dtype=jnp.float32,
                              mesh=plan.params.get("mesh"),
                              grad=pol.requires_grad)
                cells[label] = "yes" if ok else "named-only"
            except ValueError as e:
                msg = str(e)
                cells[label] = ("no-grad" if "forward-only" in msg else
                                "no-causal" if "not causal" in msg else
                                "no-bidi" if "causal contract" in msg else "no")
        cells["now"] = "yes" if eligible(b, causal=False, dtype=jnp.float32,
                                         mesh=None) else "no"
        cells["with-mesh"] = "?" if probe is None else (
            "yes" if eligible(b, causal=False, dtype=jnp.float32, mesh=probe)
            else "no")
        rows.append((b, cells))
    return shape, policies, rows


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.dispatch",
        description="Dump the mixer-backend registry and policy eligibility.")
    ap.add_argument("--list", action="store_true",
                    help="list every registered backend x canonical-policy cell")
    args = ap.parse_args(argv)
    _ensure_loaded()
    shape, policies, rows = _policy_matrix()
    print(f"device={device_kind()}  probe shape: N={shape.tokens} M={shape.latents} "
          f"D={shape.head_dim} H={shape.heads}")
    cols = list(policies)
    header = (f"{'backend':<14} {'grads':<5} {'now':<4} {'with-mesh':<9} "
              + " ".join(f"{c:<13}" for c in cols))
    print(header)
    print("-" * len(header))
    for b, cells in rows:
        flag = "yes" if b.caps.grads else "no"
        print(f"{b.name:<14} {flag:<5} {cells['now']:<4} {cells['with-mesh']:<9} "
              + " ".join(f"{cells[c]:<13}" for c in cols)
              + (f"  # {b.doc}" if args.list else ""))
    # the smoke contract: at least one backend must serve each canonical policy
    for c in cols:
        if not any(cells[c] == "yes" for _, cells in rows):
            print(f"ERROR: no eligible backend for policy {c}")
            return 1
    # ...and a sharded backend must never be eligible WITHOUT a mesh (nor a
    # dense one WITH a mesh): the strict symmetry behind "scored by mesh
    # availability"
    for b, cells in rows:
        if cells["now"] == "yes" and cells["with-mesh"] == "yes":
            print(f"ERROR: backend {b.name} eligible both with and without "
                  "a mesh — mesh symmetry broken")
            return 1
    return 0


if __name__ == "__main__":
    # `python -m repro.core.dispatch` runs this file as __main__ — a second
    # module instance with its own (empty) registry. Delegate to the
    # canonical instance the backends registered against.
    from repro.core import dispatch as _canonical

    raise SystemExit(_canonical.main())

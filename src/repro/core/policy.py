"""Plan-first mixer dispatch: MixerPolicy -> (resolve once) -> MixerPlan.

FLARE's pitch is that the O(NM) mixing is "expressed purely in terms of
standard SDPA", so it composes with whatever fused kernel is best on the
current hardware. Which kernel that *is* — and whether it must be
differentiable, what dtype it should assume, how it shards — is a
**deployment decision**, not a property of the forward math. This module
makes that decision first-class data:

    MixerPolicy   what the caller wants: backend preference order, grad
                  requirement, dtype/precision, mesh axis hints, autotune
                  opt-in. Frozen, hashable, pytree-static — usable as a jit
                  static argument and as a dict key.

    resolve_policy(policy, shape, dtype) -> MixerPlan
                  runs ONCE at model build (models.api.get_model,
                  launch.specs.build_cell). Traced functions receive the
                  resolved MixerPlan and never consult the registry again;
                  per-step dispatch is ``run_plan`` — a dict lookup.

    mixer_policy(...)  a module-level policy *stack* (context manager), so
                  training loops can say ``with mixer_policy(
                  requires_grad=True):`` and every un-planned FLARE call in
                  scope resolves against grad-capable backends only.

A FLARE-mixer selector is exactly ``MixerPolicy | MixerPlan | None``
(None = the ambient stack); a mesh plan comes from
:func:`repro.core.dispatch.sharded_plan`. The policy carries
``requires_grad``, which is what stops a training step from silently
re-resolving onto a forward-only kernel mid-trace.

See DESIGN.md §13 for the policy/plan lifecycle.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import dispatch
from repro.core.dispatch import MixerPlan, MixerShape

__all__ = [
    "MixerPolicy",
    "current_policy",
    "mixer_policy",
    "resolve_policy",
    "run_plan",
    "ensure_plan",
    "policy_from",
]


def _axes_tuple(axes) -> Optional[Tuple[str, ...]]:
    if axes is None:
        return None
    return (axes,) if isinstance(axes, str) else tuple(axes)


@dataclasses.dataclass(frozen=True)
class MixerPolicy:
    """A declarative mixer-dispatch request. All fields are hashable Python
    scalars/tuples, so a policy can be a jit static argument, a dict key, or
    a pytree-static leaf (registered below).

    Fields:
      backends: preference order. Each entry is "auto" (capability-scored
        pick) or a registered backend name; resolution walks the tuple and
        returns the first entry that satisfies the contract (causal/grad/
        device/dtype), so ``("packed", "sdpa")`` means "the fused kernel
        where it is legal, the reference everywhere else".
      requires_grad: this policy feeds a differentiated call site; only
        grad-capable backends may resolve (naming a forward-only backend is
        a resolve-time error, never a trace-time autodiff failure).
      dtype: dtype-name override for resolution (None = the data's dtype).
      precision: matmul precision hint recorded in the plan params
        ("default" | "high" | "highest"); backends may consult it.
      seq_axes / lat_axes: mesh axis-name hints for the sharded backends;
        with a mesh at resolve time these pick the sp-vs-sp2d form via
        :func:`repro.core.dispatch.sharded_plan`.
      autotune: tri-state opt-in for timed tile search at resolve
        (None = follow the REPRO_AUTOTUNE env var).
      chunk_size: causal-path chunk override merged into causal plans.
    """

    backends: Tuple[str, ...] = ("auto",)
    requires_grad: bool = False
    dtype: Optional[str] = None
    precision: Optional[str] = None
    seq_axes: Optional[Tuple[str, ...]] = None
    lat_axes: Optional[Tuple[str, ...]] = None
    autotune: Optional[bool] = None
    chunk_size: Optional[int] = None

    def __post_init__(self):
        # normalize user-friendly spellings to the hashable canonical forms
        b = self.backends
        if isinstance(b, str):
            b = (b,)
        object.__setattr__(self, "backends", tuple(b))
        object.__setattr__(self, "seq_axes", _axes_tuple(self.seq_axes))
        object.__setattr__(self, "lat_axes", _axes_tuple(self.lat_axes))
        if self.dtype is not None:
            object.__setattr__(self, "dtype", jnp.dtype(self.dtype).name)

    def with_(self, **overrides) -> "MixerPolicy":
        """A copy with the given fields replaced (policies are immutable)."""
        return dataclasses.replace(self, **overrides)

    def describe(self) -> str:
        # show every non-default field — an explicit autotune=False (opt-out
        # overriding REPRO_AUTOTUNE=1) must read differently from unset
        defaults = _DEFAULT_POLICY
        shown = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
                 if getattr(self, f.name) != getattr(defaults, f.name)}
        inner = ";".join(f"{k}={v}" for k, v in shown.items())
        return f"MixerPolicy({inner})" if inner else "MixerPolicy(auto)"


# Registered as a *static* pytree node: a policy crossing a jit boundary is
# part of the trace signature (like a static_argnum), never a traced value.
try:
    jax.tree_util.register_static(MixerPolicy)
except AttributeError:  # pragma: no cover — older jax
    jax.tree_util.register_pytree_node(
        MixerPolicy, lambda p: ((), p), lambda aux, _: aux)

_DEFAULT_POLICY = MixerPolicy()


# ---------------------------------------------------------------------------
# The policy stack
# ---------------------------------------------------------------------------

_STACK: list = [_DEFAULT_POLICY]


def current_policy() -> MixerPolicy:
    """The innermost active policy (the default policy at depth 0)."""
    return _STACK[-1]


@contextlib.contextmanager
def mixer_policy(policy: Optional[MixerPolicy] = None, **overrides):
    """Push a policy for the dynamic extent of the ``with`` block.

    ``mixer_policy(requires_grad=True)`` layers field overrides onto the
    current policy; ``mixer_policy(pol)`` installs ``pol`` (plus overrides).
    Nesting composes: inner blocks override, outer state is restored on exit
    even if the body raises.

    Trace-time caveat: the ambient policy is consulted when a bare call is
    TRACED, and is invisible to jax's jit cache — entering a different
    policy around an already-traced jitted function is a cache hit that
    keeps the old plan. Set the policy before the first trace, or (the
    plan-first path this module exists for) resolve explicitly and pass the
    plan/policy as an argument: policies are jit-static, so they key the
    cache correctly when passed in.
    """
    base = current_policy() if policy is None else policy
    new = base.with_(**overrides) if overrides else base
    _STACK.append(new)
    try:
        yield new
    finally:
        _STACK.pop()


# ---------------------------------------------------------------------------
# The selector
# ---------------------------------------------------------------------------

PolicyLike = Union[MixerPolicy, MixerPlan, None]


def policy_from(value: PolicyLike) -> Union[MixerPolicy, MixerPlan]:
    """The selector as a MixerPolicy (None -> the ambient policy) or the
    pre-resolved MixerPlan it already is."""
    if value is None:
        return current_policy()
    if isinstance(value, (MixerPolicy, MixerPlan)):
        return value
    raise TypeError(
        f"mixer policy must be MixerPolicy | MixerPlan | None, got {value!r}; "
        "name backends with MixerPolicy(backends=(...)) and build a mesh plan "
        "with dispatch.sharded_plan(mesh, ...)")


# ---------------------------------------------------------------------------
# Resolution (build time) and execution (trace time)
# ---------------------------------------------------------------------------


def _set_params(plan: MixerPlan, extra: dict) -> MixerPlan:
    """Force the given (non-None) params into a plan copy."""
    add = {k: v for k, v in extra.items() if v is not None}
    return MixerPlan(plan.backend, {**plan.params, **add}) if add else plan


def resolve_policy(policy: PolicyLike, shape: MixerShape, dtype=None, *,
                   causal: bool = False, mesh=None,
                   requires_grad: Optional[bool] = None) -> MixerPlan:
    """Resolve a policy to a concrete execution plan. Runs once, at model
    build (or at trace time for the bare functional API) — never per step.

    ``requires_grad`` overrides the policy's own field (models.api uses this
    to force grad-capable resolution for the loss path regardless of how the
    caller spelled the policy).
    """
    value = policy_from(policy)
    if isinstance(value, MixerPlan):
        rg = bool(requires_grad) if requires_grad is not None \
            else current_policy().requires_grad
        backend, plan = dispatch.resolve(value, shape=shape, dtype=dtype or jnp.float32,
                                         causal=causal, grad=rg)
        return plan

    pol = value
    rg = pol.requires_grad if requires_grad is None else bool(requires_grad)
    dt = jnp.dtype(pol.dtype) if pol.dtype is not None else \
        (jnp.dtype(dtype) if dtype is not None else jnp.float32)

    with _autotune_override(pol.autotune):
        if mesh is not None and pol.seq_axes is not None:
            named = pol.backends if pol.backends != ("auto",) else ()
            plan = dispatch.sharded_plan(mesh, pol.seq_axes, pol.lat_axes or "model",
                                         shape=shape, dtype=dt, prefer=named)
            if pol.backends != ("auto",) and plan.backend not in pol.backends:
                # an explicitly named backend is a contract everywhere else
                # in this API — never silently override it with the axis pick
                raise ValueError(
                    f"policy names backends {pol.backends!r} but its seq/lat "
                    f"axis hints resolve to {plan.backend!r} on this mesh; "
                    "drop the explicit names (use 'auto') or the axis hints")
            backend = dispatch.get_backend(plan.backend)
            dispatch._check_contract(backend, causal, rg)
        else:
            plan = _resolve_preference(pol, shape, dt, causal=causal, mesh=mesh, grad=rg)
    if causal and pol.chunk_size is not None:
        plan = _set_params(plan, {"chunk_size": pol.chunk_size})
    if pol.precision is not None:
        plan = _set_params(plan, {"precision": pol.precision})
    return plan


def _resolve_preference(pol: MixerPolicy, shape: MixerShape, dtype, *,
                        causal: bool, mesh, grad: bool) -> MixerPlan:
    """Walk ``pol.backends`` in order; first entry that satisfies the
    contract wins. Single-entry policies keep the registry's exact error
    (contract violations on an explicitly named backend are hard errors)."""
    errors = []
    for name in pol.backends:
        try:
            _, plan = dispatch.resolve(name, shape=shape, dtype=dtype, mesh=mesh,
                                       causal=causal, grad=grad)
            return plan
        except ValueError as e:
            if len(pol.backends) == 1:
                raise
            errors.append(f"{name}: {e}")
    raise ValueError(
        f"no backend in preference order {pol.backends!r} satisfies "
        f"(causal={causal}, requires_grad={grad}, dtype={jnp.dtype(dtype).name}):\n  "
        + "\n  ".join(errors))


@contextlib.contextmanager
def _autotune_override(enabled: Optional[bool]):
    if enabled is None:
        yield
        return
    from repro.backends import autotune

    with autotune.forced(enabled):
        yield


def run_plan(plan: MixerPlan, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Execute a resolved plan. This is the only mixer call that belongs
    inside traced model code: one registry dict lookup, zero resolution."""
    return dispatch.get_backend(plan.backend).run(plan, q, k, v)


def ensure_plan(plan: Optional[MixerPlan], shape: MixerShape, dtype, *,
                causal: bool = False, requires_grad: Optional[bool] = None,
                chunk_size: Optional[int] = None) -> MixerPlan:
    """Guarantee a plan: pass a pre-resolved one through (re-checking the
    grad contract, which is a capability lookup, not a resolve), or — the
    bare-functional fallback — resolve the ambient policy once at trace
    time. Model forwards call this with the build-time plan from
    ``get_model``; only direct functional callers pay the fallback."""
    if plan is not None:
        rg = bool(requires_grad) if requires_grad is not None \
            else current_policy().requires_grad
        if rg and not dispatch.get_backend(plan.backend).caps.grads:
            raise ValueError(
                f"plan {plan.describe()} names a forward-only backend but this "
                "is a differentiated path (requires_grad=True)")
        return plan  # build-time plans already carry their chunk decision
    resolved = resolve_policy(None, shape, dtype, causal=causal,
                              requires_grad=requires_grad)
    if causal and current_policy().chunk_size is None:
        # the caller's (cfg-derived) chunk wins over the plan-builder default;
        # an explicit policy chunk_size was already forced by resolve_policy
        resolved = _set_params(resolved, {"chunk_size": chunk_size})
    return resolved

"""Zero-sync observability layer (DESIGN.md §16).

Three pieces, importable without jax (jax is only touched lazily by the
profiler shims):

  - :mod:`repro.obs.metrics` — process-wide metrics registry (counters,
    gauges, fixed-bucket histograms; thread-safe, near-zero-cost disabled).
  - :mod:`repro.obs.trace` — request-lifecycle span tracing with
    Chrome-trace-event (Perfetto-loadable) export.
  - :func:`annotate` / :func:`scope` — the two XLA-profile correlation
    shims, and :func:`phase`, which joins ``annotate`` to a tracer.
    ``annotate(name)`` is a HOST-side ``jax.profiler.TraceAnnotation``:
    wrap the dispatch of a compiled program (a prefill
    launch, the fused decode step, a train step) so the host row of a
    ``jax.profiler.trace`` capture carries the same names as the engine's
    span stream. ``scope(name)`` is ``jax.named_scope``: legal INSIDE
    traced code (it only tags jaxpr/HLO metadata, no runtime effect), so
    kernel launches and model phases show up named in XLA profiles.
    ``phase(tracer, name)`` opens a host phase on both host streams at
    once: ``annotate(name)`` on the profiler's host row and, when the
    tracer is enabled, a complete span of the same name stamped inside it.
    Both clocks are the wall clock (the profile's host events sit at its
    ``profile_start_time`` plus their offset), so the two streams line up.

The boundary rule (enforced by flarecheck OB001): clocks and registry
mutation live at host boundaries only — never inside a jitted function, a
Pallas kernel, or a decode hot scope. ``scope`` is the ONE obs construct
allowed inside traced code.
"""
from __future__ import annotations

import contextlib
import time

from repro.obs.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry,
    NULL_REGISTRY, REGISTRY, get_registry,
)
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER, PHASES, Span, TID_ENGINE, Tracer,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_REGISTRY", "REGISTRY", "get_registry",
    "NULL_TRACER", "PHASES", "Span", "TID_ENGINE", "Tracer",
    "annotate", "phase", "scope",
]


def annotate(name: str):
    """Host-side profiler annotation around the *dispatch* of device work:
    ``with annotate("serve/prefill"): logits, pool = prefill(...)``.
    A no-op context when jax (or its profiler) is unavailable; never to be
    used inside traced code (that is :func:`scope`)."""
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)
    except Exception:  # pragma: no cover — profiler is optional
        return contextlib.nullcontext()


def scope(name: str):
    """``jax.named_scope`` — names operations in jaxpr/HLO metadata so XLA
    profiles correlate with engine spans. Trace-time only (zero runtime
    cost), and therefore the one obs construct that is LEGAL inside jitted
    functions and kernels."""
    try:
        import jax

        return jax.named_scope(name)
    except Exception:  # pragma: no cover — host-only tooling contexts
        return contextlib.nullcontext()


@contextlib.contextmanager
def phase(tracer, name: str, *, cat: str = "train", args: dict | None = None):
    """One host phase on both host streams: ``annotate(name)`` and, when
    ``tracer`` is enabled, ``tracer.complete(name, ...)`` with the start
    and end stamped inside the annotation; ``args`` carries the identifier
    the phases of one step share (``{"step": n}``). Host code only, like
    :func:`annotate`; records through ``complete`` alone, so any object
    with ``enabled`` and ``complete`` serves as the tracer."""
    with annotate(name):
        t0 = time.time()
        try:
            yield
        finally:
            if tracer.enabled:
                tracer.complete(name, t0, time.time() - t0, cat=cat, args=args)

"""Launch-parameter autotuner for the Pallas backends (DESIGN.md §11/§12).

Fused-kernel throughput on TPU hinges on launch parameters (FlashAttention's
central lesson), but the best choice depends on the problem shape, dtype and
device generation — none of which a hardcoded default can know. This module:

  * proposes candidates for a :class:`~repro.core.dispatch.MixerShape` per
    parameter *kind* — ``"packed"`` searches the packed-head backends'
    head-pack factor alongside their N tile,
  * times them with a caller-supplied runner (so this module stays free of
    eager kernel imports; the pack heuristic is lazily imported), and
  * memoizes the winner in an on-disk JSON cache keyed by
    ``(kind, device, dtype, N, M, D, H, jax+jaxlib version)`` so serving and
    benchmarks never pay the search twice — and never hardcode launch
    parameters again. The runtime version is part of the key because a tile
    winner timed under one compiler is not evidence about another; legacy
    un-versioned entries are still read as a fallback hit.

Timing only runs when explicitly requested (``autotune=True`` or the
``REPRO_AUTOTUNE=1`` env var): the default lookup is cache-hit-or-heuristic,
which keeps trace-time resolution deterministic and test-friendly. The cache
location follows ``REPRO_AUTOTUNE_CACHE`` (default
``~/.cache/repro/autotune.json``).

Concurrency: multiple processes (a benchmark sweep, a serving fleet warming
up) may tune simultaneously. Writes re-read the file from disk, merge the
new entry into whatever other processes stored meanwhile, and publish via
temp-file + ``os.replace`` (atomic on POSIX) — so readers never observe a
partial file and earlier writers' entries survive any serialized
interleaving. Two *simultaneous* writers can still race read-merge-replace
and drop one entry; the cost is only a re-tune of that shape, never a
wrong result, so this stays lock-free. A corrupt cache — or a malformed
entry inside one — never fails a computation: readers fall back to the
shape heuristic.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Callable, Iterable, Optional

from repro.core.dispatch import MixerShape
from repro.obs.metrics import REGISTRY

_MEM_CACHE: dict = {}  # path -> {key: entry} mirror of the JSON file
_FORCE: list = []  # policy-scoped overrides of the REPRO_AUTOTUNE env var

# cache-effectiveness counters (DESIGN.md §16) on the process-wide registry:
# plan resolution is module-level (no engine/trainer to hand a registry in),
# and one process shares one on-disk cache anyway
_M_HITS = REGISTRY.counter(
    "autotune.cache_hits", "best_params lookups served from the JSON cache")
_M_MISSES = REGISTRY.counter(
    "autotune.cache_misses", "lookups that fell through to measure/heuristic")
_M_MEASURED = REGISTRY.counter(
    "autotune.measured", "candidate sweeps actually timed on device")


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "autotune.json"),
    )


def autotune_enabled() -> bool:
    if _FORCE:
        return _FORCE[-1]
    return os.environ.get("REPRO_AUTOTUNE", "0") not in ("", "0", "false")


@contextlib.contextmanager
def forced(enabled: bool):
    """Scoped override of the autotune opt-in — how ``MixerPolicy.autotune``
    reaches the plan builders without threading kwargs through the registry."""
    _FORCE.append(bool(enabled))
    try:
        yield
    finally:
        _FORCE.pop()


def runtime_version() -> str:
    """jax+jaxlib version tag baked into cache keys: tile winners timed under
    one runtime (compiler) are not evidence about another."""
    import jax

    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    except ImportError:  # pragma: no cover
        jl = "?"
    return f"jax{jax.__version__}+jaxlib{jl}"


def _base_key(shape: MixerShape, dtype, device: str, kind: str,
              mesh: Optional[tuple] = None) -> str:
    import jax.numpy as jnp

    base = (f"{kind}|{device}|{jnp.dtype(dtype).name}|N{shape.tokens}"
            f"|M{shape.latents}|D{shape.head_dim}|H{shape.heads}")
    if mesh:
        # shard-shape component: a tile winner for a per-shard slice is not
        # evidence about the single-device problem (or another mesh shape) —
        # sharded entries get their own key space, unsharded keys are
        # byte-identical to the historical format so old caches keep hitting
        base = f"{base}|mesh{'x'.join(str(int(s)) for s in mesh)}"
    return base


def cache_key(shape: MixerShape, dtype, device: str, kind: str,
              mesh: Optional[tuple] = None) -> str:
    """The (runtime-versioned) key new winners are stored under."""
    return f"{_base_key(shape, dtype, device, kind, mesh)}|{runtime_version()}"


def legacy_cache_key(shape: MixerShape, dtype, device: str, kind: str,
                     mesh: Optional[tuple] = None) -> str:
    """Pre-versioning key format — still read as a fallback hit so caches
    written by earlier releases keep paying off until re-tuned."""
    return _base_key(shape, dtype, device, kind, mesh)


def _read_disk(path: str) -> dict:
    """Uncached read straight from disk; {} for missing/corrupt/non-dict."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}


def _load(path: str) -> dict:
    if path in _MEM_CACHE:
        return _MEM_CACHE[path]
    data = _read_disk(path)
    _MEM_CACHE[path] = data
    return data


def _store(path: str, key: str, entry: dict) -> None:
    """Publish one entry. Re-reads the file first so entries written by
    concurrent processes survive, and replaces atomically so readers never
    observe a partial file."""
    merged = {**_MEM_CACHE.get(path, {}), **_read_disk(path), key: entry}
    _MEM_CACHE[path] = merged
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # cache is an optimization; never fail the computation


def _pow2s(lo: int, hi: int) -> list:
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


# ---------------------------------------------------------------------------
# Candidate proposal + heuristics, per parameter kind
# ---------------------------------------------------------------------------

# param names per kind — doubles as entry validation for cache hits
_KIND_PARAMS = {
    "packed": ("block_n", "pack"),
}


def packed_candidates(shape: MixerShape) -> list:
    """(block_n, pack) pairs for the packed-head fused backend: every lane-
    filling pack factor that does not exceed the head count, crossed with
    the N tiles its kernels can take with tokens on lanes: power-of-two
    multiples of 128 narrower than N, and the whole N up to 1024."""
    d = max(1, shape.head_dim)
    max_pack = max(1, min(128 // d, shape.heads))
    packs = sorted({p for p in (1, 2, 4, 8, 16, 32) if p <= max_pack} | {max_pack})
    n = max(1, shape.tokens)
    bns = [b for b in _pow2s(128, 1024) if b < n] + ([n] if n <= 1024 else [])
    return [{"block_n": bn, "pack": p} for p in packs for bn in bns]


def default_packed(shape: MixerShape) -> dict:
    from repro.kernels.flare_packed import heuristic_pack  # lazy: keeps import light

    return {"block_n": min(256, max(128, shape.tokens)),
            "pack": heuristic_pack(shape.heads, shape.latents, shape.head_dim)}


_CANDIDATES = {"packed": packed_candidates}
_DEFAULTS = {"packed": default_packed}


# ---------------------------------------------------------------------------
# Measurement + lookup
# ---------------------------------------------------------------------------


def measure_tiles(shape: MixerShape, dtype, device: str,
                  runner: Callable[[dict], float], *, kind: str,
                  candidates: Optional[Iterable[dict]] = None,
                  mesh: Optional[tuple] = None) -> dict:
    """Time each candidate with ``runner(params) -> seconds`` and cache the
    winner. Returns the winning param dict (also annotated with timings)."""
    cands = list(candidates) if candidates is not None else _CANDIDATES[kind](shape)
    _M_MEASURED.inc()
    timed = []
    for params in cands:
        try:
            dt = runner(params)
        except Exception:  # noqa: BLE001 — an illegal candidate just loses the race
            continue
        timed.append((dt, params))
    if not timed:
        return _DEFAULTS[kind](shape)
    timed.sort(key=lambda p: p[0])
    best_dt, best = timed[0]
    _store(cache_path(), cache_key(shape, dtype, device, kind, mesh), {
        **best, "us": best_dt * 1e6, "candidates": len(timed),
        "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    })
    return best


def best_params(shape: MixerShape, dtype, device: str, *, kind: str,
                runner: Optional[Callable[[dict], float]] = None,
                autotune: Optional[bool] = None,
                mesh: Optional[tuple] = None) -> dict:
    """Cache-hit -> cached winner; miss -> time candidates iff autotuning is
    enabled and a runner is available, else the shape heuristic. A malformed
    cache entry counts as a miss, never an error. Lookup tries the
    runtime-versioned key first, then the legacy un-versioned key (a stale-
    runtime winner beats re-deriving the heuristic, but new measurements are
    only ever stored versioned). ``mesh`` (a shard-count tuple) keys sharded
    backends' per-shard winners separately from single-device entries."""
    cached = _load(cache_path())
    for key in (cache_key(shape, dtype, device, kind, mesh),
                legacy_cache_key(shape, dtype, device, kind, mesh)):
        entry = cached.get(key)
        if entry is not None:
            try:
                out = {p: int(entry[p]) for p in _KIND_PARAMS[kind]}
                _M_HITS.inc()
                return out
            except (KeyError, TypeError, ValueError):
                pass  # corrupt/partial entry — fall through
    _M_MISSES.inc()
    if (autotune if autotune is not None else autotune_enabled()) and runner is not None:
        best = measure_tiles(shape, dtype, device, runner, kind=kind, mesh=mesh)
        return {p: best[p] for p in _KIND_PARAMS[kind]}
    return _DEFAULTS[kind](shape)


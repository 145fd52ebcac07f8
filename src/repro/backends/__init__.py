"""Mixer backends — importing this package populates the registry in
:mod:`repro.core.dispatch`.

Each module registers one or more :class:`~repro.core.dispatch.MixerBackend`
entries with capability metadata (causal/bidirectional contract, sharding
requirements, device kinds, dtype constraints), a ``plan`` builder and a
``run`` callable. New backends (a GPU kernel, ring-attention encode, ...)
plug in here — no call site changes needed.
"""
from repro.backends import (  # noqa: F401  (import for registration side effect)
    causal,
    materialized,
    packed,
    packed_shard,
    paged,
    sdpa,
    seqparallel,
)

__all__ = ["autotune", "causal", "materialized", "packed", "packed_shard",
           "paged", "sdpa", "seqparallel"]

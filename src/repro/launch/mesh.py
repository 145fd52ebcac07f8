"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state. Single pod: 16 x 16 = 256 chips, axes (data, model). Multi-pod:
2 x 16 x 16 = 512 chips, axes (pod, data, model) — "pod" composes with
"data" for batch/FSDP sharding; "model" stays innermost (contiguous ICI).
"""
from __future__ import annotations

import jax

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    if shape is None:
        d = max(1, n // 2)
        shape = (d, n // d)
    return make_mesh(shape, axes)

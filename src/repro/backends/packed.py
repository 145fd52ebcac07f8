"""Packed-head fused Pallas backend: single-launch encode->decode with the
many-head/small-D lane packing and a custom VJP (kernels/flare_packed.py,
DESIGN.md §12).

This is the TPU FLARE fast path for training and inference alike: it is
grad-capable, so ``MixerPolicy(requires_grad=True)`` resolves here on TPU
as the bare policy does. The plan consults the autotune cache's ``packed``
kind, which searches the head-pack factor alongside the N tile. Off-TPU the
kernels run in interpret mode — correct but slow, so "auto" only picks this
backend on TPU; tests select it explicitly.
"""
from __future__ import annotations

import jax

from repro.backends import autotune
from repro.core.dispatch import (
    Capabilities,
    MixerBackend,
    MixerPlan,
    MixerShape,
    register,
)


def _runner(shape: MixerShape, dtype):
    """Build the autotuner's timing callable for this problem shape."""

    def run_once(params: dict) -> float:
        import time

        from repro.kernels.flare_packed import flare_mixer_packed

        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (shape.heads, shape.latents, shape.head_dim), dtype)
        k = jax.random.normal(kk, (shape.batch, shape.heads, shape.tokens, shape.head_dim), dtype)
        v = jax.random.normal(kv, (shape.batch, shape.heads, shape.tokens, shape.head_dim), dtype)
        fn = jax.jit(lambda q_, k_, v_: flare_mixer_packed(
            q_, k_, v_, pack=params["pack"], block_n=params["block_n"]))
        jax.block_until_ready(fn(q, k, v))  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn(q, k, v))
        return (time.perf_counter() - t0) / 3

    return run_once


def _plan(shape: MixerShape, mesh, dtype) -> MixerPlan:
    params = autotune.best_params(shape, dtype, jax.default_backend(),
                                  kind="packed", runner=_runner(shape, dtype))
    return MixerPlan("packed", {"block_n": params["block_n"],
                                "pack": params["pack"]})


def _run(plan: MixerPlan, q, k, v):
    from repro.kernels.flare_packed import flare_mixer_packed
    from repro.obs import scope

    # named_scope: the packed-kernel launch carries this label in XLA
    # profiles (trace-time metadata only — OB001-legal inside jit)
    with scope("kernels.flare_packed"):
        return flare_mixer_packed(q, k, v,
                                  pack=plan.params.get("pack"),
                                  block_n=plan.params.get("block_n", 256))


register(MixerBackend(
    name="packed",
    caps=Capabilities(bidirectional=True, device_kinds=("cpu", "tpu"),
                      dtypes=("float32", "bfloat16"), grads=True),
    plan=_plan,
    run=_run,
    score=lambda shape, device: 30.0 if device == "tpu" else 1.5,
    doc="single-launch packed-head fused kernels with custom VJP",
))

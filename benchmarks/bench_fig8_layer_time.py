"""Figure 8 (CPU-scaled): single-layer execution time — vanilla
self-attention vs Transolver physics attention vs FLARE across N, plus a
per-mixer-backend sweep (sdpa vs the packed-head fused kernels) over the
paper's small-D and a large-D config, so the perf trajectory
(BENCH_<tag>.json) tracks every backend per commit.
"""
from __future__ import annotations

import functools

import jax

from benchmarks.common import emit, mixer_backend_info, time_fn
from repro.models import pde

KEY = jax.random.PRNGKey(3)
DIM, HEADS, LATENTS = 32, 4, 16
NS = (512, 1024, 2048, 4096)

# per-backend FLARE layer times: D=8 (paper's tiny-head regime, where the
# packed backend recovers lane utilization) and D=64
BACKEND_IMPLS = ("sdpa", "packed")
BACKEND_CONFIGS = {8: dict(dim=32, heads=4), 64: dict(dim=256, heads=4)}
BACKEND_N = 512


def _backend_rows():
    from repro.core.flare import flare_block, init_flare_block
    from repro.core.policy import MixerPolicy

    for d, c in BACKEND_CONFIGS.items():
        x = jax.random.normal(jax.random.fold_in(KEY, 100 + d),
                              (1, BACKEND_N, c["dim"]))
        p = init_flare_block(KEY, c["dim"], c["heads"], LATENTS)
        for name in BACKEND_IMPLS:
            pol = MixerPolicy(backends=(name,))
            fn = jax.jit(functools.partial(flare_block, policy=pol))
            us = time_fn(fn, p, x)
            emit(f"fig8/backend/{name}/D{d}/N{BACKEND_N}", us, "",
                 backend=mixer_backend_info(pol, b=1, h=c["heads"], n=BACKEND_N,
                                            m=LATENTS, d=d))


def run():
    out = {}
    for n in NS:
        x = jax.random.normal(jax.random.fold_in(KEY, n), (1, n, DIM))
        for mixer, init in (
            ("vanilla", lambda k: pde.init_vanilla_block(k, DIM, HEADS)),
            ("transolver", lambda k: pde.init_transolver_block(k, DIM, HEADS, LATENTS)),
        ):
            p = init(KEY)
            fn = {"vanilla": pde.vanilla_block, "transolver": pde.transolver_block}[mixer]
            us = time_fn(jax.jit(lambda pp, xx: fn(pp, xx, HEADS)), p, x)
            out[(mixer, n)] = us
            emit(f"fig8/{mixer}/N{n}", us, "")
        from repro.core.flare import flare_block, init_flare_block

        p = init_flare_block(KEY, DIM, HEADS, LATENTS)
        us = time_fn(jax.jit(lambda pp, xx: flare_block(pp, xx)), p, x)
        out[("flare", n)] = us
        emit(f"fig8/flare/N{n}", us, "",
             backend=mixer_backend_info(b=1, h=HEADS, n=n, m=LATENTS,
                                        d=DIM // HEADS))
    grow = lambda m: out[(m, NS[-1])] / out[(m, NS[0])]
    emit("fig8/growth_ratio", 0.0,
         f"flare={grow('flare'):.1f}x;vanilla={grow('vanilla'):.1f}x;"
         f"transolver={grow('transolver'):.1f}x;N_ratio={NS[-1] // NS[0]}x")
    _backend_rows()
    return out


if __name__ == "__main__":
    run()

"""Mesh-parallel benchmark (DESIGN.md §15): the shard_map'd training kernel
and the slot-sharded serve pool against their single-device twins.

Rows (tracked perf trajectory):

    train/packed_shard          fwd+bwd step of the shard_map'd packed
                                kernel vs the single-device packed kernel;
                                derived carries the mesh shape, the problem
                                shape and both timings.
    serve/<arch>/sharded_tok_s  slot-sharded paged pool (mesh=...) vs the
                                single-device paged pool on the SAME seeded
                                workload; derived carries mesh shape, shard
                                count, both tok/s, host syncs per decode
                                step (0 on the fused path) and whether the
                                greedy outputs matched bit-for-bit.

The measurement runs in this process on ``jax.devices()`` and fails when
there are fewer than four: a chip belongs to the one process that holds it,
so no child process is started. CPU mesh coverage lives in
tests/test_mesh_parallel.py (virtual devices, parity only, no timings).
"""
from __future__ import annotations

import os

MESH_DEVICES = 4
MESH_SHAPE = (2, 2)


def _measure() -> dict:
    import time

    import jax
    import numpy as np
    import jax.numpy as jnp

    from repro.distributed.sharding import make_mesh

    if jax.device_count() < MESH_DEVICES:
        raise RuntimeError(
            f"bench_mesh needs {MESH_DEVICES} devices, found "
            f"{jax.device_count()} ({jax.devices()[0].platform})")
    smoke = os.environ.get("REPRO_BENCH_SERVE_SMOKE") == "1"
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))
    out: dict = {}

    # -- train/packed_shard ------------------------------------------------
    from repro.core.dispatch import MixerShape, resolve
    from repro.kernels.flare_packed import flare_mixer_packed

    B, H, N, M, D = 2, 4, (256 if smoke else 512), 16, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(H, M, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, N, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, N, D)), jnp.float32)
    shape = MixerShape.from_qkv(q, k)
    backend, plan = resolve("packed_shard", shape=shape, dtype=k.dtype,
                            mesh=mesh)

    def timed(f):
        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v)),
                             argnums=(0, 1, 2)))
        jax.block_until_ready(g(q, k, v))  # compile
        ts = []
        for _ in range(3 if smoke else 5):
            t0 = time.perf_counter()
            jax.block_until_ready(g(q, k, v))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6)

    us_shard = timed(lambda q, k, v: backend.run(plan, q, k, v))
    us_single = timed(lambda q, k, v: flare_mixer_packed(q, k, v))
    out["train"] = {
        "us_shard": us_shard, "us_single": us_single,
        "mesh": plan.params["mesh_shape"], "backend": plan.describe(),
        "B": B, "H": H, "N": N, "M": M, "D": D,
    }

    # -- serve/<arch>/sharded_tok_s ---------------------------------------
    from repro.configs import get_smoke_config
    from repro.models.api import get_model
    from repro.serve.engine import ServeEngine

    arch = "qwen2_1_5b"
    cfg = get_smoke_config(arch)
    model = get_model(cfg, seq_len_hint=64)
    params = model.init(jax.random.PRNGKey(0))
    requests = 6 if smoke else 12

    def drain(eng_mesh):
        eng = ServeEngine(model, params, capacity=64, slots=4, seed=0,
                          pool_tokens=256, block_size=16, mesh=eng_mesh)
        eng.warmup(max_prompt_len=16)
        wrng = np.random.default_rng(0)
        lens = wrng.integers(4, 17, requests)
        max_new = wrng.integers(4, 13, requests)
        for i in range(requests):
            eng.submit(wrng.integers(0, cfg.vocab, lens[i]),
                       max_new_tokens=int(max_new[i]))
        t0 = time.perf_counter()
        while eng.step():
            pass
        dt = time.perf_counter() - t0
        outs = [np.asarray(r.tokens, np.int32)
                for r in sorted(eng.sched.finished, key=lambda r: r.rid)]
        return eng, dt, outs

    single, sdt, souts = drain(None)
    shard, dt, outs = drain(mesh)
    s = shard.stats
    toks = s["tokens_generated"]
    # registry snapshot (DESIGN.md §16): the sharded engine's per-shard
    # allocators share metric handles, so these read as pool-wide sums
    msnap = shard.metrics.snapshot()
    mstep = msnap.get("engine.decode_step_s", {"count": 0, "sum": 0.0})
    out["serve_metrics"] = {
        "m_admitted": msnap.get("sched.admitted", 0),
        "m_tokens_out": msnap.get("engine.tokens_out", 0),
        "m_pages_mapped": msnap.get("pool.pages_mapped", 0),
        "m_step_ms_mean": mstep["sum"] / max(mstep["count"], 1) * 1e3,
    }
    out["serve"] = {
        "arch": arch,
        "us_per_tok": dt * 1e6 / max(toks, 1),
        "tok_s": toks / dt,
        "single_tok_s": single.stats["tokens_generated"] / sdt,
        "mesh": s["mesh_shape"], "shards": s["shards"],
        "host_syncs": s["host_syncs_per_step"],
        "compiles": s["decode_compiles"],
        "decode_backend": s["decode_backend"],
        "requests": requests,
        "match": all(np.array_equal(a, b) for a, b in zip(souts, outs)),
    }
    return out


def run() -> None:
    from benchmarks.common import emit

    data = _measure()

    t = data["train"]
    emit("train/packed_shard", t["us_shard"],
         f"mesh={t['mesh']};devices={MESH_DEVICES};"
         f"single_us={t['us_single']:.1f};"
         f"rel={t['us_shard'] / t['us_single']:.2f};"
         f"B={t['B']};H={t['H']};N={t['N']};M={t['M']};D={t['D']}",
         backend=t["backend"])
    sv = data["serve"]
    if not sv["match"]:
        raise RuntimeError("sharded greedy decode diverged from the "
                           "single-device pool")
    mm = data["serve_metrics"]
    emit(f"serve/{sv['arch']}/sharded_tok_s", sv["us_per_tok"],
         f"tok_s={sv['tok_s']:.1f};single_tok_s={sv['single_tok_s']:.1f};"
         f"mesh={sv['mesh']};shards={sv['shards']};"
         f"host_syncs_per_step={sv['host_syncs']:.1f};"
         f"compiles={sv['compiles']};requests={sv['requests']};"
         f"greedy_match={sv['match']};prefix_hit_rate=0.000;"
         f"m_admitted={mm['m_admitted']:.0f};"
         f"m_tokens_out={mm['m_tokens_out']:.0f};"
         f"m_pages_mapped={mm['m_pages_mapped']:.0f};"
         f"m_step_ms_mean={mm['m_step_ms_mean']:.2f}",
         backend=sv["decode_backend"])


if __name__ == "__main__":
    run()

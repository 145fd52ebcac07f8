#!/usr/bin/env python3
"""Chip smoke test: the two main paths of this repo on a TPU, at published
widths, through the entry points a user calls.

    python chip_smoke.py             # one chip: pde_train, then serve
    python chip_smoke.py --chips 4   # four chips: packed_shard vs packed,
                                     # slot-sharded pool vs one-device pool

Phases (one process; each frees its model before the next starts):

  pde_train  ``flare_pde`` (8 blocks, C=64, 8 heads, M=2048) takes three
             ``Trainer`` steps on seeded Darcy batches of the ``pde_40k``
             shape (8 x 40,000 points). The train plan must be ``packed``
             and the loss finite. The packed kernel's forward and
             gradients are compared with ``kernels/ref.py`` at these widths.
  serve      ``qwen2_1_5b`` served by ``ServeEngine`` on the paged pool with
             ``decode_backend="paged"`` (8 slots, capacity 2048, 16,384
             pool tokens, block 16), warmed up, answering 8 seeded requests
             of 256-1024 prompt tokens and 32 new tokens each. No decode
             compile may happen while serving, and no host sync per step.
             One decode step's logits from the kernel route are compared
             with the jnp gather route.

Each phase prints ``[phase] key: value`` lines: the resolved plan, compile
and steady seconds (timed with ``block_until_ready``), device memory, whether
the compiled step holds a Pallas kernel (``tpu_custom_call``) and each
comparison with its tolerance. Only when every phase and check passed on a
TPU is the last line ``{"ok": true, "device": {...}}``; anything else exits
non-zero without it. There is no CPU branch: a host without a TPU fails.

The script reads nothing outside its checkout: the compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``, and the tile
autotuner looks only inside ``.jax_cache`` (and never times anything).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances, each a bound on max|x - ref| / max|ref| (or ||x-ref||/||ref||
# for logits), from how the two sides round. The first three were chosen
# before any chip run; packed_shard's after one on four v5e chips:
#  - packed forward vs ref (ref at "highest" precision): 2e-2 covers a
#    kernel whose f32 contractions take one bf16 MXU pass (2^-8 relative
#    per product) through a softmax over 40,000 tokens; a wrong mask, head
#    mapping or statistic is off by O(1).
#  - packed gradients: 5e-2, the same rounding plus the (dA - delta)
#    cancellation of the softmax backward.
#  - logits, kernel vs gather route (relative L2): 5e-2. Both routes run
#    bf16 activations (2^-8 per rounding) and differ only in how each of
#    28 layers rounds its attention read; independent errors over ~100
#    roundings add to ~4e-2. A kernel that reads a wrong page or head
#    moves the logits by O(1).
#  - packed_shard vs packed: the packed bounds again. Mosaic contracts
#    these f32 operands in one bf16 pass (the packed kernel is 1.6e-2 off
#    an exact reference at flare_pde widths on a v5e), and the two sides
#    split and merge the token sum differently, so each rounds on its own;
#    a missing or wrong cross-shard merge is off by O(1).
TOL_PACKED_FWD = 2e-2
TOL_PACKED_GRAD = 5e-2
TOL_LOGITS = 5e-2


class SmokeFailure(RuntimeError):
    """A phase ran but a check on its result failed."""


def log(phase: str, key: str, value) -> None:
    print(f"[{phase}] {key}: {value}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _bootstrap() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit("chip_smoke.py: no src/repro beside this script; "
                 "run it from a checkout of the repository")
    sys.path.insert(0, src)
    # plans come from the shape heuristic; never the user's tile cache
    os.environ["REPRO_AUTOTUNE"] = "0"
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(ROOT, ".jax_cache",
                                                      "autotune.json")


def _rel(x, ref) -> float:
    import numpy as np

    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _memory(phase: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    # the allocator's peak since the process started (a later phase's peak
    # includes every earlier one's), and what is held at the phase end; a
    # program's own temporaries are given by memory_analysis instead
    log(phase, "peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    log(phase, "bytes_in_use", stats.get("bytes_in_use", "not reported"))


def _temp_bytes(compiled):
    mem = compiled.memory_analysis()
    return None if mem is None else mem.temp_size_in_bytes


def _free() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def _fwd_grad(f):
    """jit of (q, k, v, w) -> (f(q, k, v), d<f, w>/dq, /dk, /dv)."""
    import jax

    def run(q, k, v, w):
        y, vjp = jax.vjp(f, q, k, v)
        return (y,) + vjp(w)

    return jax.jit(run)


def _errors(got, want) -> dict:
    return {n: _rel(a, b) for n, a, b in zip(("y", "dq", "dk", "dv"), got, want)}


def _check_packed(e: dict, what: str) -> None:
    check(e["y"] <= TOL_PACKED_FWD and all(
        e[n] <= TOL_PACKED_GRAD for n in ("dq", "dk", "dv")), f"{what}: {e}")


def _timed(fn, *args):
    """(result, seconds) of one call, ended by block_until_ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# kernel vs reference comparisons
# ---------------------------------------------------------------------------


def packed_vs_ref(plan, *, heads: int, latents: int, head_dim: int,
                  tokens: int, seed: int = 0) -> dict:
    """Packed kernel (as the train plan launches it) against the jnp oracle
    in kernels/ref.py at "highest" matmul precision: forward output and the
    gradients of <y, w> for a random cotangent w. Batch 1; the oracle runs
    head by head so its [M, N] score matrices stay small."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flare_packed import flare_mixer_packed
    from repro.kernels.ref import flare_mixer_ref

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (heads, latents, head_dim), jnp.float32)
    k = jax.random.normal(ks[1], (1, heads, tokens, head_dim), jnp.float32)
    v = jax.random.normal(ks[2], (1, heads, tokens, head_dim), jnp.float32)
    w = jax.random.normal(ks[3], (1, heads, tokens, head_dim), jnp.float32)

    def kernel(q, k, v):
        return flare_mixer_packed(q, k, v, pack=plan.params.get("pack"),
                                  block_n=plan.params.get("block_n", 256))

    def ref(q, k, v):
        per_head = jax.lax.map(
            lambda a: flare_mixer_ref(a[0][None], a[1][None], a[2][None])[0],
            (q, k[0], v[0]))
        return per_head[None]

    got = _fwd_grad(kernel)(q, k, v, w)
    with jax.default_matmul_precision("highest"):
        want = _fwd_grad(ref)(q, k, v, w)
    finite = all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    return {"errors": _errors(got, want), "finite": finite}


# ---------------------------------------------------------------------------
# phase: pde_train
# ---------------------------------------------------------------------------


def pde_train(cfg, *, batch: int, points: int, steps: int = 3, seed: int = 0,
              ckpt_root: str = ROOT) -> dict:
    """Train ``cfg`` (a pde-family config) for ``steps`` Trainer steps on
    seeded Darcy batches of ``batch`` x ``points`` (a square grid), in a
    fresh checkpoint directory under ``ckpt_root``, then compare the packed
    kernel with its reference at the config's widths over ``points``."""
    import jax
    import numpy as np

    from repro.config import TrainConfig
    from repro.data.pde_data import darcy_batch
    from repro.models.api import get_model
    from repro.train.trainer import Trainer

    phase = "pde_train"
    grid = math.isqrt(points)
    check(grid * grid == points, f"{points} points is not a square grid")
    model = get_model(cfg)
    plan = model.plans["train"]
    log(phase, "plan", plan.describe())
    report = {"backend": plan.backend}

    batches = [jax.block_until_ready(darcy_batch(seed, i, batch, grid=grid))
               for i in range(steps)]
    log(phase, "batch", {k: tuple(v.shape) for k, v in batches[0].items()})
    # the Trainer restores any checkpoint it finds (and starts fresh after a
    # failed restore), so a directory of its own makes three real steps
    with tempfile.TemporaryDirectory(prefix=".smoke_ckpt_", dir=ckpt_root) as ckpt:
        tcfg = TrainConfig(steps=steps, checkpoint_every=steps, log_every=steps,
                           checkpoint_dir=ckpt, seed=seed)
        trainer = Trainer(model, tcfg)
        check(trainer.step == 0, "trainer resumed instead of starting fresh")
        t0 = time.perf_counter()
        compiled = trainer._train_step.lower(
            trainer.params, trainer.opt_state, batches[0]).compile()
        report["compile_s"] = time.perf_counter() - t0
        report["kernel_in_step"] = "tpu_custom_call" in compiled.as_text()
        report["step_temp_bytes"] = _temp_bytes(compiled)
        history = trainer.fit(lambda step: batches[step])
        losses = [h["loss"] for h in history]
        report["losses"] = losses
        report["fit_step_s"] = [h["time"] for h in history]
        # steady state on the compiled step, outside the fit loop's metric sync
        params, opt = trainer.params, trainer.opt_state
        steady = []
        for b in batches:
            (params, opt, _), dt = _timed(compiled, params, opt, b)
            steady.append(dt)
        report["steady_s"] = float(np.median(steady))
        del trainer, params, opt, compiled
    log(phase, "compile_s", report["compile_s"])
    log(phase, "fit_step_s", report["fit_step_s"])
    log(phase, "steady_step_s", report["steady_s"])
    log(phase, "losses", losses)
    log(phase, "kernel_in_step (tpu_custom_call)", report["kernel_in_step"])
    log(phase, "step_temp_bytes (memory_analysis)", report["step_temp_bytes"])
    report["loss_finite"] = len(losses) == steps and all(
        math.isfinite(x) for x in losses)

    cmp = packed_vs_ref(plan, heads=cfg.flare_heads, latents=cfg.flare_latents,
                        head_dim=cfg.d_model // cfg.flare_heads,
                        tokens=points, seed=seed)
    report["ref"] = cmp
    log(phase, "packed_vs_ref", f"{cmp['errors']} (tolerance y {TOL_PACKED_FWD}, "
        f"grads {TOL_PACKED_GRAD}; max|x-ref|/max|ref|, ref at highest "
        "precision)")
    _memory(phase)
    return report


def check_pde(r: dict) -> None:
    check(r["backend"] == "packed", f"train plan is {r['backend']}, not packed")
    check(r["loss_finite"], f"loss not finite: {r['losses']}")
    check(r["kernel_in_step"], "compiled train step holds no tpu_custom_call")
    check(r["ref"]["finite"], "packed kernel output or gradients not finite")
    _check_packed(r["ref"]["errors"], "packed kernel off the reference")


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def _prompts(vocab: int, n: int, lo: int, hi: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1))).astype(np.int32)
            for _ in range(n)]


def _decode_program(eng):
    """The engine's fused decode step, compiled at its live shapes."""
    import jax
    import jax.numpy as jnp

    spec = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    args = (spec(eng.params), jax.ShapeDtypeStruct((eng.slots, 1), jnp.int32),
            spec(eng.pool), spec(eng._pt_dev),
            jax.ShapeDtypeStruct((eng.slots,), jnp.int32), spec(eng.key))
    return eng._decode_step.lower(*args).compile()


def _one_step_logits(eng, prompt):
    """Logits of the first decode step for ``prompt`` (2 new tokens: one
    from prefill, one from that decode step)."""
    import numpy as np

    eng.submit(prompt, max_new_tokens=2)
    out = eng.run_all()[-1]
    _, slot = eng.sched.admission_log[-1]
    return np.asarray(eng.last_logits[slot], np.float32), out


def serve(cfg, *, slots: int = 8, capacity: int = 2048, pool_tokens: int = 16384,
          block: int = 16, requests: int = 8, prompt_lo: int = 256,
          prompt_hi: int = 1024, new_tokens: int = 32, seed: int = 0,
          min_bucket: int = 256) -> dict:
    """Serve seeded requests with the paged kernel route, then compare one
    decode step's logits with the gather route on the same weights."""
    import jax
    import numpy as np

    from repro.models.api import get_model
    from repro.serve.engine import ServeEngine

    phase = "serve"
    model = get_model(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    kw = dict(slots=slots, capacity=capacity, pool_tokens=pool_tokens,
              block_size=block, min_bucket=min_bucket)
    eng = ServeEngine(model, params, decode_backend="paged", **kw)
    report = {"decode_backend": eng.stats["decode_backend"]}
    log(phase, "plan", f"decode={report['decode_backend']} cache={eng.stats['cache']}")

    t0 = time.perf_counter()
    report["warmup_compiles"] = eng.warmup(max_prompt_len=prompt_hi)
    report["compile_s"] = time.perf_counter() - t0
    compiles0 = eng.stats["decode_compiles"]

    prompts = _prompts(cfg.vocab, requests, prompt_lo, prompt_hi, seed)
    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    outs = eng.run_all()
    jax.block_until_ready(eng.pool)
    report["serve_s"] = time.perf_counter() - t0
    st = eng.stats
    report["decode_compiles_serving"] = st["decode_compiles"] - compiles0
    report["host_syncs_per_step"] = st["host_syncs_per_step"]
    report["tokens"] = [len(o) for o in outs]
    report["decode_step_s"] = st["decode_s"] / max(st["decode_steps"], 1)
    program = _decode_program(eng)
    report["kernel_in_step"] = "tpu_custom_call" in program.as_text()
    report["step_temp_bytes"] = _temp_bytes(program)
    log(phase, "compile_s (warmup)",
        f"{report['compile_s']} ({report['warmup_compiles']} programs)")
    log(phase, "serve_s", f"{report['serve_s']} for {requests} requests, "
        f"{sum(report['tokens'])} tokens "
        f"({sum(report['tokens']) / report['serve_s']} tok/s)")
    log(phase, "steady_decode_step_s", report["decode_step_s"])
    log(phase, "prompt_lens", [len(p) for p in prompts])
    log(phase, "decode_compiles_while_serving", report["decode_compiles_serving"])
    log(phase, "host_syncs_per_step", report["host_syncs_per_step"])
    log(phase, "kernel_in_step (tpu_custom_call)", report["kernel_in_step"])
    log(phase, "step_temp_bytes (memory_analysis)", report["step_temp_bytes"])

    probe = prompts[0][:prompt_lo]
    got, tok_k = _one_step_logits(eng, probe)
    del eng
    gc.collect()
    ref_eng = ServeEngine(model, params, decode_backend="gather", **kw)
    want, tok_g = _one_step_logits(ref_eng, probe)
    del ref_eng
    err = float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))
    report["logits_rel_l2"] = err
    report["logits_finite"] = bool(np.all(np.isfinite(got)))
    log(phase, "logits kernel vs gather",
        f"rel L2 {err} (tolerance {TOL_LOGITS}); argmax {int(got.argmax())} vs "
        f"{int(want.argmax())}; tokens {tok_k.tolist()} vs {tok_g.tolist()}")
    _memory(phase)
    del params, model
    return report


def check_serve(r: dict, *, requests: int, new_tokens: int) -> None:
    check(r["decode_backend"].startswith("paged"),
          f"decode route is {r['decode_backend']}, not the paged kernel")
    check(r["decode_compiles_serving"] == 0,
          f"{r['decode_compiles_serving']} decode compiles while serving")
    check(r["host_syncs_per_step"] == 0.0,
          f"host syncs per step {r['host_syncs_per_step']}")
    check(r["tokens"] == [new_tokens] * requests, f"token counts {r['tokens']}")
    check(r["kernel_in_step"], "compiled decode step holds no tpu_custom_call")
    check(r["logits_finite"], "kernel-route logits not finite")
    check(r["logits_rel_l2"] <= TOL_LOGITS,
          f"kernel logits off the gather route: {r['logits_rel_l2']}")


# ---------------------------------------------------------------------------
# four chips: packed_shard vs packed, sharded pool vs one-device pool
# ---------------------------------------------------------------------------


def mesh_train(cfg, mesh, *, batch: int, points: int, seed: int = 0) -> dict:
    """packed_shard forward and gradients on ``mesh`` against the one-device
    packed kernel, at the config's mixer widths."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.dispatch import MixerShape, resolve
    from repro.kernels.flare_packed import flare_mixer_packed

    phase = "packed_shard"
    h, m = cfg.flare_heads, cfg.flare_latents
    d = cfg.d_model // h
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    one = jax.devices()[0]
    q = jax.device_put(jax.random.normal(ks[0], (h, m, d), jnp.float32), one)
    kv = [jax.device_put(jax.random.normal(kk, (batch, h, points, d),
                                           jnp.float32), one) for kk in ks[1:]]
    k, v, w = kv
    backend, plan = resolve("packed_shard", shape=MixerShape.from_qkv(q, k),
                            dtype=k.dtype, mesh=mesh)
    log(phase, "plan", plan.describe())

    single = _fwd_grad(lambda q, k, v: flare_mixer_packed(q, k, v))
    shard = _fwd_grad(lambda q, k, v: backend.run(plan, q, k, v))
    rep = NamedSharding(mesh, P())
    args_m = [jax.device_put(x, rep) for x in (q, k, v, w)]
    report = {"backend": plan.backend}
    t0 = time.perf_counter()
    c_single = single.lower(q, k, v, w).compile()
    c_shard = shard.lower(*args_m).compile()
    report["compile_s"] = time.perf_counter() - t0
    report["kernel_in_step"] = "tpu_custom_call" in c_shard.as_text()
    report["step_temp_bytes"] = _temp_bytes(c_shard)
    want, _ = _timed(c_single, q, k, v, w)
    got, _ = _timed(c_shard, *args_m)
    _, report["single_s"] = _timed(c_single, q, k, v, w)
    _, report["shard_s"] = _timed(c_shard, *args_m)
    report["errors"] = _errors(got, want)
    log(phase, "compile_s (both)", report["compile_s"])
    log(phase, "steady_s", f"shard {report['shard_s']} single {report['single_s']}")
    log(phase, "kernel_in_step (tpu_custom_call)", report["kernel_in_step"])
    log(phase, "step_temp_bytes per device (memory_analysis)",
        report["step_temp_bytes"])
    log(phase, "packed_shard vs packed",
        f"{report['errors']} (tolerance y {TOL_PACKED_FWD}, grads "
        f"{TOL_PACKED_GRAD}; max|x-ref|/max|ref|)")
    _memory(phase)
    return report


def check_mesh_train(r: dict) -> None:
    check(r["backend"] == "packed_shard", f"plan is {r['backend']}")
    check(r["kernel_in_step"], "sharded step holds no tpu_custom_call")
    _check_packed(r["errors"], "packed_shard off packed")


def mesh_serve(cfg, mesh, *, slots: int = 8, capacity: int = 2048,
               pool_tokens: int = 16384, block: int = 16, requests: int = 8,
               prompt_lo: int = 256, prompt_hi: int = 1024,
               new_tokens: int = 32, seed: int = 0,
               min_bucket: int = 256) -> dict:
    """Greedy tokens of the slot-sharded pool against a one-device pool on
    the same weights and requests."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.api import get_model
    from repro.serve.engine import ServeEngine

    phase = "sharded_pool"
    model = get_model(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    prompts = _prompts(cfg.vocab, requests, prompt_lo, prompt_hi, seed)
    kw = dict(slots=slots, capacity=capacity, pool_tokens=pool_tokens,
              block_size=block, min_bucket=min_bucket, decode_backend="paged")
    report = {}
    outs = {}
    for name, m in (("one_device", None), ("sharded", mesh)):
        if m is not None:
            # replicate in place of the one-device copy: both copies of the
            # f32 weights would not fit beside the pool on device 0
            params = jax.device_put(params, NamedSharding(m, P()))
        eng = ServeEngine(model, params, mesh=m, **kw)
        t0 = time.perf_counter()
        eng.warmup(max_prompt_len=prompt_hi)
        compile_s = time.perf_counter() - t0
        c0 = eng.stats["decode_compiles"]
        t0 = time.perf_counter()
        for pr in prompts:
            eng.submit(pr, max_new_tokens=new_tokens)
        outs[name] = eng.run_all()
        jax.block_until_ready(eng.pool)
        serve_s = time.perf_counter() - t0
        st = eng.stats
        report[name] = {
            "decode_backend": st["decode_backend"], "compile_s": compile_s,
            "serve_s": serve_s, "shards": st["shards"],
            "decode_compiles_serving": st["decode_compiles"] - c0,
            "host_syncs_per_step": st["host_syncs_per_step"]}
        log(phase, name, report[name])
        del eng
        gc.collect()
    report["match"] = all(np.array_equal(a, b) for a, b in
                          zip(outs["one_device"], outs["sharded"]))
    report["tokens"] = [len(o) for o in outs["sharded"]]
    log(phase, "greedy tokens sharded == one_device", report["match"])
    _memory(phase)
    del params, model
    return report


def check_mesh_serve(r: dict, *, requests: int, new_tokens: int) -> None:
    check(r["sharded"]["decode_backend"].startswith("paged_shard"),
          f"sharded route is {r['sharded']['decode_backend']}")
    check(r["sharded"]["shards"] == 4, "pool is not sharded four ways")
    check(r["tokens"] == [new_tokens] * requests, f"token counts {r['tokens']}")
    check(r["match"], "sharded greedy tokens differ from the one-device pool")
    for name in ("one_device", "sharded"):
        check(r[name]["decode_compiles_serving"] == 0,
              f"{name}: decode compiled while serving")
        check(r[name]["host_syncs_per_step"] == 0.0,
              f"{name}: host syncs per step")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: pde_train + serve; 4: only the two mesh paths")
    args = ap.parse_args(argv)
    _bootstrap()
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.config import SHAPES
    from repro.configs import get_config

    log("setup", "compile_cache", enable_compile_cache())
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("setup", "device", dev)
    if dev["platform"] != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found {dev['platform']}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    shape = SHAPES["pde_40k"]
    pde_cfg, lm_cfg = get_config("flare_pde"), get_config("qwen2_1_5b")
    serve_kw = dict(requests=8, new_tokens=32)
    try:
        if args.chips == 1:
            check_pde(pde_train(pde_cfg, batch=shape.global_batch,
                                points=shape.seq_len))
            _free()
            check_serve(serve(lm_cfg, **serve_kw), **serve_kw)
            _free()
        else:
            from repro.distributed.sharding import make_mesh

            mesh = make_mesh((2, 2), ("data", "model"))
            check_mesh_train(mesh_train(pde_cfg, mesh, batch=shape.global_batch,
                                        points=shape.seq_len))
            _free()
            check_mesh_serve(mesh_serve(lm_cfg, mesh, **serve_kw), **serve_kw)
            _free()
    except SmokeFailure as e:
        print(f"chip_smoke.py: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

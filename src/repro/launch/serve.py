"""Serving launcher: ``python -m repro.launch.serve --arch <id> --smoke``.

Boots the continuous-batching ServeEngine (slot-pool caches, per-request
insertion prefill, retire-and-admit decode — DESIGN.md §4) on a (reduced,
for CPU) config and drives it with an **open-loop Poisson arrival stream**:
requests arrive at ``--rate`` req/s regardless of completion (the
throughput-honest load model), prompts/lengths drawn from a seeded rng.
Prints tok/s, latency percentiles (p50/p99 total and first-token), slot
utilization and compile counts. ``--rate 0`` submits everything up front
(closed-loop batch drain).
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.models.api import get_model
from repro.serve.engine import ServeEngine


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in req/s "
                         "(0 = submit all requests up front)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds (expired queued "
                         "requests are dropped at admission)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--sample", default="greedy", choices=("greedy", "topk"),
                    help="on-device sampler compiled into the decode step "
                         "(greedy argmax, or top-k + temperature)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="k for --sample topk")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile every (bucket, lanes) prefill and the "
                         "fused decode step before serving, so steady state "
                         "never recompiles")
    ap.add_argument("--max-decode-compiles", type=int, default=None,
                    help="exit nonzero if the serving loop compiled the "
                         "decode step more than this many times (warmup "
                         "compiles excluded)")
    ap.add_argument("--decode-backend", default="auto",
                    choices=("auto", "paged", "gather"),
                    help="paged-pool decode read route: the Pallas "
                         "gather-decode kernel ('paged'), the jnp dense "
                         "gather ('gather'), or policy resolution ('auto')")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pool-tokens", type=int, default=None,
                    help="total pooled KV tokens — switches the engine to the "
                         "block-paged pool (DESIGN.md §4); admission is then "
                         "bounded by tokens, not slots")
    ap.add_argument("--kv-quant", default="none", choices=("none", "int8", "fp8"),
                    help="paged-pool storage quantization (dequant on read)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-pool block size in tokens")
    ap.add_argument("--coalesce", action="store_true",
                    help="batch same-bucket admissions into one prefill "
                         "launch (throughput mode; lanes are no longer "
                         "bit-identical to solo runs)")
    ap.add_argument("--mixer", default=None,
                    help="FLARE mixer backend preference, comma-separated "
                         "(e.g. 'causal_pallas,causal_stream'); default: auto")
    ap.add_argument("--mesh", default=None,
                    help="slot-shard the paged pool over a device mesh "
                         "(DESIGN.md §15): 'auto' spans every local device, "
                         "or give an explicit shape like '4' or '2x2'; "
                         "needs --pool-tokens")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-hash block reuse across requests "
                         "(DESIGN.md §4 'Prefix cache'); needs --pool-tokens "
                         "and a gqa/mla arch")
    ap.add_argument("--pin-prompt", action="store_true",
                    help="pin the shared template's blocks in the pool before "
                         "serving (prefilled via a probe request), so eviction "
                         "pressure never reclaims them; needs --share-prefix")
    ap.add_argument("--share-prefix", type=int, default=0,
                    help="multi-tenant workload: N means every prompt = one "
                         "shared --prompt-len template + a short random tail "
                         "drawn per request from N template variants (request "
                         "0 is the exact template). 0 = independent prompts. "
                         "Workload construction ignores --prefix-cache, so "
                         "cached and cold runs see identical prompts")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request-lifecycle spans and write a "
                         "Chrome-trace-event JSON (Perfetto-loadable) here "
                         "(DESIGN.md §16); host-side only — host syncs/step "
                         "stays 0.0 and greedy outputs are unchanged")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the engine's metrics registry (counters/"
                         "gauges/histograms) as JSON here at exit")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    policy = None
    if args.mixer:
        from repro.core.policy import MixerPolicy

        policy = MixerPolicy(backends=tuple(args.mixer.split(",")))
    model = get_model(cfg, policy=policy, seq_len_hint=args.capacity)
    if model.plans:
        print(f"mixer plan (resolved once at build): "
              f"infer={model.plans['infer'].describe()}")
    if model.prefill_into is None:
        raise SystemExit(f"{cfg.name} has no slot-pool serving path "
                         f"(family={cfg.family})")
    if cfg.inputs_are_embeddings:
        raise SystemExit(f"{cfg.name} takes embeddings (frontend stub) — see examples/")
    params = model.init(jax.random.PRNGKey(0))

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh

        if args.mesh == "auto":
            mesh = make_host_mesh()
        else:
            shape = tuple(int(x) for x in args.mesh.split("x"))
            axes = ("data", "model")[:len(shape)]
            if len(shape) != len(axes):
                raise SystemExit(f"--mesh {args.mesh}: at most 2 axes")
            mesh = make_host_mesh(shape, axes)

    tracer = None
    if args.trace_out:
        from repro.obs.trace import Tracer

        tracer = Tracer()
    engine = ServeEngine(model, params, capacity=args.capacity, slots=args.slots,
                         temperature=args.temperature, seed=args.seed,
                         pool_tokens=args.pool_tokens, kv_quant=args.kv_quant,
                         block_size=args.block_size,
                         coalesce_prefill=args.coalesce,
                         sample=args.sample, top_k=args.top_k,
                         decode_backend=args.decode_backend,
                         prefix_cache=args.prefix_cache, mesh=mesh,
                         tracer=tracer)
    print(f"engine: {args.slots} slots, capacity {args.capacity}, "
          f"{engine.stats['cache']}")
    if mesh is not None:
        print(f"slot-sharded pool: mesh {engine.stats['mesh_shape']} "
              f"({engine.stats['shards']} shards x "
              f"{args.slots // engine.stats['shards']} slots)")
    print(f"decode backend: {engine.stats['decode_backend']}  "
          f"sampler: {args.sample}"
          + (f"(k={args.top_k})" if args.sample == "topk" else ""))
    if args.warmup:
        n = engine.warmup(max_prompt_len=args.prompt_len)
        print(f"warmup: {n} programs compiled in "
              f"{engine.stats['warmup_s']:.2f}s")
    warm_decode_compiles = engine.stats["decode_compiles"]

    rng = np.random.default_rng(args.seed)
    # pre-draw the workload so --rate only changes arrival timing; the
    # multi-tenant shape (--share-prefix) is drawn the same way whether the
    # prefix cache is on or off, so cold/cached runs compare bit-for-bit
    if args.share_prefix > 0:
        templates = [rng.integers(0, cfg.vocab, args.prompt_len)
                     for _ in range(args.share_prefix)]
        tails = rng.integers(1, 5, args.requests)
        prompts = [templates[0].copy() if i == 0 else
                   np.concatenate([templates[i % args.share_prefix],
                                   rng.integers(0, cfg.vocab, int(tails[i]))])
                   for i in range(args.requests)]
    else:
        templates = []
        prompts = [rng.integers(0, cfg.vocab, max(1, int(p)))
                   for p in rng.integers(args.prompt_len // 2 + 1,
                                         args.prompt_len + 1, args.requests)]
    arrivals = (np.zeros(args.requests) if args.rate <= 0
                else np.cumsum(rng.exponential(1.0 / args.rate, args.requests)))

    if args.pin_prompt:
        if not templates:
            raise SystemExit("--pin-prompt needs --share-prefix")
        pinned = sum(engine.pin_prefix(t) for t in templates)
        print(f"pinned {pinned} template blocks")

    t0 = time.time()
    submitted = 0
    traffic: set[int] = set()
    outs: dict[int, np.ndarray] = {}
    while submitted < args.requests or engine.sched.has_work():
        now = time.time() - t0
        while submitted < args.requests and arrivals[submitted] <= now:
            traffic.add(engine.submit(prompts[submitted],
                                      max_new_tokens=args.max_new,
                                      deadline_s=args.deadline))
            submitted += 1
        if not engine.step() and submitted < args.requests:
            # open-loop idle gap: wait for the next arrival
            time.sleep(max(0.0, arrivals[submitted] - (time.time() - t0)))
    dt = time.time() - t0
    for r in sorted(engine.sched.finished, key=lambda r: r.rid):
        if r.rid in traffic:  # exclude the pin-probe request
            outs[r.rid] = np.asarray(r.tokens, np.int32)
    for i, (rid, o) in enumerate(sorted(outs.items())):
        # stable numbering: a pin probe consumes a rid, so print the traffic
        # index (diffable against a run without --pin-prompt)
        print(f"req {i}: {o.tolist()}")

    s = engine.stats
    tok_s = s["tokens_generated"] / dt if dt > 0 else float("inf")
    print(f"\n{s['requests']} requests / {s['tokens_generated']} tokens in "
          f"{dt:.2f}s ({tok_s:.1f} tok/s; prefill {s['prefill_s']:.2f}s "
          f"decode {s['decode_s']:.2f}s over {s['decode_steps']} steps)")
    print(f"latency p50/p99: {s['latency_p50_s'] * 1e3:.1f}/"
          f"{s['latency_p99_s'] * 1e3:.1f} ms  first-token p50/p99: "
          f"{s['first_token_p50_s'] * 1e3:.1f}/{s['first_token_p99_s'] * 1e3:.1f} ms")
    print(f"slot utilization {s['slot_utilization']:.2f}, "
          f"{s['prefill_compiles']} prefill bucket compiles, "
          f"{s['coalesced_prefills']} coalesced launches, "
          f"{s['dropped']} dropped")
    serve_compiles = s["decode_compiles"] - warm_decode_compiles
    print(f"decode compiles: {s['decode_compiles']} total, {serve_compiles} "
          f"while serving; warmup: {s['warmup_compiles']} programs "
          f"({s['warmup_s']:.2f}s); host syncs/step: "
          f"{s['host_syncs_per_step']:.1f}")
    if (args.max_decode_compiles is not None
            and serve_compiles > args.max_decode_compiles):
        raise SystemExit(f"decode step compiled {serve_compiles}x while "
                         f"serving (bound {args.max_decode_compiles}) — the "
                         "steady-state loop is retracing")
    if engine.paged:
        p = s["pool"]
        print(f"paged pool: {p['blocks_mapped']}/{p['blocks_total']} blocks "
              f"mapped (peak {p['blocks_peak_mapped']}), "
              f"{p['pages_appended']} pages appended at block boundaries, "
              f"admitted peak {s['admitted_peak']}/{args.slots} slots")
        print(f"prefix cache: enabled={s['prefix_cache']} "
              f"hit_rate={s['prefix_hit_rate']:.3f} "
              f"shared_pages={s['shared_pages']} "
              f"cow_copies={s['cow_copies']} "
              f"pinned={s.get('pinned_pages', 0)}")
    if args.trace_out:
        n = engine.tracer.write(args.trace_out)
        print(f"trace: {n} spans -> {args.trace_out}")
    if args.metrics_out:
        engine.metrics.dump_json(args.metrics_out)
        print(f"metrics: {len(engine.metrics.snapshot())} series -> "
              f"{args.metrics_out}")


if __name__ == "__main__":
    main()

"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

On real TPU pods this launches the pjit'd fault-tolerant Trainer on the
production mesh; on CPU (this container) use --smoke to train the reduced
config of the same family end-to-end (data -> train loop -> checkpoints).
"""
import argparse
import logging

import jax

from repro.compile_cache import enable_compile_cache
from repro.config import TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.data.synthetic import TokenStream
from repro.data.pde_data import darcy_batch
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import get_model
from repro.train.trainer import Trainer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--mesh", choices=["none", "host", "single", "multi"], default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mixer", default=None,
                    help="FLARE mixer backend preference, comma-separated "
                         "(e.g. 'packed,sdpa', or 'packed_shard' with "
                         "--mesh for the shard_map'd kernel); default: auto")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-step train spans (data vs device step "
                         "breakdown) and write Chrome-trace-event JSON here")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the trainer's metrics registry as JSON here")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh == "host":
        mesh = make_host_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    policy = None
    if args.mixer:
        from repro.core.policy import MixerPolicy

        policy = MixerPolicy(backends=tuple(args.mixer.split(",")))
    # a named sharded backend (packed_shard) resolves against the training
    # mesh (DESIGN.md §15); without --mixer the mesh stays a Trainer concern
    model = get_model(cfg, policy=policy, seq_len_hint=args.seq_len,
                      mesh=mesh if policy is not None else None)
    if model.plans:
        print(f"mixer plans (resolved once at build): "
              f"train={model.plans['train'].describe()} "
              f"infer={model.plans['infer'].describe()}")

    tcfg = TrainConfig(steps=args.steps, learning_rate=args.lr,
                       checkpoint_every=max(10, args.steps // 4),
                       checkpoint_dir=args.ckpt, log_every=10)
    tracer = None
    if args.trace_out:
        from repro.obs.trace import Tracer

        tracer = Tracer()
    trainer = Trainer(model, tcfg, mesh, num_microbatches=args.microbatches,
                      tracer=tracer)

    if cfg.family == "pde":
        batch_fn = lambda step: darcy_batch(0, step % 16, args.global_batch,
                                            grid=16, cg_iters=100)
    else:
        stream = TokenStream(cfg.vocab, args.seq_len, seed=tcfg.seed)

        def batch_fn(step):
            b = stream.global_batch(step, args.global_batch, 1)
            if cfg.inputs_are_embeddings or cfg.family in ("encdec", "audio"):
                import numpy as np

                rng = np.random.default_rng(step)
                b["embeds"] = rng.standard_normal(
                    (args.global_batch, args.seq_len, cfg.d_model)).astype("float32")
                if cfg.inputs_are_embeddings:
                    b.pop("tokens", None)
            return b

    history = trainer.fit(batch_fn)
    if history:
        print(f"\n{cfg.name}: {len(history)} steps, "
              f"loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}")
    if args.trace_out:
        n = trainer.tracer.write(args.trace_out)
        print(f"trace: {n} spans -> {args.trace_out}")
    if args.metrics_out:
        trainer.metrics.dump_json(args.metrics_out)
        print(f"metrics: {len(trainer.metrics.snapshot())} series -> "
              f"{args.metrics_out}")


if __name__ == "__main__":
    main()

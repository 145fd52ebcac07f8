"""Driver for configurations whose ``entry`` is ``train_lm``: the program's
``Trainer`` steps a language model on packed token sequences from the
cell's traffic mix (``kind: tokens``, :func:`token_batches`).

It runs as ``drive_train`` does, and uses that driver's step hook, its
comparison and verdict and its program size: set-up builds one Trainer
(its compiled step and state) with weights made on the device from the
configuration's ``weights_seed`` and batches from the run's seed, then drives it through the mix's first ``check_steps`` steps with its
own ``fit`` on batches that all differ; the same Trainer then runs the
window, which is whole steps and ends with the first step that completes
``seconds`` after it began. After the window the program's state is freed
and the reference repeats the first steps; the comparison decides
``correct``. Besides, the window's steps give the routing counters that the
model returns with each step's metrics (``moe.rows_held``,
``moe.load_max_over_mean``), and a traced run hands the readers the
compiled step's text in ``ctx["hlo"]``.
"""
from __future__ import annotations

import functools
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, harness
from bench.drive_train import _program_bytes, _since, _StepHook, compare, verdict
from bench.metrics import _trace

COUNTERS = ("moe.rows_held", "moe.load_max_over_mean")


def model_config(prog: dict):
    """The program's ``ModelConfig`` from the configuration's ``program``
    section, its nested groups made into their dataclasses."""
    from repro.config import AttnConfig, MLAConfig, ModelConfig, MoEConfig, YarnConfig

    kw = dict(prog)
    attn = dict(kw.get("attn", {}))
    if attn.get("mla") is not None:
        attn["mla"] = MLAConfig(**attn["mla"])
    if attn.get("rope_scaling") is not None:
        attn["rope_scaling"] = YarnConfig(**attn["rope_scaling"])
    kw["attn"] = AttnConfig(**attn)
    if kw.get("moe") is not None:
        kw["moe"] = MoEConfig(**kw["moe"])
    return ModelConfig(**kw)


# ----------------------------------------------------------------- tokens

def token_batches(mix: dict, vocab: int, key) -> list:
    """``mix["distinct_batches"]`` batches {"tokens", "labels"} (int32
    [batch, seq_len], on the device) made in one call from a JAX key.

    One stream of ``distinct_batches * batch * (seq_len + 1)`` tokens:
    documents of log-normal length (median ``doc_len_median``, sigma
    ``doc_len_sigma``, at least 1), each followed by ``eos_id``, their ids
    drawn Zipf(``zipf_exponent``) over the other ``vocab - 1`` ids (rank r
    is the r-th id that is not ``eos_id``). The stream is cut into rows of
    ``seq_len + 1``, so documents pack with no mask between them and split
    across rows; labels are the next token."""
    static = (mix["distinct_batches"], mix["batch"], mix["seq_len"], int(vocab),
              float(mix["zipf_exponent"]), float(mix["doc_len_median"]),
              float(mix["doc_len_sigma"]), int(mix["eos_id"]))
    stream = _token_stream(key, static)
    nb, b, s = static[:3]
    return [{"tokens": stream[i, :, :s], "labels": stream[i, :, 1:]} for i in range(nb)]


@functools.partial(jax.jit, static_argnums=1)
def _token_stream(key, static):
    nb, b, s, vocab, a, median, sigma, eos = static
    total = nb * b * (s + 1)
    k_tok, k_len = jax.random.split(key)
    p = jnp.arange(1, vocab, dtype=jnp.float32) ** -a
    cdf = jnp.cumsum(p) / jnp.sum(p)
    rank = jnp.searchsorted(cdf, jax.random.uniform(k_tok, (total,)), side="right")
    rank = jnp.minimum(rank, vocab - 2)
    ids = rank + (rank >= eos).astype(rank.dtype)
    # one length per token: documents of at least one token cover the stream
    length = jnp.maximum(1, jnp.round(median * jnp.exp(
        sigma * jax.random.normal(k_len, (total,))))).astype(jnp.int32)
    ends = jnp.cumsum(length + 1) - 1
    is_eos = jnp.zeros((total,), bool).at[ends].set(True, mode="drop")
    return jnp.where(is_eos, eos, ids).astype(jnp.int32).reshape(nb, b, s + 1)


# ------------------------------------------------------------------- runs

def first_steps(c: dict, seed: int, hook) -> dict:
    """Set-up: the cell's Trainer, with weights made on the device from the
    seed and the mix's batches, driven through its first ``check_steps``
    steps by its own ``fit``. Returns what ``drive_train.first_steps``
    returns."""
    from repro.config import TrainConfig
    from repro.models.api import get_model
    from repro.optim.adamw import init_adamw
    from repro.train.trainer import Trainer

    cfg, mix, ref = c["config"], c["mix"], c["ref"]
    opt = cfg["optimizer"]
    marks = {"start": time.perf_counter()}
    model = get_model(model_config(cfg["program"]))
    # the weights are the configuration's one draw; the seed draws the data
    k_w = jax.random.fold_in(gen.key_from_seed(cfg["weights_seed"]), 1)
    k_data = jax.random.fold_in(gen.key_from_seed(seed), 2)
    params = jax.jit(ref.weights, static_argnums=1)(k_w, ref._hashable(cfg))
    params = jax.tree.map(lambda a: a.astype(cfg["program"]["param_dtype"]), params)
    batches = token_batches(mix, cfg["vocab_size"], k_data)
    jax.block_until_ready((params, batches))
    marks["weights_and_batches"] = time.perf_counter()
    feed = lambda step: batches[step % len(batches)]
    tcfg = TrainConfig(
        steps=opt["steps"], learning_rate=opt["learning_rate"],
        warmup_frac=opt["warmup_frac"], weight_decay=opt["weight_decay"],
        beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
        grad_clip=opt["grad_clip"], checkpoint_every=1 << 30,
        checkpoint_dir=tempfile.mkdtemp(prefix="bench_ckpt_"), log_every=1 << 30)
    try:
        trainer = Trainer(model, tcfg, tracer=hook)
        hook.trainer = trainer
        # its own initial state goes before the seeded state is made
        trainer.params = trainer.opt_state = None
        trainer.params, trainer.opt_state = params, init_adamw(params)
        del params
        p0 = jax.device_get(trainer.params)
        marks["trainer"] = time.perf_counter()
        hist = trainer.fit(feed, steps=1)
        marks["first_step"] = time.perf_counter()
        m1 = jax.device_get(trainer.opt_state.m)
        hist += trainer.fit(feed, steps=mix["check_steps"])
        marks["check_steps"] = time.perf_counter()
    except BaseException:
        shutil.rmtree(tcfg.checkpoint_dir, ignore_errors=True)
        raise
    prog = {"losses": [h["loss"] for h in hist], "p0": p0,
            "p_last": jax.device_get(trainer.params),
            "grad1": jax.tree.map(lambda m: np.asarray(m) / (1 - opt["beta1"]), m1)}
    return {"trainer": trainer, "batches": batches, "feed": feed, "k_w": k_w,
            "ckpt": tcfg.checkpoint_dir, "prog": prog, "marks": marks}


def run(c: dict, seed: int, seconds: float, trace: bool, *, t_start: float,
        counter, trace_dir: str | None = None) -> dict:
    cfg, mix, ref = c["config"], c["mix"], c["ref"]
    hook = _StepHook()
    st = first_steps(c, seed, hook)
    trainer, batches, feed = st.pop("trainer"), st.pop("batches"), st.pop("feed")
    n_check = mix["check_steps"]
    ctx = {"config": cfg, "mix": mix}
    try:
        # the window: the same Trainer, the same feed
        t_setup = time.perf_counter()
        snap = counter.snapshot()
        start_step = trainer.step
        if trace:
            jax.profiler.start_trace(trace_dir)
            ann = jax.profiler.TraceAnnotation(_trace.WINDOW)

            def stop():
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()

            t0 = time.time()
            ann.__enter__()
            hook.arm(t0, steps=mix["trace_steps"], on_stop=stop)
        else:
            t0 = time.time()
            hook.arm(t0, seconds=seconds)
        hist = trainer.fit(feed, steps=1 << 30)
        window = hook.ends[-1] - t0
        steps = trainer.step - start_step
        compiles = counter.since(snap)
        ctx.update({k: [h[k] for h in hist] for k in COUNTERS if hist and k in hist[0]})
        mem = max(harness.allocator_peak(), _program_bytes(
            trainer._train_step, trainer.params, trainer.opt_state, feed(0)))
        if trace:
            ctx["hlo"] = trainer.step_program(feed(0)).as_text()
        # the Trainer outlives this scope (its signal handlers hold it):
        # free its state for the reference
        hook.trainer = trainer.params = trainer.opt_state = None
        del trainer, feed, batches[n_check:]
        gc.collect()
    finally:
        shutil.rmtree(st["ckpt"], ignore_errors=True)

    # the reference, from the same seed, on the same first batches
    t_ref = time.perf_counter()
    r = ref.train_reference(st["k_w"], cfg, batches[:n_check])
    t_ref = time.perf_counter() - t_ref
    del batches
    numbers, where = compare(st["prog"], r)
    checks, correct = verdict(numbers, cfg["limits"])
    counted = {k: {"mean": sum(v) / len(v), "min": min(v), "max": max(v)}
               for k, v in ctx.items() if k in COUNTERS and v}
    info = {"steps_in_window": steps, "window_s": window, "reference_s": t_ref,
            "compiles_in_window": compiles, "check_steps": n_check,
            "losses": st["prog"]["losses"], "ref_losses": r["losses"],
            "numbers": numbers, **where, "counters": counted,
            "setup_marks_s": _since(st["marks"], t_start)}
    ctx.update(steps=steps, window_s=window)
    return {
        "correct": correct, "attempted": steps,
        "failed": 0,
        "e2e": {"setup_s": t_setup - t_start, "train_step_s": window / steps},
        "checks": checks, "info": info, "memory_peak_bytes": mem, "ctx": ctx,
    }

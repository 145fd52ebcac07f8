"""Driver for configurations whose ``entry`` is ``train``: the program's
``Trainer`` steps on batches from the cell's traffic mix.

Set-up builds one Trainer (its compiled step and state) with weights made
on the device from the seed, then drives it through the mix's first
``check_steps`` steps with its own ``fit`` on batches that all differ; the
same Trainer then runs the window. The window is whole steps: it ends with
the first step that completes ``seconds`` after it began, and
``train_step_s`` is its length over its steps. After the window the
program's state is freed and the reference repeats the first steps; the
comparison decides ``correct``.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import time

from bench import gen, harness
from bench.metrics import _trace

# leaves whose reference gradient is under this share of the median leaf's
# move under Adam by round-off alone; they are left out of the change
NOUGHT_GRAD = 1e-3


class _StepHook:
    """A tracer the Trainer calls after every step: it stamps the step's
    end, and at the end of the window stops the fit loop (and the
    profiler, in a traced run)."""

    enabled = True

    def __init__(self):
        self.trainer = None
        self.ends = []
        self.stop_after_s = None
        self.stop_after_steps = None
        self.t0 = None
        self.on_stop = None

    def arm(self, t0, *, seconds=None, steps=None, on_stop=None):
        self.t0, self.ends = t0, []
        self.stop_after_s, self.stop_after_steps, self.on_stop = seconds, steps, on_stop

    def complete(self, name, ts, dur, **_):
        if name != "train_step" or self.t0 is None:
            return
        self.ends.append(ts + dur)
        done = ((self.stop_after_s is not None and ts + dur - self.t0 >= self.stop_after_s)
                or (self.stop_after_steps is not None
                    and len(self.ends) >= self.stop_after_steps))
        if done:
            if self.on_stop is not None:
                self.on_stop()
            self.trainer._stop = True
            self.t0 = None

    def instant(self, *a, **k):
        pass


def _program_bytes(jitted, *args) -> int:
    """Bytes one run of a compiled program holds: arguments, outputs and
    temporaries less what the outputs alias. The allocator's peak leaves
    out a program's temporaries on this runtime."""
    m = jitted.lower(*args).compile().memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _since(marks: dict, t_start: float) -> dict:
    """Each set-up mark as seconds from the process start."""
    return {k: v - t_start for k, v in marks.items()}


def compare(got: dict, ref: dict) -> tuple[dict, dict]:
    """Every number the training comparison can read, of a run ``got``
    against the reference ``ref`` (each: per-step ``losses``, the first
    clipped gradient ``grad1``, the parameters ``p0`` before the first step
    and ``p_last`` after the last): the losses' relative gap at the first
    step and at the worst step; and, for the first gradient and for the
    parameters' change, the gap of leaf norms at the worst leaf and the
    median of the leaves' gaps. Leaves whose reference gradient is nought
    to rounding are left out of the change."""
    import jax
    import numpy as np

    gaps = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
    g_got, g_ref = harness.leaf_norms(got["grad1"]), harness.leaf_norms(ref["grad1"])
    delta = lambda t: harness.leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        t["p_last"], t["p0"]))
    d_got, d_ref = delta(got), delta(ref)
    med = statistics.median(g_ref.values())
    moved = {k for k, v in g_ref.items() if v >= NOUGHT_GRAD * med}
    grad, grad_leaf, grad_med = harness.leaf_norm_gaps(g_got, g_ref)
    upd, upd_leaf, upd_med = harness.leaf_norm_gaps(d_got, d_ref, keep=moved)
    numbers = {"loss_rel_gap": max(gaps), "loss_rel_gap_step1": gaps[0],
               "grad_norm_gap": grad, "grad_norm_gap_median": grad_med,
               "update_norm_gap": upd, "update_norm_gap_median": upd_med}
    where = {"worst_grad_leaf": grad_leaf, "worst_update_leaf": upd_leaf,
             "leaves_left_out_of_update": sorted(set(g_ref) - moved)}
    return numbers, where


def verdict(numbers: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all hold."""
    checks = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    return checks, all(harness.finite(v["value"]) and v["value"] <= v["limit"]
                       for v in checks.values())


def model_config(prog: dict):
    from repro.config import AttnConfig, ModelConfig

    kw = dict(prog)
    kw["attn"] = AttnConfig(**kw.get("attn", {}))
    return ModelConfig(**kw)


def first_steps(c: dict, seed: int, hook) -> dict:
    """Set-up: the cell's Trainer, with weights made on the device from the
    seed and the mix's batches, driven through its first ``check_steps``
    steps by its own ``fit``. Returns the Trainer, the batches, the feed,
    the weights' key, its checkpoint directory and the readings of those
    steps (per-step losses, the first gradient as Adam's first moment
    holds it, the parameters before and after)."""
    import jax
    import numpy as np

    from repro.config import TrainConfig
    from repro.models.api import get_model
    from repro.optim.adamw import init_adamw
    from repro.train.trainer import Trainer

    cfg, mix, ref = c["config"], c["mix"], c["ref"]
    opt = cfg["optimizer"]
    marks = {"start": time.perf_counter()}
    key = gen.key_from_seed(seed)
    k_w, k_data = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    model = get_model(model_config(cfg["program"]))
    params = jax.jit(ref.weights, static_argnums=1)(k_w, ref._hashable(cfg))
    params = jax.tree.map(lambda a: a.astype(cfg["program"]["param_dtype"]), params)
    batches = gen.darcy_batches(mix, k_data)
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
    import jax

    cfg, mix, ref = c["config"], c["mix"], c["ref"]
    hook = _StepHook()
    st = first_steps(c, seed, hook)
    trainer, batches, feed = st.pop("trainer"), st.pop("batches"), st.pop("feed")
    n_check = mix["check_steps"]
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
        trainer.fit(feed, steps=1 << 30)
        window = hook.ends[-1] - t0
        steps = trainer.step - start_step
        compiles = counter.since(snap)
        mem = max(harness.allocator_peak(), _program_bytes(
            trainer._train_step, trainer.params, trainer.opt_state, feed(0)))
        hook.trainer = None
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
    info = {"steps_in_window": steps, "window_s": window, "reference_s": t_ref,
            "compiles_in_window": compiles, "check_steps": n_check,
            "losses": st["prog"]["losses"], "ref_losses": r["losses"],
            "numbers": numbers, **where,
            "setup_marks_s": _since(st["marks"], t_start)}
    return {
        "correct": correct, "attempted": steps,
        "failed": 0,
        "e2e": {"setup_s": t_setup - t_start, "train_step_s": window / steps},
        "checks": checks, "info": info, "memory_peak_bytes": mem,
        "ctx": {"steps": steps, "window_s": window, "config": cfg, "mix": mix},
    }

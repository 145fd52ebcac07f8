"""Fault-tolerant training loop.

Features exercised at CPU scale and designed for pod scale:
  - pjit'd train step with param/opt/batch shardings from repro.distributed
  - deterministic step-keyed data (restart/elastic-safe; see data/synthetic)
  - async checkpoints every K steps; SIGTERM/SIGINT triggers a final
    blocking save before exit (preemption safety)
  - automatic resume from the latest checkpoint, onto the *current* mesh
    (elastic restore — device count may differ from the saving run)
  - straggler watchdog: per-step wall time vs a running median; slow steps
    fire `on_straggler` (on a real pod this triggers re-slicing; here it
    logs and is unit-tested)
  - optional int8 error-feedback gradient compression (DP axis)
"""
from __future__ import annotations

import logging
import signal
import statistics
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.config import TrainConfig
from repro.distributed.sharding import batch_spec, param_shardings
from repro.obs import annotate, phase
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.optim.adamw import init_adamw
from repro.train.steps import make_train_step

log = logging.getLogger("repro.train")


class Trainer:
    def __init__(
        self,
        model,
        tcfg: TrainConfig,
        mesh: Optional[Mesh] = None,
        *,
        num_microbatches: int = 1,
        on_straggler: Optional[Callable[[int, float, float], None]] = None,
        straggler_factor: float = 3.0,
        tracer=None,
        metrics=None,
    ):
        self.model = model
        self.tcfg = tcfg
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        # observability (DESIGN.md §16): per-trainer registry + optional
        # span tracer; the step-time breakdown (host data feed vs device
        # step, incl. the metric sync) is recorded from the two stamps the
        # fit loop takes anyway
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._m_steps = self.metrics.counter(
            "train.steps", "optimizer steps completed")
        self._m_data_s = self.metrics.histogram(
            "train.data_s", "per-step host data feed seconds")
        self._m_step_s = self.metrics.histogram(
            "train.step_s", "per-step device step seconds (incl. metric sync)")
        self._m_ckpts = self.metrics.counter(
            "train.checkpoints", "checkpoint saves issued")
        self._m_stragglers = self.metrics.counter(
            "train.stragglers", "steps flagged by the straggler watchdog")
        self.on_straggler = on_straggler or (
            lambda step, dt, med: log.warning("straggler: step %d took %.3fs (median %.3fs)", step, dt, med)
        )
        self.straggler_factor = straggler_factor
        self.ckpt = CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints)
        self._stop = False
        self._step_times: list[float] = []
        self.step = 0
        self.params = None
        self.opt_state = None
        self._programs = {}
        self._build()

    # ------------------------------------------------------------ setup

    def _build(self):
        key = jax.random.PRNGKey(self.tcfg.seed)
        shapes = jax.eval_shape(self.model.init, key)
        if self.mesh is not None:
            p_sh = param_shardings(shapes, self.mesh)
            o_m = param_shardings(shapes, self.mesh)
            step_sh = NamedSharding(self.mesh, P())
            self._p_sh = p_sh
            self._batch_sh = NamedSharding(self.mesh, batch_spec(self.mesh))
        else:
            self._p_sh = None
            self._batch_sh = None

        # resume or initialize
        template = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        last, restored = (None, None)
        try:
            last, restored = self.ckpt.restore_latest(template, shardings=self._p_sh)
        except Exception as e:  # noqa: BLE001 - any corruption -> fresh start
            log.warning("checkpoint restore failed (%s); starting fresh", e)
        if restored is not None:
            self.params = restored
            self.step = last
            log.info("resumed from step %d", last)
        else:
            init = self.model.init
            if self._p_sh is not None:
                init = jax.jit(self.model.init, out_shardings=self._p_sh)
            self.params = init(key)
            self.step = 0
        self.opt_state = init_adamw(self.params)
        # fast-forward optimizer step counter on resume (moments restart at
        # zero — documented warm-restart behaviour; full opt-state saving is
        # available via save_full_state)
        self.opt_state = self.opt_state._replace(step=jnp.asarray(self.step, jnp.int32))

        # a model that counts (MoE routing) returns its counters with the
        # step's metrics, read back at the same ``train/sync``
        counted = getattr(self.model, "loss_counters", None)
        train_step = make_train_step(counted or self.model.loss, self.tcfg,
                                     num_microbatches=self.num_microbatches,
                                     counters=counted is not None)
        if self.mesh is not None:
            # layers that split work over the mesh (the MoE layer's
            # shard_map) find it as the ambient abstract mesh
            meshed, abstract = train_step, self.mesh.abstract_mesh

            def train_step(*args):
                with jax.sharding.use_abstract_mesh(abstract):
                    return meshed(*args)

            self._train_step = jax.jit(
                train_step,
                in_shardings=(self._p_sh, None, self._batch_sh),
                # the next step takes the params as this one lays them out
                out_shardings=(self._p_sh, None, None),
                donate_argnums=(0, 1),
            )
        else:
            self._train_step = jax.jit(train_step, donate_argnums=(0, 1))

        signal.signal(signal.SIGTERM, self._handle_term)
        try:
            signal.signal(signal.SIGINT, self._handle_term)
        except ValueError:  # non-main thread (tests)
            pass

    def step_program(self, batch):
        """The compiled train step for the trainer's state and ``batch``
        (arrays or ``jax.ShapeDtypeStruct``s), compiled once per batch
        shape: the program ``fit`` runs on such batches. ``as_text()``
        gives each instruction with its ``op_name`` (the scopes that made
        it), ``memory_analysis()`` the bytes one step holds."""
        leaves, tree = jax.tree_util.tree_flatten(batch)
        key = (tree, tuple((tuple(x.shape), str(x.dtype)) for x in leaves))
        if key not in self._programs:
            self._programs[key] = self._train_step.lower(
                self.params, self.opt_state, batch).compile()
        return self._programs[key]

    def _handle_term(self, signum, frame):  # noqa: ARG002
        log.warning("signal %s received: will checkpoint and stop", signum)
        self._stop = True

    # ------------------------------------------------------------- loop

    def fit(self, batch_fn: Callable[[int], dict], *, steps: Optional[int] = None):
        """batch_fn(step) -> global batch (numpy). Returns metric history."""
        steps = steps or self.tcfg.steps
        history = []
        while self.step < steps and not self._stop:
            n = self.step
            ids = {"step": n}
            # the profiler's host row sees the iteration and its phases
            # under ``train/...``; an enabled tracer gets each phase as a
            # span of the same name, and ``train_step`` after the iteration
            with annotate("train/step"):
                t0 = time.time()
                with phase(self.tracer, "train/data", args=ids):
                    batch = batch_fn(n)
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                t1 = time.time()  # host data feed done; device step begins
                with phase(self.tracer, "train/dispatch", args=ids):
                    self.params, self.opt_state, metrics = self._train_step(
                        self.params, self.opt_state, batch)
                # the float() sync blocks until the step has executed, so
                # everything after t1 is device step + metric readback
                with phase(self.tracer, "train/sync", args=ids):
                    metrics = {k: float(v) for k, v in metrics.items()}
                now = time.time()
                dt = now - t0
                self._m_steps.inc()
                self._m_data_s.observe(t1 - t0)
                self._m_step_s.observe(now - t1)
                self._watchdog(dt)
                self.step += 1
                metrics["step"] = self.step
                metrics["time"] = dt
                history.append(metrics)
                if self.step % self.tcfg.log_every == 0:
                    log.info("step %d loss %.4f gnorm %.3f lr %.2e (%.2fs)",
                             self.step, metrics["loss"], metrics["grad_norm"],
                             metrics["lr"], dt)
                if self.step % self.tcfg.checkpoint_every == 0:
                    with phase(self.tracer, "train/checkpoint", args=ids):
                        self.ckpt.save(self.step, self.params)
                    self._m_ckpts.inc()
            # only once every annotation of the step has closed: a tracer
            # may stop the profiler when it receives the step
            if self.tracer.enabled:
                self.tracer.complete(
                    "train_step", t0, dt, cat="train",
                    args={"step": n, "data_s": round(t1 - t0, 6),
                          "step_s": round(now - t1, 6),
                          "loss": metrics.get("loss")})
        # final (blocking) save — also the preemption path
        self.ckpt.save(self.step, self.params, blocking=True)
        return history

    def _watchdog(self, dt: float):
        self._step_times.append(dt)
        if len(self._step_times) >= 5:
            med = statistics.median(self._step_times[-50:])
            if dt > self.straggler_factor * med:
                self._m_stragglers.inc()
                self.on_straggler(self.step, dt, med)

    def save_full_state(self):
        """Blocking save of params + optimizer moments (exact resume)."""
        self.ckpt.save(self.step, {"params": self.params,
                                   "m": self.opt_state.m, "v": self.opt_state.v},
                       blocking=True)

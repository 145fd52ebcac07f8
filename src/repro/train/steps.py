"""Jittable train/eval steps: microbatched gradient accumulation + AdamW.

The microbatch loop is a lax.scan so remat happens *per microbatch* — the
saved-activation footprint is one microbatch deep regardless of the global
batch, which is what lets the 32B/72B train_4k cells fit 16 GiB/chip
(verified per-cell by the dry-run memory analysis).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.distributed.sharding import constrain_dim_to_batch_axes
from repro.optim.adamw import AdamWState, adamw_update
from repro.optim.schedule import onecycle_schedule


def make_train_step(loss_fn: Callable, tcfg: TrainConfig, *, num_microbatches: int = 1,
                    counters: bool = False):
    """loss_fn(params, microbatch) -> scalar, or with ``counters`` ->
    (scalar, {name: scalar}): device counters the step returns among its
    metrics (averaged over microbatches). Returns train_step(params, opt,
    batch)."""

    def grads_of(params, batch):
        if counters:
            (loss, cnt), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return loss, cnt, grads
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, {}, grads

    def train_step(params, opt_state: AdamWState, batch):
        if num_microbatches == 1:
            loss, cnt, grads = grads_of(params, batch)
        else:
            mbs = jax.tree.map(
                lambda x: constrain_dim_to_batch_axes(
                    x.reshape((num_microbatches, x.shape[0] // num_microbatches) + x.shape[1:]),
                    dim=1),
                batch,
            )

            def body(carry, mb):
                gsum, lsum = carry
                loss, cnt, grads = grads_of(params, mb)
                gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gsum, grads)
                return (gsum, lsum + loss), cnt

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), cnt = jax.lax.scan(body, (zeros, jnp.zeros((), jnp.float32)), mbs)
            inv = 1.0 / num_microbatches
            grads = jax.tree.map(lambda g: g * inv, gsum)
            loss = lsum * inv
            cnt = {k: jnp.mean(v) for k, v in cnt.items()}

        lr = onecycle_schedule(
            opt_state.step, total_steps=tcfg.steps, peak_lr=tcfg.learning_rate,
            warmup_frac=tcfg.warmup_frac,
        )
        params, opt_state, gnorm = adamw_update(
            params, grads, opt_state,
            lr=lr, weight_decay=tcfg.weight_decay, beta1=tcfg.beta1,
            beta2=tcfg.beta2, eps=tcfg.eps, grad_clip=tcfg.grad_clip,
        )
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr, **cnt}
        return params, opt_state, metrics

    return train_step


def make_eval_step(loss_fn: Callable):
    def eval_step(params, batch):
        return loss_fn(params, batch)

    return eval_step

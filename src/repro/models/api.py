"""Uniform model API: every architecture exposes init/loss/prefill/decode.

The launcher, dry-run and trainer talk only to this interface:

    m = get_model(cfg)
    params = m.init(key)                        # or jax.eval_shape(m.init, key)
    loss = m.loss(params, batch)                # train_4k
    logits, caches = m.prefill(params, batch, capacity)   # prefill_32k
    caches0 = m.init_caches(batch_size, capacity)
    logits, caches = m.decode_step(params, token, caches)  # decode_* / long_*
    # continuous-batching insertion prefill (DESIGN.md §4): write ONE
    # request's prefilled state into live pool slots instead of minting a
    # fresh full-batch cache; batch may carry "lengths" for padded buckets
    logits, caches0 = m.prefill_into(params, batch, caches0, slots, capacity=cap)

Mixer dispatch is **plan-first** (DESIGN.md §13): ``get_model`` resolves the
caller's :class:`~repro.core.policy.MixerPolicy` to concrete
:class:`~repro.core.dispatch.MixerPlan`s exactly once, here at build — one
plan for the differentiated (loss) path, one for inference — and the model
closures hand those plans to the forwards. Traced step functions never
consult the backend registry; ``m.plans`` exposes the resolved plans for
observability (the serving engine reports ``plans["infer"].describe()``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

# nominal token count used to resolve plans when the caller gives no
# seq_len hint (plan *validity* never depends on it — kernels pad/clip —
# only tile-size choices do)
DEFAULT_TOKENS_HINT = 4096


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    loss: Callable[..., jax.Array]
    forward: Callable[..., Any]
    prefill: Optional[Callable[..., Any]] = None
    decode_step: Optional[Callable[..., Any]] = None
    init_caches: Optional[Callable[..., Any]] = None
    # insertion prefill (params, batch, cache, slots, *, capacity) ->
    # (logits, cache): prefill a (small) request batch and scatter its state
    # into the live slot pool — the continuous-batching serving contract
    # (DESIGN.md §4). None for families without a slot-pool serving path.
    prefill_into: Optional[Callable[..., Any]] = None
    # suffix prefill (params, batch, caches) -> (logits, caches): continue
    # an existing cache by batch["tokens"] suffix rows at batch["offsets"]
    # — the prefix-cache hit path (DESIGN.md §4 "Prefix cache"). Only set
    # for families whose cache is position-addressable history (gqa/mla,
    # unwindowed); None disables prefix caching for the family.
    prefill_suffix: Optional[Callable[..., Any]] = None
    # resolved mixer plans ({"train": ..., "infer": ...}) for FLARE-mixing
    # families; empty for pure-attention/SSM families
    plans: Mapping[str, Any] = field(default_factory=dict)
    # (params, batch) -> (loss, {name: scalar}): the loss with per-step
    # device counters (MoE routing) that a train step returns beside its
    # metrics; None where the model counts nothing
    loss_counters: Optional[Callable[..., Any]] = None


def make_prefill_into(prefill, init_caches):
    """Generic insertion prefill: run the family prefill on the request
    batch (right-padded bucket + "lengths"), then scatter the per-request
    cache lanes into the pool at ``slots`` (serve.cache slot-axis discovery
    keeps this family-agnostic). The legacy ``prefill`` contract (mint a
    fresh full-batch cache) stays untouched as the compat path — the serve
    engine builds this same adapter (with a DeprecationWarning) for models
    that ship only ``prefill``. Paged pools route through
    ``serve.pool.PagedModelCache.make_prefill_into`` instead (the token
    leaves land in block storage, not slot lanes — DESIGN.md §4)."""

    def prefill_into(params, batch, cache, slots, *, capacity):
        from repro.serve.cache import insert_slots, slot_axes

        logits, part = prefill(params, batch, capacity)
        return logits, insert_slots(cache, part, slots,
                                    slot_axes(init_caches, capacity))

    return prefill_into


def _mixer_shape(cfg: ModelConfig, family: str, seq_len_hint: Optional[int]):
    from repro.core.dispatch import MixerShape

    if family == "flare_lm":
        heads, latents = cfg.attn.num_heads, cfg.attn.flare_latents
        head_dim = cfg.d_model // heads
    elif family == "encdec":
        heads = cfg.flare_heads or cfg.attn.num_heads
        latents = cfg.flare_latents or 256
        head_dim = cfg.d_model // heads
    else:  # pde
        heads, latents = cfg.flare_heads, cfg.flare_latents
        head_dim = cfg.d_model // heads
    return MixerShape(batch=1, heads=heads, tokens=seq_len_hint or DEFAULT_TOKENS_HINT,
                      latents=latents, head_dim=head_dim)


def _resolve_plans(cfg: ModelConfig, policy, *, family: str, causal: bool,
                   mesh=None, seq_len_hint: Optional[int] = None):
    """The build-time resolve step: policy -> ({"infer": plan[, "train":
    plan]}, train_resolve_error).

    The train plan is always resolved with requires_grad=True (regardless of
    how the policy was spelled), so a training step can never land on a
    forward-only kernel; the infer plan honors the policy as given. A policy
    that *cannot* satisfy the grad contract (it names only forward-only
    backends) is still fine for inference-only use: the build succeeds with
    no train plan and ``model.loss`` raises the recorded resolve error —
    never a silent fallback onto a different backend.
    """
    from repro.core.dispatch import MixerPlan
    from repro.core.policy import MixerPolicy, resolve_policy

    shape = _mixer_shape(cfg, family, seq_len_hint)
    dtype = jnp.dtype(cfg.compute_dtype) if family != "pde" else jnp.float32
    infer = resolve_policy(policy, shape, dtype, causal=causal, mesh=mesh)
    try:
        train = resolve_policy(policy, shape, dtype, causal=causal, mesh=mesh,
                               requires_grad=True)
        train_error = None
    except ValueError as e:
        train, train_error = None, e
    if causal:
        # the cfg chunk drives the causal scan unless the policy pinned one
        chunk = None
        if isinstance(policy, MixerPolicy):
            chunk = policy.chunk_size
        chunk = chunk or cfg.attn.flare_chunk
        infer = MixerPlan(infer.backend, {**infer.params, "chunk_size": chunk})
        if train is not None:
            train = MixerPlan(train.backend, {**train.params, "chunk_size": chunk})
    plans = {"infer": infer}
    if train is not None:
        plans["train"] = train
    return plans, train_error


def _train_guard(loss_fn, train_error):
    """Wrap a loss closure so an inference-only policy errors loudly (with
    the original resolve reason) the moment training is attempted."""
    if train_error is None:
        return loss_fn

    def _raise(p, b):
        raise ValueError(
            "this model was built with an inference-only mixer policy and "
            f"cannot train: {train_error}")

    return _raise


def get_model(cfg: ModelConfig, *, policy=None, mesh=None,
              seq_len_hint: Optional[int] = None) -> Model:
    """policy: FLARE mixer-dispatch request — a MixerPolicy, a pre-resolved
    MixerPlan (e.g. from dispatch.sharded_plan), or None for the ambient
    policy stack. Resolved HERE, once; the returned model's step functions
    carry the plans and never re-resolve. ``mesh``/``seq_len_hint`` feed
    resolution (sharded-backend selection, tile autotuning)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "flare_lm"):
        from repro.models import transformer as t

        # only the FLARE family resolves mixer plans; gqa/mla families keep
        # their own attention-impl vocabulary (models.attention.attn_sdpa)
        plans, train_error = (_resolve_plans(cfg, policy, family="flare_lm",
                                             causal=True, mesh=mesh,
                                             seq_len_hint=seq_len_hint)
                              if fam == "flare_lm" else ({}, None))
        train_plan = plans.get("train")
        infer_plan = plans.get("infer")

        def _fwd(p, b):
            # public API: slice the TP-padded vocab back to the true vocab
            logits, aux = t.lm_forward(p, b, cfg, mixer_plan=infer_plan)
            return logits[..., : cfg.vocab], aux

        lm_prefill = lambda p, b, cap: t.lm_prefill(p, b, cfg, cap,
                                                    mixer_plan=infer_plan)
        lm_caches = lambda bs, cap: t.init_lm_caches(bs, cfg, cap)
        # prefix-cache suffix path: only where the cache is stable,
        # position-addressable history (unwindowed gqa/mla over token ids)
        lm_suffix = None
        loss_counters = None
        if cfg.moe is not None:
            loss_counters = lambda p, b: t.lm_loss_and_counters(
                p, b, cfg, mixer_plan=train_plan)
        if (cfg.attn.kind in ("gqa", "mla") and cfg.attn.sliding_window is None
                and not cfg.inputs_are_embeddings):
            lm_suffix = lambda p, b, c: t.lm_prefill_suffix(p, b, c, cfg)
        return Model(
            cfg=cfg,
            init=lambda key: t.init_lm(key, cfg),
            loss=_train_guard(
                lambda p, b: t.lm_loss(p, b, cfg, mixer_plan=train_plan),
                train_error),
            forward=_fwd,
            prefill=lm_prefill,
            decode_step=lambda p, tok, c: t.lm_decode_step(p, tok, c, cfg),
            init_caches=lm_caches,
            prefill_into=make_prefill_into(lm_prefill, lm_caches),
            prefill_suffix=lm_suffix,
            plans=plans,
            loss_counters=loss_counters,
        )
    if fam in ("encdec", "audio"):
        from repro.models import transformer as t

        plans, train_error = (_resolve_plans(cfg, policy, family="encdec",
                                             causal=False, mesh=mesh,
                                             seq_len_hint=seq_len_hint)
                              if cfg.encoder_mixer == "flare" else ({}, None))
        train_plan = plans.get("train")
        infer_plan = plans.get("infer")

        def _efwd(p, b):
            logits, aux = t.encdec_forward(p, b, cfg, mixer_plan=infer_plan)
            return logits[..., : cfg.vocab], aux

        return Model(
            cfg=cfg,
            init=lambda key: t.init_encdec(key, cfg),
            loss=_train_guard(
                lambda p, b: t.encdec_loss(p, b, cfg, mixer_plan=train_plan),
                train_error),
            forward=_efwd,
            prefill=lambda p, b, cap: t.encdec_prefill(p, b, cfg, cap,
                                                       mixer_plan=infer_plan),
            decode_step=lambda p, tok, c: t.encdec_decode_step(p, tok, c, cfg),
            init_caches=None,  # enc-dec caches come from prefill (need memory)
            plans=plans,
        )
    if fam == "ssm":
        from repro.models import rwkv_lm as r

        def _rfwd(p, b):
            logits, aux = r.rwkv_forward(p, b, cfg)
            return logits[..., : cfg.vocab], aux

        rwkv_prefill = lambda p, b, cap: r.rwkv_prefill(p, b, cfg, cap)
        rwkv_caches = lambda bs, cap: r.init_rwkv_caches(bs, cfg)
        return Model(
            cfg=cfg,
            init=lambda key: r.init_rwkv_lm(key, cfg),
            loss=lambda p, b: r.rwkv_loss(p, b, cfg),
            forward=_rfwd,
            prefill=rwkv_prefill,
            decode_step=lambda p, tok, c: r.rwkv_decode_step(p, tok, c, cfg),
            init_caches=rwkv_caches,
            prefill_into=make_prefill_into(rwkv_prefill, rwkv_caches),
        )
    if fam == "hybrid":
        from repro.models import zamba as z

        def _zfwd(p, b):
            logits, aux = z.zamba_forward(p, b, cfg)
            return logits[..., : cfg.vocab], aux

        zamba_prefill = lambda p, b, cap: z.zamba_prefill(p, b, cfg, cap)
        zamba_caches = lambda bs, cap: z.init_zamba_caches(bs, cfg, cap)
        return Model(
            cfg=cfg,
            init=lambda key: z.init_zamba(key, cfg),
            loss=lambda p, b: z.zamba_loss(p, b, cfg),
            forward=_zfwd,
            prefill=zamba_prefill,
            decode_step=lambda p, tok, c: z.zamba_decode_step(p, tok, c, cfg),
            init_caches=zamba_caches,
            prefill_into=make_prefill_into(zamba_prefill, zamba_caches),
        )
    if fam == "pde":
        from repro.models import pde

        def _init(key):
            return pde.init_surrogate(
                key, "flare", in_dim=3, out_dim=1, dim=cfg.d_model,
                num_blocks=cfg.num_layers, num_heads=cfg.flare_heads,
                num_latents=cfg.flare_latents,
            )

        plans, train_error = _resolve_plans(cfg, policy, family="pde",
                                            causal=False, mesh=mesh,
                                            seq_len_hint=seq_len_hint)
        train_plan = plans.get("train")
        return Model(
            cfg=cfg,
            init=_init,
            loss=_train_guard(
                lambda p, b: pde.surrogate_loss(p, b, num_heads=cfg.flare_heads,
                                                policy=train_plan,
                                                remat=cfg.remat),
                train_error),
            forward=lambda p, b: pde.surrogate_forward(p, b["x"],
                                                       num_heads=cfg.flare_heads,
                                                       policy=plans["infer"]),
            plans=plans,
        )
    raise ValueError(f"unknown family {fam!r}")

"""Decoder-only LM and encoder-decoder assemblies.

Layers are *stacked* (leaves get a leading [L] axis) and executed with
``jax.lax.scan`` so the HLO stays small for 64-80 layer configs; remat is a
``jax.checkpoint`` policy around the scanned body. Heterogeneous stacks
(deepseek's leading dense FFN layer, zamba's shared-attention interleave)
are composed from multiple scans.

Initializers are pure jnp, so ``jax.eval_shape`` gives allocation-free
parameter trees for the dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core.flare import init_flare_layer
from repro.core.flare_stream import (
    stream_append,
    stream_init,
)
from repro.models.attention import (
    KVCache,
    MLACache,
    gqa_decode,
    gqa_extend,
    gqa_forward,
    init_gqa,
    init_kv_cache,
    init_mla,
    init_mla_cache,
    mla_decode,
    mla_extend,
    mla_forward,
    prefill_kv_cache,
    prefill_mla_cache,
)
from repro.models.moe import MoEStats, init_moe, moe_ffn
from repro.models.rope import text_mrope_positions, text_positions
from repro.nn.modules import (
    dense,
    init_dense,
    init_embedding,
    init_layernorm,
    init_resmlp,
    init_rmsnorm,
    init_swiglu,
    layernorm,
    resmlp,
    rmsnorm,
    swiglu,
)


def _norm_init(cfg: ModelConfig, dim, param_dtype):
    if cfg.norm == "rmsnorm":
        return init_rmsnorm(dim, param_dtype=param_dtype)
    return init_layernorm(dim, param_dtype=param_dtype)


def _norm_apply(cfg: ModelConfig, params, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(params, x, eps=cfg.norm_eps)
    return layernorm(params, x, eps=cfg.norm_eps)


def stack_layers(init_fn, key, n: int):
    """Initialize n layers and stack each leaf along a new [L] axis."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _constrain_bhsd(x: jax.Array) -> jax.Array:
    """Pin [B, H, S, D] attention tensors: B over (pod, data), H over model.

    Needed inside the enc-dec decoder scan, where the cross-attention K/V
    derive from a closure constant and GSPMD otherwise falls back to full
    replication ('involuntary full rematerialization', peak ~ O(global
    microbatch)); see EXPERIMENTS.md §Perf seamless note.
    """
    try:
        from jax.sharding import PartitionSpec as P

        am = jax.sharding.get_abstract_mesh()
        if am is None or not am.axis_names:
            return x
        sizes = dict(zip(am.axis_names, am.axis_sizes))
        fsdp = tuple(a for a in ("pod", "data") if a in am.axis_names)
        if not fsdp or x.shape[0] % _mesh_size(am, fsdp):
            return x
        h_ax = "model" if ("model" in sizes and x.shape[1] % sizes["model"] == 0) else None
        return jax.lax.with_sharding_constraint(x, P(fsdp, h_ax, None, None))
    except Exception:  # pragma: no cover — conservative fallback
        return x


def _constrain_batch(x: jax.Array) -> jax.Array:
    """Pin dim 0 to the batch/FSDP mesh axes when tracing under a mesh.

    Used on tensors that cross a scan boundary as closure constants (the
    enc-dec cross-attention memory): without the pin, GSPMD can hit an
    'involuntary full rematerialization' and replicate score-scale tensors
    (EXPERIMENTS.md §Perf, seamless note). No-op outside a mesh context.
    """
    try:
        from jax.sharding import PartitionSpec as P

        am = jax.sharding.get_abstract_mesh()
        if am is None or not am.axis_names:
            return x
        fsdp = tuple(a for a in ("pod", "data") if a in am.axis_names)
        if not fsdp or x.shape[0] % _mesh_size(am, fsdp):
            return x
        return jax.lax.with_sharding_constraint(x, P(fsdp, *([None] * (x.ndim - 1))))
    except Exception:  # pragma: no cover — conservative fallback
        return x


def _mesh_size(am, axes) -> int:
    n = 1
    for a in axes:
        n *= dict(zip(am.axis_names, am.axis_sizes))[a]
    return n


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save nothing


# ---------------------------------------------------------------------------
# Decoder-only LM (dense GQA / MLA / MoE / flare_stream mixers)
# ---------------------------------------------------------------------------


def _param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


VOCAB_PAD_MULTIPLE = 256


def padded_vocab(vocab: int) -> int:
    """Round the vocab up to a TP-friendly multiple (Megatron-style). A
    non-divisible vocab leaves the logits REPLICATED on the model axis —
    seamless's 256206 vocab cost ~124 GiB/device of fp32 logits copies
    before padding (EXPERIMENTS.md §Perf, vocab-padding fix)."""
    return -(-vocab // VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


def mask_padded_logits(logits: jax.Array, vocab: int) -> jax.Array:
    """-inf the padded tail so it is invisible to softmax/logsumexp/argmax."""
    vp = logits.shape[-1]
    if vp == vocab:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0)
    return jnp.where(col < vocab, logits, -jnp.inf)


def init_decoder_layer(key, cfg: ModelConfig) -> dict:
    pd = _param_dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"norm1": _norm_init(cfg, cfg.d_model, pd), "norm2": _norm_init(cfg, cfg.d_model, pd)}
    if cfg.attn.kind == "gqa":
        p["attn"] = init_gqa(k1, cfg.attn, cfg.d_model, param_dtype=pd)
    elif cfg.attn.kind == "mla":
        p["attn"] = init_mla(k1, cfg.attn, cfg.d_model, param_dtype=pd)
    elif cfg.attn.kind == "flare_stream":
        p["attn"] = init_flare_layer(
            k1, cfg.d_model, cfg.attn.num_heads, cfg.attn.flare_latents, param_dtype=pd
        )
    else:
        raise ValueError(cfg.attn.kind)
    if cfg.moe is not None:
        p["mlp"] = init_moe(k2, cfg.moe, cfg.d_model, param_dtype=pd)
    else:
        p["mlp"] = init_swiglu(k2, cfg.d_model, cfg.d_ff, param_dtype=pd)
    return p


def init_dense_ffn_layer(key, cfg: ModelConfig) -> dict:
    """Like init_decoder_layer but forces a dense FFN (deepseek layer 0)."""
    pd = _param_dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"norm1": _norm_init(cfg, cfg.d_model, pd), "norm2": _norm_init(cfg, cfg.d_model, pd)}
    p["attn"] = (init_mla if cfg.attn.kind == "mla" else init_gqa)(k1, cfg.attn, cfg.d_model, param_dtype=pd)
    p["mlp"] = init_swiglu(k2, cfg.d_model, cfg.d_ff, param_dtype=pd)
    return p


def _flare_stream_mix(layer, x, cfg: ModelConfig, *, plan=None):
    """Causal FLARE as an LM mixer (chunked training path). ``plan`` is the
    MixerPlan resolved once at model build (models.api.get_model); executing
    it here is a registry dict lookup, never a re-resolve. When called bare
    (plan=None) the ambient MixerPolicy stack resolves at trace time."""
    from repro.core.dispatch import MixerShape
    from repro.core.flare import _merge_heads, _split_heads  # layout helpers
    from repro.core.policy import ensure_plan, run_plan

    h = cfg.attn.num_heads
    k = _split_heads(resmlp(layer["k_proj"], x), h)
    v = _split_heads(resmlp(layer["v_proj"], x), h)
    q = layer["q_latent"].astype(x.dtype)
    plan = ensure_plan(plan, MixerShape.from_qkv(q, k), k.dtype, causal=True,
                       chunk_size=cfg.attn.flare_chunk)
    y = run_plan(plan, q, k, v)
    return dense(layer["out_proj"], _merge_heads(y))


def decoder_layer_forward(layer, x, cfg: ModelConfig, *, positions, moe_cfg=None,
                          dense_ffn: bool = False, impl: str = "auto",
                          mixer_plan=None):
    """One pre-norm block. Returns (x, MoEStats; zeros without routed
    experts). ``impl`` is the SDPA vocabulary ("auto" | "xla" |
    "chunked" | "pallas") for the gqa/mla
    attention paths; ``mixer_plan`` is the resolved FLARE MixerPlan for
    flare_stream layers — the two dispatch vocabularies are no longer
    conflated into one threaded kwarg."""
    stats = MoEStats.zero()
    xin = _norm_apply(cfg, layer["norm1"], x)
    if cfg.attn.kind == "gqa":
        a = gqa_forward(layer["attn"], xin, cfg.attn, positions=positions, causal=True, impl=impl)
    elif cfg.attn.kind == "mla":
        a = mla_forward(layer["attn"], xin, cfg.attn, positions=positions, causal=True, impl=impl)
    else:  # flare_stream
        a = _flare_stream_mix(layer["attn"], xin, cfg, plan=mixer_plan)
    x = x + a
    xin = _norm_apply(cfg, layer["norm2"], x)
    if cfg.moe is not None and not dense_ffn:
        m, stats = moe_ffn(layer["mlp"], xin, cfg.moe)
    else:
        m = swiglu(layer["mlp"], xin)
    return x + m, stats


def init_lm(key, cfg: ModelConfig) -> dict:
    pd = _param_dtype(cfg)
    keys = jax.random.split(key, 4)
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    n_scan = cfg.num_layers - n_dense
    params = {
        "embed": init_embedding(keys[0], padded_vocab(cfg.vocab), cfg.d_model, param_dtype=pd),
        "final_norm": _norm_init(cfg, cfg.d_model, pd),
        "layers": stack_layers(lambda k: init_decoder_layer(k, cfg), keys[1], n_scan),
    }
    if n_dense:
        params["dense_layers"] = stack_layers(lambda k: init_dense_ffn_layer(k, cfg), keys[2], n_dense)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[3], cfg.d_model, padded_vocab(cfg.vocab), param_dtype=pd)
    return params


def _embed_inputs(params, batch, cfg: ModelConfig):
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.inputs_are_embeddings:
        x = batch["embeds"].astype(cd)
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = params["embed"]["table"].astype(cd)[tokens]
    if "positions" in batch:
        positions = batch["positions"]
    elif cfg.attn.mrope_sections is not None:
        positions = text_mrope_positions(b, s)
    else:
        positions = text_positions(b, s)
    return x, positions


def _lm_logits(params, batch, cfg: ModelConfig, *, impl: str, mixer_plan):
    """Full-sequence forward -> (logits fp32 [B,S,V], MoEStats over the
    layers)."""
    x, positions = _embed_inputs(params, batch, cfg)

    def body_for(dense_ffn):
        def body(carry, layer):
            x, st = carry
            x, a = decoder_layer_forward(layer, x, cfg, positions=positions,
                                         dense_ffn=dense_ffn, impl=impl,
                                         mixer_plan=mixer_plan)
            return (x, st.merge(a)), None
        return body

    st = MoEStats.zero()
    if "dense_layers" in params:
        (x, st), _ = jax.lax.scan(_remat(body_for(True), cfg.remat), (x, st),
                                  params["dense_layers"])
    (x, st), _ = jax.lax.scan(_remat(body_for(False), cfg.remat), (x, st), params["layers"])
    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(x.dtype).T
    else:
        logits = dense(params["lm_head"], x)
    logits = mask_padded_logits(logits.astype(jnp.float32), cfg.vocab)
    return logits, st


def lm_forward(params, batch, cfg: ModelConfig, *, impl: str = "auto",
               mixer_plan=None):
    """Full-sequence forward -> (logits fp32 [B,S,V], aux_loss)."""
    logits, st = _lm_logits(params, batch, cfg, impl=impl, mixer_plan=mixer_plan)
    return logits, st.aux


def lm_loss_and_counters(params, batch, cfg: ModelConfig, *, impl: str = "auto",
                         mixer_plan=None):
    """Next-token cross-entropy (labels = batch['labels']) plus the MoE
    balance loss times ``aux_alpha``, and the step's routing counters:
    ``moe.rows_held`` (token-slots routed to held experts, summed over the
    layers) and ``moe.load_max_over_mean`` (worst layer); none without
    routed experts."""
    from repro.core.policy import mixer_policy

    # the loss is the differentiated entry point: under a build-time plan the
    # grad contract was checked at resolve; for bare calls the policy scope
    # restricts ambient resolution to grad-capable mixers
    with mixer_policy(requires_grad=True):
        logits, st = _lm_logits(params, batch, cfg, impl=impl, mixer_plan=mixer_plan)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    if cfg.moe is None:
        return nll, {}
    return nll + cfg.moe.aux_alpha * st.aux, {
        "moe.rows_held": st.rows_held,
        "moe.load_max_over_mean": st.load_max_over_mean}


def lm_loss(params, batch, cfg: ModelConfig, *, impl: str = "auto",
            mixer_plan=None):
    """:func:`lm_loss_and_counters` without the counters."""
    return lm_loss_and_counters(params, batch, cfg, impl=impl, mixer_plan=mixer_plan)[0]


# ------------------------------- serving ----------------------------------


class LMCaches(NamedTuple):
    dense: Any          # stacked caches for the leading dense layers (or None)
    layers: Any         # stacked caches for the scanned layers
    pos: jax.Array      # [B] int32 next position, per sequence slot


def init_lm_caches(batch: int, cfg: ModelConfig, capacity: int) -> LMCaches:
    n_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    n_scan = cfg.num_layers - n_dense

    def one(_):
        if cfg.attn.kind == "gqa":
            return init_kv_cache(batch, cfg.attn, capacity)
        if cfg.attn.kind == "mla":
            return init_mla_cache(batch, cfg.attn, capacity)
        return stream_init(batch, cfg.attn.num_heads, cfg.attn.flare_latents,
                           cfg.d_model // cfg.attn.num_heads)

    stackn = lambda n: jax.tree.map(lambda *xs: jnp.stack(xs), *[one(i) for i in range(n)])
    return LMCaches(
        dense=stackn(n_dense) if n_dense else None,
        layers=stackn(n_scan),
        pos=jnp.zeros((batch,), jnp.int32),
    )


def _last_valid(x: jax.Array, lengths: Optional[jax.Array]) -> jax.Array:
    """x: [B, S, C] -> [B, 1, C] at each row's last REAL position (serving
    prefill right-pads prompts to a bucket; see DESIGN.md §4)."""
    if lengths is None:
        return x[:, -1:]
    idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)[:, None, None]
    return jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)


def _decode_positions(pos: jax.Array, b: int, mrope: bool):
    """Per-slot decode positions from the cache's [B] position vector
    (legacy scalar positions broadcast)."""
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    if mrope:
        return jnp.broadcast_to(pos[None, :, None], (3, b, 1))
    return pos[:, None]


def _layer_decode(layer, x, cfg: ModelConfig, cache, *, positions, dense_ffn=False):
    xin = _norm_apply(cfg, layer["norm1"], x)
    if cfg.attn.kind == "gqa":
        a, cache = gqa_decode(layer["attn"], xin, cfg.attn, cache, positions=positions)
    elif cfg.attn.kind == "mla":
        a, cache = mla_decode(layer["attn"], xin, cfg.attn, cache, positions=positions)
    else:  # flare_stream: single-token append
        from repro.core.flare import _merge_heads, _split_heads

        fl = layer["attn"]
        h = cfg.attn.num_heads
        k = _split_heads(resmlp(fl["k_proj"], xin), h)[:, :, 0]
        v = _split_heads(resmlp(fl["v_proj"], xin), h)[:, :, 0]
        cache, y = stream_append(cache, fl["q_latent"].astype(x.dtype), k, v)
        a = dense(fl["out_proj"], y.reshape(y.shape[0], 1, -1))
    x = x + a
    xin = _norm_apply(cfg, layer["norm2"], x)
    if cfg.moe is not None and not dense_ffn:
        m, _ = moe_ffn(layer["mlp"], xin, cfg.moe)
    else:
        m = swiglu(layer["mlp"], xin)
    return x + m, cache


def lm_decode_step(params, token, caches: LMCaches, cfg: ModelConfig):
    """One-token decode. token: [B, 1] int32 -> (logits [B, V], caches).

    ``caches`` may also be a :class:`repro.serve.pool.views.PagedCacheView`
    (the block-paged pool, DESIGN.md §4): decode reads then route through
    the view adapter — dense gather on entry, single-token write-back on
    exit — with the decode math below untouched."""
    from repro.serve.pool.views import resolve_cache_view

    caches, writeback = resolve_cache_view(caches)
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.inputs_are_embeddings:
        x = token.astype(cd)  # [B, 1, C] embeddings passed directly
        b = x.shape[0]
    else:
        b = token.shape[0]
        x = params["embed"]["table"].astype(cd)[token]
    positions = _decode_positions(caches.pos, b, cfg.attn.mrope_sections is not None)

    def body(x, inp):
        layer, cache = inp
        x, cache = _layer_decode(layer, x, cfg, cache, positions=positions)
        return x, cache

    if caches.dense is not None:
        def dense_body(x, inp):
            layer, cache = inp
            x, cache = _layer_decode(layer, x, cfg, cache, positions=positions, dense_ffn=True)
            return x, cache

        x, new_dense = jax.lax.scan(dense_body, x, (params["dense_layers"], caches.dense))
    else:
        new_dense = None
    x, new_caches = jax.lax.scan(body, x, (params["layers"], caches.layers))
    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(x.dtype).T
    else:
        logits = dense(params["lm_head"], x)
    logits = mask_padded_logits(logits[:, 0].astype(jnp.float32), cfg.vocab)
    return (logits[:, : cfg.vocab],
            writeback(LMCaches(new_dense, new_caches, caches.pos + 1)))


def lm_prefill(params, batch, cfg: ModelConfig, capacity: int, *, impl: str = "auto",
               mixer_plan=None):
    """Run the full prompt, return (last-token logits [B, V], populated caches).

    ``batch["lengths"]`` ([B] int32, optional): true prompt lengths when the
    token array is a right-padded serving bucket (DESIGN.md §4). Causality
    keeps real positions exact under right-padding; the mask only has to keep
    padded positions out of the carried stream states, cache lengths, and
    the returned logits (taken at each row's last real position).

    ``mixer_plan`` is accepted for API symmetry; the flare_stream prefill is
    the *stateful* chunked path (it must return the latent state for decode),
    which is pinned to flare_causal_with_state rather than registry-run."""
    lengths = batch.get("lengths")
    x, positions = _embed_inputs(params, batch, cfg)
    b, s = x.shape[:2]
    mask = None
    if lengths is not None:
        mask = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1) < lengths[:, None]

    def body(x, layer):
        xin = _norm_apply(cfg, layer["norm1"], x)
        if cfg.attn.kind == "gqa":
            a, (k, v) = gqa_forward(layer["attn"], xin, cfg.attn, positions=positions,
                                    causal=True, impl=impl, return_kv=True)
            cache = prefill_kv_cache(k, v, cfg.attn, capacity, lengths)
        elif cfg.attn.kind == "mla":
            a, (ckv, kr) = mla_forward(layer["attn"], xin, cfg.attn, positions=positions,
                                       causal=True, impl=impl, return_kv=True)
            cache = prefill_mla_cache(ckv, kr, capacity, lengths)
        else:  # flare_stream: chunked causal prefill, keep final latent state
            from repro.core.flare import _merge_heads, _split_heads
            from repro.core.flare_stream import flare_causal_with_state

            fl = layer["attn"]
            h = cfg.attn.num_heads
            k = _split_heads(resmlp(fl["k_proj"], xin), h)
            v = _split_heads(resmlp(fl["v_proj"], xin), h)
            q = fl["q_latent"].astype(x.dtype)
            st, y = flare_causal_with_state(q, k, v, chunk_size=cfg.attn.flare_chunk,
                                            mask=mask)
            a = dense(fl["out_proj"], _merge_heads(y))
            cache = st
        x = x + a
        xin = _norm_apply(cfg, layer["norm2"], x)
        if cfg.moe is not None:
            m, _ = moe_ffn(layer["mlp"], xin, cfg.moe)
        else:
            m = swiglu(layer["mlp"], xin)
        return x + m, cache

    # NB: heterogeneous stacks prefill their dense layers through the same
    # body (mlp dispatch is per-params); configs with first_dense_layers use
    # separate stacks:
    if "dense_layers" in params:
        def dense_prefill_body(x, layer):
            xin = _norm_apply(cfg, layer["norm1"], x)
            a, (ckv, kr) = mla_forward(layer["attn"], xin, cfg.attn, positions=positions,
                                       causal=True, impl=impl, return_kv=True)
            cache = prefill_mla_cache(ckv, kr, capacity, lengths)
            x = x + a
            x = x + swiglu(layer["mlp"], _norm_apply(cfg, layer["norm2"], x))
            return x, cache

        x, dense_caches = jax.lax.scan(dense_prefill_body, x, params["dense_layers"])
    else:
        dense_caches = None
    x, layer_caches = jax.lax.scan(body, x, params["layers"])
    x = _norm_apply(cfg, params["final_norm"], _last_valid(x, lengths))
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(x.dtype).T
    else:
        logits = dense(params["lm_head"], x)
    logits = logits[:, 0, : cfg.vocab].astype(jnp.float32)
    pos = jnp.full((b,), s, jnp.int32) if lengths is None else lengths
    return logits, LMCaches(dense_caches, layer_caches, pos)


def lm_prefill_suffix(params, batch, caches: LMCaches, cfg: ModelConfig):
    """Prefix-cache suffix prefill (DESIGN.md §4 "Prefix cache"): ``caches``
    already holds each row's shared prompt prefix (``batch["offsets"]`` [B]
    tokens, gathered from block storage by the serve pool); run ONLY the
    suffix tokens — width-S cache-extend attention at absolute positions
    ``offset + i`` — and return (last-real-token logits, caches advanced to
    the full prompt length). ``batch["tokens"]`` is a right-padded suffix
    bucket with true lengths ``batch["lengths"]``.

    gqa/mla only: FLARE streams and rwkv/ssm recurrences are dense
    token-order states that cannot be reconstructed from a shared block
    range, so those families keep the full-prompt path (``models/api.py``
    leaves their ``prefill_suffix`` unset)."""
    if cfg.attn.kind not in ("gqa", "mla"):
        raise ValueError(f"prefill_suffix supports gqa/mla, not {cfg.attn.kind!r}")
    lengths = batch["lengths"]
    offsets = batch["offsets"]
    tokens = batch["tokens"]
    b, s = tokens.shape
    cd = jnp.dtype(cfg.compute_dtype)
    x = params["embed"]["table"].astype(cd)[tokens]
    pos2d = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    if cfg.attn.mrope_sections is not None:
        positions = jnp.broadcast_to(pos2d[None], (3, b, s))
    else:
        positions = pos2d
    ext = gqa_extend if cfg.attn.kind == "gqa" else mla_extend

    def body_for(dense_ffn):
        def body(x, inp):
            layer, cache = inp
            xin = _norm_apply(cfg, layer["norm1"], x)
            a, cache = ext(layer["attn"], xin, cfg.attn, cache,
                           positions=positions, offsets=offsets, lengths=lengths)
            x = x + a
            xin = _norm_apply(cfg, layer["norm2"], x)
            if cfg.moe is not None and not dense_ffn:
                m, _ = moe_ffn(layer["mlp"], xin, cfg.moe)
            else:
                m = swiglu(layer["mlp"], xin)
            return x + m, cache

        return body

    if caches.dense is not None:
        x, dense_caches = jax.lax.scan(body_for(True), x,
                                       (params["dense_layers"], caches.dense))
    else:
        dense_caches = None
    x, layer_caches = jax.lax.scan(body_for(False), x,
                                   (params["layers"], caches.layers))
    x = _norm_apply(cfg, params["final_norm"], _last_valid(x, lengths))
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(x.dtype).T
    else:
        logits = dense(params["lm_head"], x)
    logits = logits[:, 0, : cfg.vocab].astype(jnp.float32)
    return logits, LMCaches(dense_caches, layer_caches, offsets + lengths)


# ---------------------------------------------------------------------------
# Encoder-decoder (seamless-m4t backbone)
# ---------------------------------------------------------------------------


def init_encoder_layer(key, cfg: ModelConfig) -> dict:
    pd = _param_dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"norm1": _norm_init(cfg, cfg.d_model, pd), "norm2": _norm_init(cfg, cfg.d_model, pd)}
    if cfg.encoder_mixer == "flare":
        p["attn"] = init_flare_layer(k1, cfg.d_model, cfg.flare_heads or cfg.attn.num_heads,
                                     cfg.flare_latents or 256, param_dtype=pd)
    else:
        p["attn"] = init_gqa(k1, cfg.attn, cfg.d_model, param_dtype=pd)
    p["mlp"] = init_swiglu(k2, cfg.d_model, cfg.d_ff, param_dtype=pd)
    return p


def init_crossdec_layer(key, cfg: ModelConfig) -> dict:
    pd = _param_dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": _norm_init(cfg, cfg.d_model, pd),
        "self_attn": init_gqa(k1, cfg.attn, cfg.d_model, param_dtype=pd),
        "norm_x": _norm_init(cfg, cfg.d_model, pd),
        "cross_attn": init_gqa(k2, cfg.attn, cfg.d_model, param_dtype=pd),
        "norm2": _norm_init(cfg, cfg.d_model, pd),
        "mlp": init_swiglu(k3, cfg.d_model, cfg.d_ff, param_dtype=pd),
    }


def init_encdec(key, cfg: ModelConfig) -> dict:
    pd = _param_dtype(cfg)
    keys = jax.random.split(key, 5)
    return {
        "embed": init_embedding(keys[0], padded_vocab(cfg.vocab), cfg.d_model, param_dtype=pd),
        "encoder": stack_layers(lambda k: init_encoder_layer(k, cfg), keys[1], cfg.num_encoder_layers),
        "enc_norm": _norm_init(cfg, cfg.d_model, pd),
        "decoder": stack_layers(lambda k: init_crossdec_layer(k, cfg), keys[2], cfg.num_layers),
        "final_norm": _norm_init(cfg, cfg.d_model, pd),
        "lm_head": init_dense(keys[3], cfg.d_model, padded_vocab(cfg.vocab), param_dtype=pd),
    }


def encode(params, src_embeds, cfg: ModelConfig, *, impl: str = "auto",
           mixer_plan=None):
    """src_embeds: [B, S, C] from the (stubbed) modality frontend.

    ``impl`` drives the dense-attention path; ``mixer_plan`` is the resolved
    FLARE MixerPlan for FLARE encoder stacks (None = ambient policy)."""
    from repro.core.flare import flare_layer

    x = src_embeds.astype(jnp.dtype(cfg.compute_dtype))
    positions = text_positions(x.shape[0], x.shape[1])

    def body(x, layer):
        xin = _norm_apply(cfg, layer["norm1"], x)
        if cfg.encoder_mixer == "flare":
            a = flare_layer(layer["attn"], xin, policy=mixer_plan)
        else:
            a = gqa_forward(layer["attn"], xin, cfg.attn, positions=positions,
                            causal=False, impl=impl)
        x = x + a
        x = x + swiglu(layer["mlp"], _norm_apply(cfg, layer["norm2"], x))
        return x, None

    x, _ = jax.lax.scan(_remat(body, cfg.remat), x, params["encoder"])
    return _constrain_batch(_norm_apply(cfg, params["enc_norm"], x))


def _precompute_cross_kv(params, memory, cfg: ModelConfig):
    """All decoder layers' cross-attention K/V in one shot, OUTSIDE the scan.

    Keeps the memory-derived tensors on the standard batch/head sharding
    (computing them inside the scan body from the closure constant trips
    GSPMD into full replication — peak ~ O(global microbatch); see
    EXPERIMENTS.md §Perf seamless note). Also the classic enc-dec serving
    optimization: the cross K/V are position-independent.
    """
    from repro.models.attention import _heads
    from repro.models.rope import apply_rope, rope_angles

    a = cfg.attn
    mem_pos = text_positions(memory.shape[0], memory.shape[1])
    ang = rope_angles(mem_pos, a.head_dim, a.rope_theta)

    def one_layer(wk, bk, wv, bv):
        k = memory @ wk.astype(memory.dtype)
        v = memory @ wv.astype(memory.dtype)
        if bk is not None:
            k = k + bk.astype(memory.dtype)
            v = v + bv.astype(memory.dtype)
        k = _heads(k, a.num_kv_heads)
        v = _heads(v, a.num_kv_heads)
        return apply_rope(k, ang), v

    ca = params["decoder"]["cross_attn"]
    kx, vx = jax.vmap(one_layer)(ca["wk"]["kernel"], ca["wk"].get("bias"),
                                 ca["wv"]["kernel"], ca["wv"].get("bias"))
    return kx, vx  # [L, B, Hkv, S, D] each


def encdec_forward(params, batch, cfg: ModelConfig, *, impl: str = "auto",
                   mixer_plan=None):
    """Teacher-forced training forward -> (logits, aux=0)."""
    memory = encode(params, batch["embeds"], cfg, impl=impl, mixer_plan=mixer_plan)
    cd = jnp.dtype(cfg.compute_dtype)
    y = params["embed"]["table"].astype(cd)[batch["tokens"]]
    positions = text_positions(y.shape[0], y.shape[1])
    kx, vx = _precompute_cross_kv(params, memory, cfg)

    def body(y, inp):
        layer, k_l, v_l = inp
        a = gqa_forward(layer["self_attn"], _norm_apply(cfg, layer["norm1"], y),
                        cfg.attn, positions=positions, causal=True, impl=impl)
        y = y + a
        # cross-attention: queries from decoder, precomputed memory K/V
        a = _cross_attend_kv(layer["cross_attn"], _norm_apply(cfg, layer["norm_x"], y),
                             k_l, v_l, cfg, positions, impl)
        y = y + a
        y = y + swiglu(layer["mlp"], _norm_apply(cfg, layer["norm2"], y))
        return y, None

    y, _ = jax.lax.scan(_remat(body, cfg.remat), y, (params["decoder"], kx, vx))
    y = _norm_apply(cfg, params["final_norm"], y)
    logits = mask_padded_logits(dense(params["lm_head"], y).astype(jnp.float32), cfg.vocab)
    return logits, jnp.zeros((), jnp.float32)


def _cross_attend_kv(p, q_in, k, v, cfg: ModelConfig, q_pos, impl):
    """Cross-attention with precomputed (RoPE'd) memory K/V."""
    import math as _math

    from repro.models.attention import _expand_kv, _heads, _unheads, attn_sdpa
    from repro.models.rope import apply_rope, rope_angles

    a = cfg.attn
    q = _heads(dense(p["wq"], q_in), a.num_heads)
    q = apply_rope(q, rope_angles(q_pos, a.head_dim, a.rope_theta))
    g = a.num_heads // a.num_kv_heads
    out = attn_sdpa(q, _expand_kv(k, g), _expand_kv(v, g),
                    scale=1.0 / _math.sqrt(a.head_dim), causal=False, impl=impl)
    return dense(p["wo"], _unheads(out))


def _cross_attend(p, q_in, memory, cfg: ModelConfig, q_pos, kv_pos, impl):
    """Cross-attention built from the GQA projections (no causal mask)."""
    from repro.models.attention import _heads
    from repro.models.rope import apply_rope, rope_angles

    a = cfg.attn
    k = _heads(dense(p["wk"], memory), a.num_kv_heads)
    v = _heads(dense(p["wv"], memory), a.num_kv_heads)
    k = apply_rope(k, rope_angles(kv_pos, a.head_dim, a.rope_theta))
    return _cross_attend_kv(p, q_in, k, v, cfg, q_pos, impl)


def encdec_loss(params, batch, cfg: ModelConfig, *, impl: str = "auto",
                mixer_plan=None):
    from repro.core.policy import mixer_policy

    # the loss is the differentiated entry point: the requires_grad scope
    # keeps bare (plan-less) calls off forward-only mixers
    with mixer_policy(requires_grad=True):
        logits, _ = encdec_forward(params, batch, cfg, impl=impl,
                                   mixer_plan=mixer_plan)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


class EncDecCaches(NamedTuple):
    self_caches: Any      # stacked KVCache [L, ...]
    memory: jax.Array     # [B, S_src, C] encoder output
    pos: jax.Array        # [B] int32, per sequence slot


def encdec_prefill(params, batch, cfg: ModelConfig, capacity: int, *, impl: str = "auto",
                   mixer_plan=None):
    """Encode source; teacher-force the target prefix; return decode caches."""
    memory = encode(params, batch["embeds"], cfg, impl=impl, mixer_plan=mixer_plan)
    cd = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    y = params["embed"]["table"].astype(cd)[tokens]
    positions = text_positions(y.shape[0], y.shape[1])
    mem_pos = text_positions(memory.shape[0], memory.shape[1])

    def body(y, layer):
        a, (k, v) = gqa_forward(layer["self_attn"], _norm_apply(cfg, layer["norm1"], y),
                                cfg.attn, positions=positions, causal=True, impl=impl,
                                return_kv=True)
        cache = prefill_kv_cache(k, v, cfg.attn, capacity)
        y = y + a
        y = y + _cross_attend(layer["cross_attn"], _norm_apply(cfg, layer["norm_x"], y),
                              memory, cfg, positions, mem_pos, impl)
        y = y + swiglu(layer["mlp"], _norm_apply(cfg, layer["norm2"], y))
        return y, cache

    y, caches = jax.lax.scan(body, y, params["decoder"])
    y = _norm_apply(cfg, params["final_norm"], y[:, -1:])
    logits = dense(params["lm_head"], y)[:, 0, : cfg.vocab].astype(jnp.float32)
    pos = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    return logits, EncDecCaches(caches, memory, pos)


def encdec_decode_step(params, token, caches: EncDecCaches, cfg: ModelConfig):
    cd = jnp.dtype(cfg.compute_dtype)
    y = params["embed"]["table"].astype(cd)[token]  # [B, 1, C]
    b = y.shape[0]
    positions = _decode_positions(caches.pos, b, False)
    mem_pos = text_positions(caches.memory.shape[0], caches.memory.shape[1])

    def body(y, inp):
        layer, cache = inp
        a, cache = gqa_decode(layer["self_attn"], _norm_apply(cfg, layer["norm1"], y),
                              cfg.attn, cache, positions=positions)
        y = y + a
        y = y + _cross_attend(layer["cross_attn"], _norm_apply(cfg, layer["norm_x"], y),
                              caches.memory, cfg, positions, mem_pos, "auto")
        y = y + swiglu(layer["mlp"], _norm_apply(cfg, layer["norm2"], y))
        return y, cache

    y, new_caches = jax.lax.scan(body, y, (params["decoder"], caches.self_caches))
    y = _norm_apply(cfg, params["final_norm"], y)
    logits = dense(params["lm_head"], y)[:, 0, : cfg.vocab].astype(jnp.float32)
    return logits, EncDecCaches(new_caches, caches.memory, caches.pos + 1)

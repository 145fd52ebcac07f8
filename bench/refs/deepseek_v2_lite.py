"""Plain reference of DeepSeek-V2-Lite training on one chip's share of a
layer: forward, next-token loss with the sequence-wise balance loss,
gradients and AdamW steps, in straightforward ``jax.numpy``.

It follows DeepSeek-V2 (arXiv:2405.04434) as its published config.json and
modeling code define it:

- pre-norm blocks ``x += MLA(RMSNorm x); x += FFN(RMSNorm x)``, RMSNorm at
  ``rms_norm_eps``; the first ``first_k_dense_replace`` blocks have a dense
  SwiGLU FFN, the others routed and shared experts;
- MLA without query compression: ``q = x W_q`` split per head into a
  no-position part and a rotary part; ``c = RMSNorm(x W_dkv)`` (the latent,
  ``kv_lora_rank`` wide) gives per-head ``k_nope = c W_uk`` and ``v = c W_uv``;
  one rotary key ``k_rope = x W_kr`` shared by the heads; causal softmax of
  ``(q_nope k_nope + q_rope k_rope) * scale`` over ``v``, then ``W_o``;
- YaRN rotary frequencies from the equations (``rope_scaling``): per
  rotary pair i, ``inv_freq = (theta^(-2i/d) / factor) ramp_i +
  theta^(-2i/d) (1 - ramp_i)``, the ramp linear between the correction
  dims that ``beta_fast`` and ``beta_slow`` give over
  ``original_max_position_embeddings``; cos and sin times
  ``m(mscale) / m(mscale_all_dim)``, the softmax scale
  ``(qk_nope + qk_rope)^-1/2 * m(mscale_all_dim)^2``, where
  ``m(a) = 0.1 a ln(factor) + 1``. Pairs are (2i, 2i+1), the layout of the
  published checkpoint's rotary weights;
- the router: softmax over all ``router_experts`` experts, top
  ``num_experts_per_tok``, gates renormalized only if ``norm_topk_prob``,
  times ``routed_scaling_factor``; every held expert runs over all tokens
  and is weighed by its gate (zero where not selected); the shared experts
  (``n_shared_experts`` x ``moe_intermediate_size`` wide) run on every token;
- the loss: mean next-token cross-entropy over the vocabulary slice, plus
  ``aux_loss_alpha`` times each MoE layer's sequence-wise balance loss
  ``sum_i f_i P_i`` (``f_i = E / (k S) * #slots choosing i``, ``P_i`` the
  sequence's mean routing probability), averaged over sequences.

Departures from the published model, as the configuration cuts it: 1 dense
and 4 MoE layers of 27; routed experts ``expert_offset`` ..
``expert_offset + n_routed_experts - 1`` held (what experts held on other
chips would add is left out, as on the chip); the vocabulary is the slice
of the first ``vocab_size`` ids with the head over that slice; no
multi-token prediction (V2-Lite has none). The weights are random from a
key: kernels normal with standard deviation ``fan_in^-1/2``, RMSNorm scales
``1 + N(0, 0.1)``, and the embedding N(0, 1), as PaLM (arXiv:2204.02311)
draws it, so that each token's embedding, and not the blocks' outputs, sets
the scale of the residual stream: with embeddings of norm 1 the attention
output, near the mean of a sequence's values at initialization, outweighs
them and routes every token of a sequence to the same experts. The
parameter tree has the nesting and leaf names of the system under
test (layers stacked on a leading axis), so the two compare leaf by leaf;
nothing here is imported from it.

It computes in float32 with every contraction at HIGHEST precision, one
sequence at a time, each layer and each head's attention under
``jax.checkpoint``; no kernel, sort or cache.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.refs.flare_pde import _hashable, adamw  # noqa: F401  (the same optimizer)

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


# ---------------------------------------------------------------- weights

def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _kernel(key, din, dout):
    return {"kernel": _normal(key, (din, dout), 1.0 / math.sqrt(din))}


def _norm(key, dim):
    return {"scale": 1.0 + _normal(key, (dim,), 0.1)}


def _attn_w(key, cfg):
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope, rope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    k = jax.random.split(key, 7)
    return {"w_q": _kernel(k[0], c, h * (nope + rope)),
            "w_dkv": _kernel(k[1], c, r), "kv_norm": _norm(k[2], r),
            "w_kr": _kernel(k[3], c, rope),
            "w_uk": _kernel(k[4], r, h * nope),
            "w_uv": _kernel(k[5], r, h * cfg["v_head_dim"]),
            "w_o": _kernel(k[6], h * cfg["v_head_dim"], c)}


def _swiglu_w(key, c, f):
    k = jax.random.split(key, 3)
    return {"w_gate": _kernel(k[0], c, f), "w_up": _kernel(k[1], c, f),
            "w_down": _kernel(k[2], f, c)}


def _dense_layer_w(key, cfg):
    c = cfg["hidden_size"]
    k = jax.random.split(key, 4)
    return {"norm1": _norm(k[0], c), "attn": _attn_w(k[1], cfg),
            "norm2": _norm(k[2], c), "mlp": _swiglu_w(k[3], c, cfg["intermediate_size"])}


def _moe_layer_w(key, cfg):
    c, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    k = jax.random.split(key, 8)
    return {"norm1": _norm(k[0], c), "attn": _attn_w(k[1], cfg), "norm2": _norm(k[2], c),
            "mlp": {"router": _kernel(k[3], c, cfg["router_experts"]),
                    "w_gate": _normal(k[4], (n, c, f), 1.0 / math.sqrt(c)),
                    "w_up": _normal(k[5], (n, c, f), 1.0 / math.sqrt(c)),
                    "w_down": _normal(k[6], (n, f, c), 1.0 / math.sqrt(f)),
                    "shared": _swiglu_w(k[7], c, cfg["n_shared_experts"] * f)}}


def _stack(layers):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def weights(key, cfg: dict) -> dict:
    """Seeded float32 weights for the sizes in ``cfg`` (the configuration
    file). Same key, same weights, whoever calls it."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    k = jax.random.split(key, 5)
    return {"embed": {"table": _normal(k[0], (v, c), 1.0)},
            "dense_layers": _stack([_dense_layer_w(kk, cfg)
                                    for kk in jax.random.split(k[1], n_dense)]),
            "layers": _stack([_moe_layer_w(kk, cfg) for kk in jax.random.split(k[2], n_moe)]),
            "final_norm": _norm(k[3], c),
            "lm_head": _kernel(k[4], c, v)}


# ---------------------------------------------------------------- forward

def _rmsnorm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"]["kernel"])) * _mm(x, p["w_up"]["kernel"]),
               p["w_down"]["kernel"])


def yarn_m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_low_high(cfg: dict) -> tuple[int, int]:
    """The correction dims of the rotary pairs: (10, 23) at the published
    config."""
    rs, d, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def dim(rot):
        return d * math.log(rs["original_max_position_embeddings"] / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    return max(math.floor(dim(rs["beta_fast"])), 0), min(math.ceil(dim(rs["beta_slow"])), d - 1)


def yarn_inv_freq(cfg: dict):
    rs, d, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    extra = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    low, high = yarn_low_high(cfg)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (extra / rs["factor"]) * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_m(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, cos, sin):
    """Rotate pairs (2i, 2i+1) of x [..., S, d] by per-position cos/sin [S, d/2]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def mla(p, x, cfg: dict):
    """x [S, C] -> [S, C]: causal multi-head latent attention."""
    s = x.shape[0]
    h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rs = cfg["rope_scaling"]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    m = yarn_m(rs["factor"], rs["mscale"]) / yarn_m(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    q = _mm(x, p["w_q"]["kernel"]).reshape(s, h, nope + rope).transpose(1, 0, 2)
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], cos, sin)
    c = _rmsnorm(p["kv_norm"], _mm(x, p["w_dkv"]["kernel"]), cfg["rms_norm_eps"])
    k_rope = _rotate(_mm(x, p["w_kr"]["kernel"]), cos, sin)            # [S, rope]
    k_nope = _mm(c, p["w_uk"]["kernel"]).reshape(s, h, nope).transpose(1, 0, 2)
    v = _mm(c, p["w_uv"]["kernel"]).reshape(s, h, dv).transpose(1, 0, 2)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def head(args):  # one head's [S, S] scores, recomputed in the backward
        qn, qr, kn, vh = args
        scores = _mm(qn, kn.T) + _mm(qr, k_rope.T)
        w = jax.nn.softmax(jnp.where(causal, scores * softmax_scale(cfg), -jnp.inf), axis=-1)
        return _mm(w, vh)

    out = jax.lax.map(jax.checkpoint(head), (q_nope, q_rope, k_nope, v))  # [H, S, dv]
    return _mm(out.transpose(1, 0, 2).reshape(s, h * dv), p["w_o"]["kernel"])


def router(p, x, cfg: dict):
    """x [S, C] -> (gates [S, E] with zeros off the top-k, probabilities
    [S, E], top-k ids [S, k])."""
    probs = jax.nn.softmax(_mm(x, p["router"]["kernel"]), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    gates = jnp.zeros_like(probs).at[rows, idx].set(top)
    return gates, probs, idx


def seq_aux(probs, idx, n_experts: int):
    """One sequence's balance loss sum_i f_i P_i."""
    s, k = idx.shape
    counts = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    return jnp.sum(counts * (n_experts / (k * s)) * jnp.mean(probs, axis=0))


def moe(p, x, cfg: dict):
    """x [S, C] -> (held experts' and shared experts' output [S, C], the
    sequence's balance loss)."""
    gates, probs, idx = router(p, x, cfg)
    off = cfg["expert_offset"]
    y = _swiglu(p["shared"], x)
    for e in range(cfg["n_routed_experts"]):
        h = jax.nn.silu(_mm(x, p["w_gate"][e])) * _mm(x, p["w_up"][e])
        y = y + gates[:, off + e, None] * _mm(h, p["w_down"][e])
    return y, seq_aux(probs, idx, cfg["router_experts"])


def _dense_block(p, x, cfg):
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["attn"], _rmsnorm(p["norm1"], x, eps), cfg)
    return x + _swiglu(p["mlp"], _rmsnorm(p["norm2"], x, eps))


def _moe_block(p, x, cfg):
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["attn"], _rmsnorm(p["norm1"], x, eps), cfg)
    y, aux = moe(p["mlp"], _rmsnorm(p["norm2"], x, eps), cfg)
    return x + y, aux


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def seq_forward(params, tokens, cfg: dict):
    """tokens [S] -> (logits [S, V], summed balance loss of the MoE layers)."""
    x = params["embed"]["table"][tokens]
    dense_block = jax.checkpoint(lambda p, x: _dense_block(p, x, cfg))
    moe_block = jax.checkpoint(lambda p, x: _moe_block(p, x, cfg))
    for i in range(cfg["first_k_dense_replace"]):
        x = dense_block(_layer(params["dense_layers"], i), x)
    aux = jnp.zeros((), jnp.float32)
    for i in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        x, a = moe_block(_layer(params["layers"], i), x)
        aux = aux + a
    x = _rmsnorm(params["final_norm"], x, cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"]), aux


def seq_loss(params, tokens, labels, cfg: dict):
    """One sequence's mean cross-entropy plus alpha times its balance loss."""
    logits, aux = seq_forward(params, tokens, cfg)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold) + cfg["aux_loss_alpha"] * aux


def loss(params, batch, cfg: dict):
    """The batch's loss: the mean over its sequences."""
    per = [seq_loss(params, t, l, cfg) for t, l in zip(batch["tokens"], batch["labels"])]
    return sum(per) / len(per)


def _seq_accumulate(params, tokens, labels, cfg, total, grads):
    """Add one sequence's loss and gradient to ``total`` and ``grads``."""
    lv, g = jax.value_and_grad(seq_loss)(params, tokens, labels, cfg)
    return total + lv, jax.tree.map(jnp.add, grads, g)


_seq_accumulate_jit = jax.jit(_seq_accumulate, static_argnums=3, donate_argnums=(4, 5))


def loss_and_grad_sum(params, batch, cfg: dict):
    """The sums over the batch's sequences of their losses and gradients,
    one sequence at a time."""
    total = jnp.zeros((), jnp.float32)
    grads = jax.tree.map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        for t, l in zip(batch["tokens"], batch["labels"]):
            total, grads = _seq_accumulate_jit(params, t, l, _hashable(cfg), total, grads)
    return total, grads


def _adamw_step(params, grad_sum, m, v, step, opt, n):
    return adamw(params, jax.tree.map(lambda g: g / n, grad_sum), m, v, step, opt)


_adamw = jax.jit(_adamw_step, static_argnums=(4, 5, 6), donate_argnums=(0, 1, 2, 3))


def train_reference(key, cfg: dict, batches) -> dict:
    """Run ``len(batches)`` AdamW steps from ``weights(key, cfg)``; the
    batch's loss and gradient are the means over its sequences. Returns
    each step's loss, the first clipped gradient, the initial params and
    the params after the last step (all on the host)."""
    opt = _hashable(cfg["optimizer"])
    params = jax.jit(weights, static_argnums=1)(key, _hashable(cfg))
    p0 = jax.device_get(params)
    m = v = None
    losses, grad1 = [], None
    for step, batch in enumerate(batches):
        total, grads = loss_and_grad_sum(params, batch, cfg)
        if m is None:
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
        n = int(batch["tokens"].shape[0])
        params, m, v, clipped = _adamw(params, grads, m, v, step, opt, n)
        del grads
        losses.append(float(total) / n)
        if grad1 is None:
            grad1 = jax.device_get(clipped)
    return {"losses": losses, "grad1": grad1, "p0": p0,
            "p_last": jax.device_get(params)}

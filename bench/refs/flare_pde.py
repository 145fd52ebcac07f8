"""Plain reference of the FLARE PDE surrogate: forward, relative-L2 loss,
gradients and AdamW steps, in straightforward ``jax.numpy``.

It follows the FLARE paper (arXiv:2508.12594): App. B's ResMLP
(``h = W_in x [+ x]``, ``h += GELU(W h)`` per residual layer,
``y = W_out h [+ h]``), Eq. 10's pre-norm block
``x += FLARE(LN x); x += ResMLP(LN x)``, and per head the two softmaxes of
one score matrix ``S = Q K^T`` (unscaled): ``Z = softmax_N(S) V`` and
``Y = softmax_M(S)^T Z``. The loss is the batch mean of the relative L2
error (paper Eq. 21). The optimizer is AdamW with global-norm clipping,
decoupled weight decay and a one-cycle schedule (linear warm-up, cosine
decay to peak/1e4). The parameter tree uses the same nesting and leaf names
as the system under test, so the two can be compared leaf by leaf; nothing
here is imported from it.

It computes in float32 with every contraction at HIGHEST precision. The
score matrices are built per (sample, head) under ``jax.checkpoint``
inside ``lax.map``, and each block is recomputed in the backward pass, so
the gradient at 40,000 points fits on one chip.
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp


HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- weights

def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _dense_w(key, din, dout):
    kw, kb = jax.random.split(key)
    return {"kernel": _normal(kw, (din, dout), 1.0 / math.sqrt(din)),
            "bias": _normal(kb, (dout,), 0.02)}


def _resmlp_w(key, din, dhid, dout, layers):
    keys = jax.random.split(key, layers + 2)
    return {"w_in": _dense_w(keys[0], din, dhid),
            "res": [_dense_w(keys[1 + i], dhid, dhid) for i in range(layers)],
            "w_out": _dense_w(keys[-1], dhid, dout)}


def _ln_w(key, dim):
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + _normal(ks, (dim,), 0.1), "bias": _normal(kb, (dim,), 0.02)}


def weights(key, cfg: dict) -> dict:
    """Seeded float32 weights for the sizes in ``cfg`` (the configuration
    file). Same key, same weights, whoever calls it."""
    c, h, m = cfg["hidden_size"], cfg["num_heads"], cfg["num_latents"]
    d = c // h
    keys = jax.random.split(key, cfg["num_blocks"] + 3)
    blocks = []
    for i in range(cfg["num_blocks"]):
        kb = jax.random.split(keys[3 + i], 7)
        blocks.append({
            "ln1": _ln_w(kb[0], c),
            "mixer": {
                "q_latent": _normal(kb[1], (h, m, d), 1.0 / math.sqrt(d)),
                "k_proj": _resmlp_w(kb[2], c, c, c, cfg["kv_proj_layers"]),
                "v_proj": _resmlp_w(kb[3], c, c, c, cfg["kv_proj_layers"]),
                "out_proj": _dense_w(kb[4], c, c),
            },
            "ln2": _ln_w(kb[5], c),
            "mlp": _resmlp_w(kb[6], c, c, c, cfg["mlp_layers"]),
        })
    return {
        "in_proj": _resmlp_w(keys[0], cfg["in_dim"], c, c, cfg["io_layers"]),
        "blocks": blocks,
        "out_norm": _ln_w(keys[1], c),
        "out_proj": _resmlp_w(keys[2], c, c, cfg["out_dim"], cfg["io_layers"]),
    }


# ---------------------------------------------------------------- forward

def _dense(p, x):
    return jnp.matmul(x, p["kernel"], precision=HIGHEST) + p["bias"]


def _resmlp(p, x):
    din, dhid = p["w_in"]["kernel"].shape
    dout = p["w_out"]["kernel"].shape[1]
    h = _dense(p["w_in"], x)
    if din == dhid:
        h = h + x
    for lp in p["res"]:
        h = h + jax.nn.gelu(_dense(lp, h))
    y = _dense(p["w_out"], h)
    return y + h if dhid == dout else y


def _layernorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * p["scale"] + p["bias"]


def _mix_one(q, k, v):
    """One (sample, head): q [M, D], k/v [N, D] -> y [N, D]."""
    s = jnp.matmul(q, k.T, precision=HIGHEST)                   # [M, N]
    z = jnp.matmul(jax.nn.softmax(s, axis=1), v, precision=HIGHEST)
    return jnp.matmul(jax.nn.softmax(s, axis=0).T, z, precision=HIGHEST)


def flare_mix(q, k, v):
    """q [H, M, D], k/v [B, H, N, D] -> [B, H, N, D]."""
    b, h, n, d = k.shape
    qq = jnp.broadcast_to(q[None], (b,) + q.shape).reshape(b * h, *q.shape[1:])
    one = jax.checkpoint(lambda a: _mix_one(a[0], a[1], a[2]))
    y = jax.lax.map(one, (qq, k.reshape(b * h, n, d), v.reshape(b * h, n, d)))
    return y.reshape(b, h, n, d)


def _heads(x, h):
    b, n, c = x.shape
    return x.reshape(b, n, h, c // h).transpose(0, 2, 1, 3)


def _block(p, x):
    mx = p["mixer"]
    h = mx["q_latent"].shape[0]
    y = _layernorm(p["ln1"], x)
    k = _heads(_resmlp(mx["k_proj"], y), h)
    v = _heads(_resmlp(mx["v_proj"], y), h)
    o = flare_mix(mx["q_latent"], k, v)
    b, _, n, d = o.shape
    x = x + _dense(mx["out_proj"], o.transpose(0, 2, 1, 3).reshape(b, n, h * d))
    return x + _resmlp(p["mlp"], _layernorm(p["ln2"], x))


def forward(params, x):
    """x [B, N, in_dim] -> [B, N, out_dim]."""
    h = _resmlp(params["in_proj"], x)
    block = jax.checkpoint(_block)
    for bp in params["blocks"]:
        h = block(bp, h)
    return _resmlp(params["out_proj"], _layernorm(params["out_norm"], h))


def loss(params, batch):
    pred = forward(params, batch["x"])
    y = batch["y"]
    err = jnp.sqrt(jnp.sum(jnp.square(pred - y), axis=(-2, -1)))
    ref = jnp.sqrt(jnp.sum(jnp.square(y), axis=(-2, -1)))
    return jnp.mean(err / jnp.maximum(ref, 1e-12))


# --------------------------------------------------------------- optimizer

def onecycle_lr(step, opt: dict):
    """Learning rate of 0-based ``step``: linear warm-up over
    ``warmup_frac`` of ``steps``, then cosine decay to peak / 1e4."""
    peak, total = opt["learning_rate"], opt["steps"]
    warm = max(1.0, opt["warmup_frac"] * total)
    if step < warm:
        return peak * step / warm
    prog = min(max((step - warm) / max(1.0, total - warm), 0.0), 1.0)
    floor = peak / 1e4
    return floor + 0.5 * (peak - floor) * (1.0 + math.cos(math.pi * prog))


def adamw(params, grads, m, v, step: int, opt: dict):
    """One AdamW step (float32) from 0-based ``step``; returns the new
    params and moments and the clipped gradient the moments took in."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = step + 1
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    lr, wd = onecycle_lr(step, opt), opt["weight_decay"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    new = jax.tree.map(
        lambda p, a, b: p - lr * ((a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
                                  + wd * p), params, m, v)
    return new, m, v, grads


def train_reference(key, cfg: dict, batches) -> dict:
    """Run ``len(batches)`` AdamW steps from ``weights(key, cfg)``.
    Returns each step's loss, the first clipped gradient, the initial
    params and the params after the last step (all on the host)."""
    opt = cfg["optimizer"]
    params = jax.jit(weights, static_argnums=1)(key, _hashable(cfg))
    p0 = jax.device_get(params)
    vg = jax.jit(jax.value_and_grad(loss))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for step, batch in enumerate(batches):
        lval, grads = vg(params, batch)
        params, m, v, clipped = adamw(params, grads, m, v, step, opt)
        losses.append(float(lval))
        if grad1 is None:
            grad1 = jax.device_get(clipped)
    return {"losses": losses, "grad1": grad1, "p0": p0,
            "p_last": jax.device_get(params)}


class _hashable(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))

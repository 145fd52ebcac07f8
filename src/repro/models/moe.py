"""Mixture-of-Experts FFN: dropless top-k routing over a share of the experts.

One layer serves every MoE model (Mixtral, DeepSeek-V2) on every path:
training, prefill, decode and suffix prefill. It is told which routed
experts it holds (``MoEConfig.experts_held`` from ``expert_offset``; all of
them by default), as one rank holds its share under expert parallelism.
The router scores all ``num_experts``; the layer computes what its own
experts add for the token-slots routed to them, plus the shared experts.
What the experts held elsewhere would add is not computed here, and no code
stands in for them.

Routing (:func:`route`): softmax over all experts in float32, then top-k.
The k gates are renormalized to sum to 1 iff ``norm_topk_prob`` (Mixtral's
rule: equal to a softmax over the k selected logits) and left as they are
otherwise (DeepSeek-V2: they sum to less than 1); then times
``routed_scale``.

Dispatch is dropless. The T*k token-slots are sorted by held expert, slots
of experts held elsewhere last (``moe.dispatch``); the held experts' SwiGLU
runs as grouped matmuls over the sorted rows (``moe.experts``), whose work
follows the rows routed to held experts (megablox's Pallas grouped
matmul, whose grid visits only the row tiles of held groups); each slot's output returns by the
inverse permutation, weighted by its gate, and is summed over the token's
slots (``moe.combine``). There is no capacity and no slot is dropped. The
router, its softmax, top-k and the balance loss are ``moe.route``.

Under a mesh (the ambient abstract mesh: ``jax.sharding.set_mesh``, or a
``Trainer`` given a mesh) whose "model" axis divides the held experts,
each of its ranks runs the grouped matmuls of its own experts on its
tokens (expert parallelism; where the axis divides the experts' width
instead, a slice of every expert), inside ``shard_map``, and the ranks'
outputs add up. Every rank sees the router's choices for its
tokens, so no token moves: the all-reduce of the outputs stands where an
all-to-all of tokens would.

Balance loss (:func:`seq_aux_loss`): DeepSeek-V2's sequence-wise form. Per
sequence, ``f_i = E / (k S) * #slots choosing i`` and ``P_i`` the mean
routing probability; the loss is ``sum_i f_i P_i``, averaged over
sequences (``aux_alpha`` weighs it in the model's loss).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.config import MoEConfig
from repro.nn.modules import dense, init_dense


class MoEStats(NamedTuple):
    """What one or more MoE layers report besides their output: the
    balance loss (before ``aux_alpha``), the token-slots routed to held
    experts, and the experts' load, max over mean (all float32 scalars;
    the last two carry no gradient)."""
    aux: jax.Array
    rows_held: jax.Array
    load_max_over_mean: jax.Array

    @classmethod
    def zero(cls) -> "MoEStats":
        z = jnp.zeros((), jnp.float32)
        return cls(z, z, z)

    def merge(self, other: "MoEStats") -> "MoEStats":
        """Across layers: losses and rows add, the worst load is kept."""
        return MoEStats(self.aux + other.aux, self.rows_held + other.rows_held,
                        jnp.maximum(self.load_max_over_mean, other.load_max_over_mean))


def init_moe(key, cfg: MoEConfig, d_model: int, *, param_dtype=jnp.float32) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    e, f = cfg.held, cfg.expert_ffn
    std = 1.0 / math.sqrt(d_model)
    kg, ku, kd = jax.random.split(ke, 3)
    params = {
        # the router scores every expert, held here or not
        "router": init_dense(kr, d_model, cfg.num_experts, param_dtype=param_dtype),
        # the held experts' weights: [held, d_model, f] / [held, f, d_model]
        "w_gate": (jax.random.truncated_normal(kg, -2, 2, (e, d_model, f), jnp.float32) * std).astype(param_dtype),
        "w_up": (jax.random.truncated_normal(ku, -2, 2, (e, d_model, f), jnp.float32) * std).astype(param_dtype),
        "w_down": (jax.random.truncated_normal(kd, -2, 2, (e, f, d_model), jnp.float32) * (1.0 / math.sqrt(f))).astype(param_dtype),
    }
    if cfg.num_shared:
        sf = cfg.shared_ffn or cfg.expert_ffn * cfg.num_shared
        k1, k2, k3 = jax.random.split(ks, 3)
        params["shared"] = {
            "w_gate": init_dense(k1, d_model, sf, param_dtype=param_dtype),
            "w_up": init_dense(k2, d_model, sf, param_dtype=param_dtype),
            "w_down": init_dense(k3, sf, d_model, param_dtype=param_dtype),
        }
    return params


def route(logits: jax.Array, cfg: MoEConfig):
    """logits [..., E] -> (gates [..., k] float32, expert ids [..., k],
    probabilities [..., E] float32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate * cfg.routed_scale, idx, probs


def expert_counts(idx: jax.Array, num_experts: int) -> jax.Array:
    """idx [B, S, k] -> [B, E] float32: slots choosing each expert, per
    sequence."""
    return jnp.sum(jax.nn.one_hot(idx, num_experts, dtype=jnp.float32), axis=(1, 2))


def seq_aux_loss(probs: jax.Array, counts: jax.Array, top_k: int) -> jax.Array:
    """probs [B, S, E], counts [B, E] (:func:`expert_counts`) -> the
    sequence-wise balance loss, averaged over sequences."""
    s, e = probs.shape[1], probs.shape[2]
    f = counts * (e / (top_k * s))
    return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


# The dispatch and the combine are each other's transpose, and each is a
# gather both ways: slot ``order[j]`` (of token ``order[j] // k``) is sorted
# row j, and sorted row ``inv[i]`` is slot i.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xf, order, inv, k):
    """Token rows xf [T, C] -> sorted slot rows [T*k, C]."""
    return jnp.take(xf, order // k, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(ys, order, inv, k):
    """Sorted slot rows ys [T*k, C] -> token rows [T, C], each the sum of
    its k slots (accumulated in float32)."""
    rows = jnp.take(ys, inv, axis=0).astype(jnp.float32)
    return rows.reshape(inv.shape[0] // k, k, -1).sum(1).astype(ys.dtype)


def _dispatch_fwd(xf, order, inv, k):
    return _dispatch(xf, order, inv, k), (order, inv)


def _dispatch_bwd(k, res, g):
    return _combine(g, *res, k), None, None


def _combine_fwd(ys, order, inv, k):
    return _combine(ys, order, inv, k), (order, inv)


def _combine_bwd(k, res, g):
    return _dispatch(g, *res, k), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


# row, contraction and output tiles of the grouped matmuls: the fastest of
# those measured on a TPU v5e at deepseek_v2_lite.train_4k's shapes, where
# megablox's kernel beat jax.lax.ragged_dot by a fifth (PERF.md §6)
GMM_TILES = (512, 1024, 512)


def _row_tile(rows: int) -> int:
    return min(GMM_TILES[0], -(-rows // 16) * 16)


def _grouped(x, w, sizes):
    """Rows of ``x`` [R, K] in consecutive groups of ``sizes`` [G] times
    ``w`` [G, K, N] (megablox's grouped matmul; interpreted off the TPU). R
    is a multiple of the row tile; rows past the last group are left
    unwritten, and callers mask them."""
    tiles = (_row_tile(x.shape[0]), min(GMM_TILES[1], x.shape[1]),
             min(GMM_TILES[2], w.shape[-1]))
    return gmm(x, w, sizes, x.dtype, tiles, interpret=jax.default_backend() != "tpu")


def _routed(xf, gate, idx, w_gate, w_up, w_down, offset, k):
    """Token rows xf [T, C] with their gates and expert ids [T, k] -> what
    the experts of ``w_*`` (stacked, ``offset`` the id of the first) add to
    each token [T, C]. ``offset`` may be traced."""
    t, c = xf.shape
    held = w_gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(t * k) - offset
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)  # slots held elsewhere sort last
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32), axis=0)[:held]
        # [R, C] grouped by expert, R = T*k padded to the row tile; rows
        # past the held slots are zeros here, and their gradient too
        rows = -(-t * k // _row_tile(t * k)) * _row_tile(t * k)
        valid = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        xs = jnp.pad(_dispatch(xf, order, inv, k), ((0, rows - t * k), (0, 0)))
        xs = jnp.where(valid, xs, 0)
        # each sorted row's gate; 0 for slots held elsewhere
        ws = jnp.take(jnp.where(mine, gate.reshape(t * k), 0.0), order)

    with jax.named_scope("moe.experts"):
        w_gu = jnp.concatenate([w_gate, w_up], axis=-1)
        hg, hu = jnp.split(_grouped(xs, w_gu.astype(xf.dtype), sizes), 2, axis=-1)
        ys = _grouped(jax.nn.silu(hg) * hu, w_down.astype(xf.dtype), sizes)

    with jax.named_scope("moe.combine"):
        ys = jnp.where(valid, ys, 0)[: t * k]
        return _combine(ys * ws[:, None].astype(ys.dtype), order, inv, k)


def _model_split(held: int, ffn: int):
    """How the "model" axis of the ambient mesh (``jax.sharding.set_mesh``)
    divides the held experts, as ``distributed/sharding.py`` lays them out:
    ``("expert", n)`` when the held experts divide over its n ranks (expert
    parallelism), ``("ffn", n)`` when the experts' width does (each rank a
    slice of every expert), None without such an axis or when neither
    divides."""
    am = jax.sharding.get_abstract_mesh()
    n = dict(zip(am.axis_names, am.axis_sizes)).get("model", 1)
    if n == 1:
        return None
    if held % n == 0:
        return "expert", n
    if ffn % n == 0:
        return "ffn", n
    return None


def _routed_sharded(xf, gate, idx, params, cfg: MoEConfig, split):
    """:func:`_routed` under ``shard_map`` over the mesh's "model" axis:
    each rank runs the grouped matmuls of its share of the held experts (its
    experts, or its slice of every expert's width) on its tokens (split
    over the batch axes), and the ranks' outputs add up. The router's
    choices are the whole layer's, so no token moves between ranks."""
    from jax.sharding import PartitionSpec as P

    mode, n = split
    am = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    batch = tuple(a for a in ("pod", "data") if a in sizes)
    tok = batch if batch and xf.shape[0] % math.prod(sizes[a] for a in batch) == 0 else None
    per_rank = cfg.held // n if mode == "expert" else 0
    if mode == "expert":
        w_specs = (P("model", None, None),) * 3
    else:
        w_specs = (P(None, None, "model"), P(None, None, "model"), P(None, "model", None))

    def body(xf, gate, idx, w_gate, w_up, w_down):
        offset = cfg.expert_offset + jax.lax.axis_index("model") * per_rank
        return _routed(xf, gate, idx, w_gate, w_up, w_down, offset, cfg.top_k)[None]

    parts = jax.shard_map(
        body, mesh=am, in_specs=(P(tok, None),) * 3 + w_specs,
        out_specs=P("model", tok, None), check_vma=False,
    )(xf, gate, idx, params["w_gate"], params["w_up"], params["w_down"])
    return jnp.sum(parts.astype(jnp.float32), axis=0).astype(xf.dtype)


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig):
    """x [B, S, C] -> (y [B, S, C], :class:`MoEStats`). Routed experts held
    here plus the shared experts. Under a mesh whose "model" axis divides
    the held experts or their width, the routed experts run split over it
    (:func:`_routed_sharded`)."""
    b, s, c = x.shape
    t, k, e, held = b * s, cfg.top_k, cfg.num_experts, cfg.held
    xf = x.reshape(t, c)

    with jax.named_scope("moe.route"):
        logits = jnp.dot(xf.astype(jnp.float32),
                         params["router"]["kernel"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        gate, idx, probs = route(logits, cfg)  # [T, k], [T, k], [T, E]
        counts = expert_counts(idx.reshape(b, s, k), e)
        aux = seq_aux_loss(probs.reshape(b, s, e), counts, k)

    split = _model_split(held, cfg.expert_ffn)
    if split is None:
        y = _routed(xf, gate, idx, params["w_gate"], params["w_up"], params["w_down"],
                    cfg.expert_offset, k)
    else:
        y = _routed_sharded(xf, gate, idx, params, cfg, split)

    y = y.reshape(b, s, c)
    if "shared" in params:
        sh = params["shared"]
        hsh = jax.nn.silu(dense(sh["w_gate"], x)) * dense(sh["w_up"], x)
        y = y + dense(sh["w_down"], hsh)
    load = jnp.sum(counts, axis=0)
    rows_held = jnp.sum(load[cfg.expert_offset:cfg.expert_offset + held])
    stats = MoEStats(aux, jax.lax.stop_gradient(rows_held),
                     jax.lax.stop_gradient(jnp.max(load) / jnp.mean(load)))
    return y, stats

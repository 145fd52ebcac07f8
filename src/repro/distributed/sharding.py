"""Divisibility-aware sharding rules for every model family.

Scheme (DESIGN.md §6): TP over the "model" axis on the canonical
column/row-parallel dims; FSDP (ZeRO-3-style) over ("pod","data") on the
complementary dim. Rules are path-regex keyed with a size-based fallback;
any dim that does not divide its assigned mesh axes falls back to
replication for that dim (collected in `report` for the dry-run log).

Stacked-layer leading axes ([L, ...], or [G, per_group, ...] for zamba) are
detected by rank surplus and left unsharded.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with Auto axis types. jax defaults to Explicit
    axes; every mesh here is consumed by NamedSharding placement and
    ``shard_map`` bodies written for compiler-propagated (Auto) sharding."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def fsdp_axes(mesh: Mesh):
    """The composed batch/FSDP axis tuple for this mesh."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


# Each rule: (path regex, spec builder taking (fsdp,) -> tuple of axis specs
# for the *trailing* dims of the param). "M" = model axis, "F" = fsdp axes.
_RULES: list[tuple[str, tuple]] = [
    # embeddings / unembeddings
    (r"embed/table$", ("M", "F")),
    (r"lm_head/kernel$", ("F", "M")),
    # attention projections (column-parallel in, row-parallel out)
    (r"(wq|wk|wv|w_q|w_uq|w_uk|w_uv|w_kr|w_dq|w_dkv)/kernel$", ("F", "M")),
    (r"(wo|w_o)/kernel$", ("M", "F")),
    # dense FFN
    (r"(w_gate|w_up|cm_k|w_in)/kernel$", ("F", "M")),
    (r"(w_down|cm_v|w_out)/kernel$", ("M", "F")),
    (r"(w_r|w_k|w_v|w_g|in_proj)/kernel$", ("F", "M")),
    (r"(out_proj|cm_r)/kernel$", ("M", "F")),
    # ResMLP interior residual layers
    (r"res/\d+/kernel$", ("F", "M")),
    # MoE stacked experts [E, C, F] / [E, F, C]: EP over model when the
    # expert count divides it (deepseek, 64e); otherwise expert-TP — F over
    # model, C over FSDP (mixtral, 8e on a 16-way axis). Sharding the
    # CONTRACTION dim over data is never a candidate: it turns every expert
    # matmul into an activation-sized data-axis all-reduce
    # (EXPERIMENTS.md §Perf cell C).
    # (a third candidate — F over the composed FSDPxTP axis with C unsharded —
    # was tried and REFUTED: GSPMD resolved the token/F data-axis conflict by
    # gathering activations, 2.6x worse collectives; see §Perf cell C it.2)
    (r"mlp/w_gate$", [("M", "F", None), (None, "F", "M")]),
    (r"mlp/w_up$", [("M", "F", None), (None, "F", "M")]),
    (r"mlp/w_down$", [("M", None, "F"), (None, "M", "F")]),
    # FLARE latent queries [H, M, D]: heads over model (head-parallel latents)
    (r"q_latent$", ("M", None, None)),
    # zamba LoRA stacks [G, in, r] / [G, r, out]
    (r"lora_\w+/a$", (None, "F", None)),
    (r"lora_\w+/b$", (None, None, "F")),
    # rwkv6 lora/decay small matrices
    (r"(lora_a|decay_a)$", ("F", "M")),
    (r"lora_b$", (None, None, None)),
    (r"decay_b$", (None, "F")),
    # mamba2 conv + per-head params: replicate (tiny)
    (r"(conv_w|conv_b|a_log|dt_bias|d_skip|u|mu\w*|cm_mu_\w+|decay_base)$", None),
    # norms / biases (possibly layer-stacked): replicate — tiny, and sharding
    # them would put mesh axes on the scanned [L] dim
    (r"(bias|scale)$", None),
]


def _concretize(tag, fsdp):
    if tag == "M":
        return "model"
    if tag == "F":
        return fsdp
    if tag == "FM":  # composed storage axis: FSDP x TP on one dim
        return tuple(fsdp) + ("model",)
    return tag


def spec_for_leaf(path: str, shape: tuple, mesh: Mesh, report: Optional[list] = None) -> P:
    """PartitionSpec for one parameter leaf."""
    fsdp = fsdp_axes(mesh)
    ndim = len(shape)
    if ndim <= 1:
        return P()
    for pat, tags in _RULES:
        if re.search(pat, path):
            if tags is None:
                return P()
            candidates = tags if isinstance(tags, list) else [tags]
            chosen = None
            for cand in candidates:
                cand = tuple(_concretize(t, fsdp) for t in cand)
                # leading stacked-layer dims -> None
                lead = ndim - len(cand)
                if lead < 0:  # param is lower-rank than rule (e.g. unstacked)
                    cand = cand[-ndim:]
                    lead = 0
                full = (None,) * lead + cand
                if _divisible(shape, full, mesh):
                    return P(*full)
                if chosen is None:
                    chosen = full
            return _check_divisible(path, shape, chosen, mesh, report)
    # Fallback: shard the largest dim over model, next largest over fsdp.
    order = np.argsort(shape)[::-1]
    full = [None] * ndim
    m_sz = _axis_size(mesh, "model")
    f_sz = _axis_size(mesh, fsdp)
    placed_model = placed_fsdp = False
    for ax in order:
        if not placed_model and shape[ax] % m_sz == 0 and shape[ax] >= m_sz:
            full[ax] = "model"
            placed_model = True
        elif not placed_fsdp and shape[ax] % f_sz == 0 and shape[ax] >= f_sz:
            full[ax] = fsdp
            placed_fsdp = True
    if report is not None and not placed_model:
        report.append(f"fallback-replicated(model): {path} {shape}")
    return P(*full)


def _divisible(shape, spec, mesh: Mesh) -> bool:
    for dim, axis in zip(shape, spec):
        sz = _axis_size(mesh, axis)
        if axis is not None and (dim % sz or dim < sz):
            return False
    return True


def _check_divisible(path, shape, spec, mesh: Mesh, report) -> P:
    out = []
    for dim, axis in zip(shape, spec):
        sz = _axis_size(mesh, axis)
        if axis is not None and (dim % sz or dim < sz):
            if report is not None:
                report.append(f"replicated {axis} (dim {dim} % {sz}): {path} {shape}")
            out.append(None)
        else:
            out.append(axis)
    return P(*out)


def param_shardings(params_shape, mesh: Mesh, report: Optional[list] = None):
    """NamedSharding pytree matching a params eval_shape tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    out = []
    for kpath, leaf in flat:
        path = "/".join(_pstr(p) for p in kpath)
        spec = spec_for_leaf(path, leaf.shape, mesh, report)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def _pstr(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def batch_spec(mesh: Mesh, *, ndim: int = 2) -> P:
    """Batch tensors: shard dim 0 over the composed (pod, data) axes."""
    return P(fsdp_axes(mesh), *([None] * (ndim - 1)))


def cache_shardings(caches_shape, mesh: Mesh, *, batch_axes=None, report=None):
    """KV/recurrent-state caches: shard the batch dim (detected as dim 0 of
    rank>=2 leaves, after any stacked [L] prefix) over (pod, data); shard the
    head dim over model when divisible.

    Heuristic on shapes (caches are NamedTuples of arrays, possibly stacked
    with leading [L]): we shard dim0-after-stack over fsdp when divisible,
    else replicate; scalar lengths/positions replicate.
    """
    fsdp = batch_axes or fsdp_axes(mesh)
    f_sz = _axis_size(mesh, fsdp)
    m_sz = _axis_size(mesh, "model")

    def one(leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        spec = [None] * len(shape)
        # find the batch dim: first dim divisible by fsdp size (skipping
        # stacked-layer dims whose size is small and equal to num_layers is
        # ambiguous — we simply take the first divisible dim)
        placed_f = False
        for i, d in enumerate(shape):
            if not placed_f and d % f_sz == 0 and d >= f_sz:
                spec[i] = fsdp
                placed_f = True
            elif placed_f and d % m_sz == 0 and d >= m_sz and spec[i] is None:
                spec[i] = "model"
                break
        if report is not None and not placed_f:
            report.append(f"cache replicated over fsdp: {shape}")
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, caches_shape)


def shard_slot_pool(pool: dict, mesh: Mesh, specs: dict) -> dict:
    """Pin a serve pool onto its slot sharding (DESIGN.md §15; the MaxText
    multi-host-inference idiom: serving state sharded over the flattened
    mesh, host orchestration global). ``specs`` is
    ``PagedModelCache.pool_pspecs(mesh.axis_names)``. Re-pinning an
    already-correctly-placed pool is free, so the engine calls this after
    every plain-jit pool mutation (prefill, COW copy, slot reset) to keep
    the shard_map'd decode step's input shardings stable — one trace, no
    resharding churn."""
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
    return {
        "dense": tuple(put(x, s) for x, s in zip(pool["dense"], specs["dense"])),
        "data": tuple(put(x, specs["data"]) for x in pool["data"]),
        "scale": tuple(None if x is None else put(x, specs["scale"])
                       for x in pool["scale"]),
    }


def constrain_dim_to_batch_axes(x, dim: int = 0):
    """with_sharding_constraint pinning `dim` to the (pod, data) axes, using
    the ambient abstract mesh (set via jax.sharding.set_mesh). No-op when no
    mesh is set or the dim does not divide.

    Critical use: the microbatch reshape [B, ...] -> [nmb, B/nmb, ...] in
    train/steps.py. Row-major reshape semantics move the batch sharding onto
    the SCAN dim (each data shard owns whole microbatches), silently
    replicating every microbatch's compute across the data axis
    (EXPERIMENTS.md §Perf, systemic fix).
    """
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is None or not am.axis_names:
            return x
        sizes = dict(zip(am.axis_names, am.axis_sizes))
        fsdp = tuple(a for a in ("pod", "data") if a in sizes)
        n = 1
        for a in fsdp:
            n *= sizes[a]
        if not fsdp or x.shape[dim] % n or x.shape[dim] < n:
            return x
        spec = [None] * x.ndim
        spec[dim] = fsdp
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:  # pragma: no cover
        return x

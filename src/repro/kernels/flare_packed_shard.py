"""Mesh-parallel packed-head FLARE mixer: the PR 2 block-diagonal fused
kernel under ``shard_map`` (DESIGN.md §15).

The single-launch kernel in ``flare_packed.py`` keeps Z in VMEM between its
encode and decode phases — which is exactly what stops it from sharding over
the token axis: a shard's encode statistics are *local*, and the decode
phase needs the *global* Z. So the sharded form splits the launch at the one
point where cross-shard information is required, and pays for it with the
smallest possible collectives (everything exchanged is O(M·D) per head —
the latent bottleneck, never the token axis):

  forward   enc-stats kernel  -> (num, mx, den)   local flash statistics
            combine (plain JAX; the flash-merge across shards):
                gmax = pmax(mx);  scale = exp(mx - gmax)
                Z    = psum(num * scale) / psum(den * scale)
            decode kernel     -> y                 local tokens vs global Z

  backward  dZ kernel         -> dZ_local          (decode-weight sweep)
            dZ = psum(dZ_local)                    latent grads are global
            grads kernel      -> dq_local, dk, dv  (encode recompute sweep,
                                                    from global mx/den/Z)
            dq = psum(sum_over_batch(dq_local))    latent queries are shared
                                                   across batch AND shards

Layout: the sequence axis shards K/V's token dim (``seq_axes``, normally
``"data"``); whole heads shard over ``lat_axes`` (normally ``"model"``) —
heads are fully independent in FLARE, so the model axis needs *zero*
collectives. All four Pallas bodies reuse ``flare_packed``'s in-kernel
helpers, so per-block arithmetic (masking, segmented softmax, flash
recomputation) is bitwise-identical to the single-device kernel; on a
1-shard mesh the whole pipeline is bit-identical to ``flare_mixer_packed``.

The custom VJP wraps the *shard-local* pipeline (collectives included), so
``jax.grad`` through the public wrapper runs mesh-parallel end to end.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.kernels.flare_packed import (
    LANE,
    NEG_INF,
    _bd_mask,
    _compact_block_diag,
    _compiler_params,
    _decode_weights,
    _expand_block_diag,
    _pack_heads,
    _pad_axis,
    _round_up,
    _scores,
    _token_ok,
    _unpack_heads,
    _vmem,
    _PackedCfg,
    heuristic_pack,
)

__all__ = ["flare_mixer_packed_shard"]


def _axes_tuple(axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _axes_size(mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(int(mesh.shape[a]) for a in axes) if axes else 1


def _spec_entry(axes: Tuple[str, ...]):
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def _lane_expand(cfg: _PackedCfg, r: jax.Array, wl: int, fill) -> jax.Array:
    """Per-latent-row values [G, 1, S] -> the packed-compact layout [G, Mp, Wl]
    (row s = p*Mp + m lands on latent m's lanes of head p; lane padding gets
    ``fill`` so it divides/multiplies to an exact no-op)."""
    g = r.shape[0]
    x = jnp.moveaxis(r.reshape(g, cfg.pack, cfg.mp), 1, 2)   # [G, Mp, pack]
    x = jnp.repeat(x, cfg.d, axis=2)                          # [G, Mp, pack*D]
    if wl > cfg.pack * cfg.d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, wl - cfg.pack * cfg.d)),
                    constant_values=fill)
    return x


# ---------------------------------------------------------------------------
# Kernel 1: shard-local encode statistics (the fused kernel's phase 0, but
# emitting the UNNORMALIZED numerator so shards can be flash-merged)
# ---------------------------------------------------------------------------


def _enc_stats_kernel(q_ref, k_ref, v_ref, num_ref, mx_ref, den_ref,
                      mx_scr, den_scr, num_scr, *,
                      cfg: _PackedCfg, n_blocks: int):
    n_idx = pl.program_id(1)
    wl = q_ref.shape[-1]
    bd = _bd_mask(cfg, wl)
    qbd = _expand_block_diag(cfg, q_ref[0], bd)

    @pl.when(n_idx == 0)
    def _init():
        mx_scr[...] = jnp.full_like(mx_scr, NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        num_scr[...] = jnp.zeros_like(num_scr)

    k = k_ref[0]
    v = v_ref[0]
    s = _scores(cfg, qbd, k, n_idx)                       # [S, bn]
    m_prev = mx_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    ok = _token_ok(cfg, s.shape, n_idx)
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    den_scr[...] = den_scr[...] * alpha + jnp.sum(p, axis=-1)
    num_scr[...] = num_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    mx_scr[...] = m_new

    @pl.when(n_idx == n_blocks - 1)
    def _finish():
        num_ref[0] = _compact_block_diag(cfg, jnp.where(bd, num_scr[...], 0.0))
        mx_ref[0, 0] = mx_scr[...]
        den_ref[0, 0] = den_scr[...]


def _enc_stats_launch(cfg: _PackedCfg, gh: int, q_p, k_p, v_p):
    g, np_, wl = k_p.shape
    s_rows = cfg.pack * cfg.mp
    n_blocks = np_ // cfg.block_n
    bn, mp = cfg.block_n, cfg.mp
    kernel = functools.partial(_enc_stats_kernel, cfg=cfg, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(g, n_blocks),
        in_specs=[
            pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_ % gh, 0, 0)),
            pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0)),
            pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_, 0, 0)),
            pl.BlockSpec((1, 1, s_rows), lambda g_, n_: (g_, 0, 0)),
            pl.BlockSpec((1, 1, s_rows), lambda g_, n_: (g_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, mp, wl), jnp.float32),   # numerator
            jax.ShapeDtypeStruct((g, 1, s_rows), jnp.float32),  # local max
            jax.ShapeDtypeStruct((g, 1, s_rows), jnp.float32),  # local den
        ],
        scratch_shapes=[
            _vmem((s_rows,), jnp.float32),
            _vmem((s_rows,), jnp.float32),
            _vmem((s_rows, wl), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
    )(q_p, k_p, v_p)


# ---------------------------------------------------------------------------
# Kernel 2: decode sweep against the (globally combined) latent summary Z
# ---------------------------------------------------------------------------


def _decode_kernel(q_ref, k_ref, z_ref, y_ref, *, cfg: _PackedCfg):
    n_idx = pl.program_id(1)
    wl = q_ref.shape[-1]
    bd = _bd_mask(cfg, wl)
    qbd = _expand_block_diag(cfg, q_ref[0], bd)
    zbd = _expand_block_diag(cfg, z_ref[0], bd)
    s = _scores(cfg, qbd, k_ref[0], n_idx)
    w = _decode_weights(cfg, s)
    y = jax.lax.dot_general(w, zbd, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


def _decode_launch(cfg: _PackedCfg, gh: int, q_p, k_p, z, out_dtype):
    g, np_, wl = k_p.shape
    n_blocks = np_ // cfg.block_n
    bn, mp = cfg.block_n, cfg.mp
    kernel = functools.partial(_decode_kernel, cfg=cfg)
    return pl.pallas_call(
        kernel,
        grid=(g, n_blocks),
        in_specs=[
            pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_ % gh, 0, 0)),
            pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0)),
            pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0))],
        out_shape=[jax.ShapeDtypeStruct((g, np_, wl), out_dtype)],
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
    )(q_p, k_p, z)[0]


# ---------------------------------------------------------------------------
# Kernel 3 (backward): shard-local dZ accumulation (decode-weight sweep)
# ---------------------------------------------------------------------------


def _dz_kernel(q_ref, k_ref, dy_ref, dz_ref, dz_scr, *,
               cfg: _PackedCfg, n_blocks: int):
    n_idx = pl.program_id(1)
    wl = q_ref.shape[-1]
    bd = _bd_mask(cfg, wl)
    qbd = _expand_block_diag(cfg, q_ref[0], bd)

    @pl.when(n_idx == 0)
    def _init():
        dz_scr[...] = jnp.zeros_like(dz_scr)

    dy = dy_ref[0].astype(jnp.float32)
    s = _scores(cfg, qbd, k_ref[0], n_idx)
    w = _decode_weights(cfg, s)
    dz_scr[...] = dz_scr[...] + jnp.where(bd, jax.lax.dot_general(
        w, dy, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), 0.0)

    @pl.when(n_idx == n_blocks - 1)
    def _finish():
        dz_ref[0] = _compact_block_diag(cfg, dz_scr[...])


def _dz_launch(cfg: _PackedCfg, gh: int, q_p, k_p, dy_p):
    g, np_, wl = k_p.shape
    s_rows = cfg.pack * cfg.mp
    n_blocks = np_ // cfg.block_n
    bn, mp = cfg.block_n, cfg.mp
    kernel = functools.partial(_dz_kernel, cfg=cfg, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(g, n_blocks),
        in_specs=[
            pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_ % gh, 0, 0)),
            pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0)),
            pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0)),
        ],
        out_specs=[pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((g, mp, wl), jnp.float32)],
        scratch_shapes=[_vmem((s_rows, wl), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
    )(q_p, k_p, dy_p)[0]


# ---------------------------------------------------------------------------
# Kernel 4 (backward): dq/dk/dv from the GLOBAL statistics + global dZ
# ---------------------------------------------------------------------------


def _grads_kernel(q_ref, k_ref, v_ref, z_ref, mx_ref, den_ref, y_ref, dy_ref,
                  dz_ref, dq_ref, dk_ref, dv_ref, dqa_scr, de_scr, *,
                  cfg: _PackedCfg, n_blocks: int):
    n_idx = pl.program_id(1)
    wl = q_ref.shape[-1]
    bd = _bd_mask(cfg, wl)
    qbd = _expand_block_diag(cfg, q_ref[0], bd)
    zbd = _expand_block_diag(cfg, z_ref[0], bd)
    dzbd = _expand_block_diag(cfg, dz_ref[0], bd)

    @pl.when(n_idx == 0)
    def _init():
        dqa_scr[...] = jnp.zeros_like(dqa_scr)
        # flash trick: rowsum(dA ∘ A) == rowsum(dZ ∘ Z) per latent row —
        # both factors are global here, so de needs no collective of its own
        de_scr[...] = jnp.sum(dzbd * zbd, axis=-1)

    k = k_ref[0]
    v = v_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    s = _scores(cfg, qbd, k, n_idx)
    # encode weights from the GLOBAL saved stats: a is each local token's
    # weight in the full-sequence encode softmax
    a = jnp.exp(s - mx_ref[0, 0][:, None]) / den_ref[0, 0][:, None]
    ok = _token_ok(cfg, s.shape, n_idx)
    if ok is not None:
        a = jnp.where(ok, a, 0.0)
    w = _decode_weights(cfg, s)
    dw = jax.lax.dot_general(zbd, dy, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = jax.lax.dot_general(bd.astype(jnp.float32), dy * y,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    ds_dec = w * (dw - delta)
    da = jax.lax.dot_general(dzbd, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds_enc = a * (da - de_scr[...][:, None])
    ds = ds_enc + ds_dec
    dk_ref[0] = jax.lax.dot_general(
        ds, qbd.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)
    dv_ref[0] = jax.lax.dot_general(
        a, dzbd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dqa_scr[...] = dqa_scr[...] + jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n_idx == n_blocks - 1)
    def _finish():
        dq_ref[0] = _compact_block_diag(
            cfg, jnp.where(bd, dqa_scr[...], 0.0)).astype(dq_ref.dtype)


def _grads_launch(cfg: _PackedCfg, gh: int, q_p, k_p, v_p, z, mx, den,
                  y_p, dy_p, dz):
    g, np_, wl = k_p.shape
    s_rows = cfg.pack * cfg.mp
    n_blocks = np_ // cfg.block_n
    bn, mp = cfg.block_n, cfg.mp
    kernel = functools.partial(_grads_kernel, cfg=cfg, n_blocks=n_blocks)
    q_spec = pl.BlockSpec((1, mp, wl), lambda g_, n_: (g_ % gh, 0, 0))
    stream = pl.BlockSpec((1, bn, wl), lambda g_, n_: (g_, n_, 0))
    per_group = lambda shape: pl.BlockSpec(
        (1,) + shape, lambda g_, n_: (g_,) + (0,) * len(shape))
    return pl.pallas_call(
        kernel,
        grid=(g, n_blocks),
        in_specs=[
            q_spec,
            stream,                       # k
            stream,                       # v
            per_group((mp, wl)),          # z compact (global)
            per_group((1, s_rows)),       # global encode max
            per_group((1, s_rows)),       # global encode den
            stream,                       # y
            stream,                       # dy
            per_group((mp, wl)),          # dz compact (global)
        ],
        out_specs=[
            per_group((mp, wl)),          # dq (written once per group)
            stream,                       # dk
            stream,                       # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, mp, wl), jnp.float32),
            jax.ShapeDtypeStruct((g, np_, wl), k_p.dtype),
            jax.ShapeDtypeStruct((g, np_, wl), v_p.dtype),
        ],
        scratch_shapes=[
            _vmem((s_rows, wl), jnp.float32),
            _vmem((s_rows,), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
    )(q_p, k_p, v_p, z, mx, den, y_p, dy_p, dz)


# ---------------------------------------------------------------------------
# custom_vjp shard core: runs INSIDE the shard_map body on shard-local packed
# arrays; the collectives over ``axes`` (the sequence axes) are part of both
# the forward and the backward rule, so jax.grad never has to differentiate
# through a collective itself.
# ---------------------------------------------------------------------------


def _combine_stats(cfg: _PackedCfg, axes, num, mx, den):
    """Flash-merge the per-shard encode statistics into the global Z.
    Collective volume: O(G · M · D) — the latent bottleneck, independent of
    N. On a 1-shard axis every step is an exact no-op (scale == 1.0)."""
    wl = num.shape[-1]
    gmx = lax.pmax(mx, axes)
    scale = jnp.exp(mx - gmx)                                # [G, 1, S]
    num_g = lax.psum(num * _lane_expand(cfg, scale, wl, 1.0), axes)
    den_g = lax.psum(den * scale, axes)                      # [G, 1, S]
    z = num_g / _lane_expand(cfg, den_g, wl, 1.0)
    return z, gmx, den_g


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _shard_core(cfg: _PackedCfg, gh: int, axes, q_p, k_p, v_p):
    y, _ = _shard_core_fwd(cfg, gh, axes, q_p, k_p, v_p)
    return y


def _shard_core_fwd(cfg: _PackedCfg, gh: int, axes, q_p, k_p, v_p):
    num, mx, den = _enc_stats_launch(cfg, gh, q_p, k_p, v_p)
    z, gmx, den_g = _combine_stats(cfg, axes, num, mx, den)
    y = _decode_launch(cfg, gh, q_p, k_p, z, v_p.dtype)
    return y, (q_p, k_p, v_p, z, gmx, den_g, y)


def _shard_core_bwd(cfg: _PackedCfg, gh: int, axes, res, dy):
    q_p, k_p, v_p, z, gmx, den_g, y = res
    # dZ needs every shard's decode-weight contribution before sweep 2
    dz = lax.psum(_dz_launch(cfg, gh, q_p, k_p, dy), axes)
    dq_g, dk, dv = _grads_launch(cfg, gh, q_p, k_p, v_p, z, gmx, den_g,
                                 y, dy, dz)
    g, mp, wl = dq_g.shape
    # latent queries are shared across the batch AND the sequence shards:
    # reduce over the local batch here; the cross-shard sum is shard_map's
    # transpose of q's replicated in_spec (an explicit psum here would
    # double-count it)
    dq = dq_g.reshape(g // gh, gh, mp, wl).sum(axis=0)
    return dq.astype(q_p.dtype), dk, dv


_shard_core.defvjp(_shard_core_fwd, _shard_core_bwd)


# ---------------------------------------------------------------------------
# Public wrapper: [H, M, D] x [B, H, N, D] -> [B, H, N, D], mesh-parallel
# ---------------------------------------------------------------------------


def _local_mixer(q, k, v, *, axes: Tuple[str, ...], pack: int, block_n: int,
                 interpret: bool):
    """The shard-local pipeline: identical packing/padding to
    ``flare_mixer_packed`` (on this shard's head/token slices), then the
    split-launch core with cross-shard flash merges over ``axes``."""
    b, h, n, d = k.shape
    m = q.shape[1]
    pack = max(1, min(pack, h))
    gh = -(-h // pack)
    hp = gh * pack
    mp = _round_up(m, 16)
    wl = _round_up(pack * d, LANE)
    bn = min(block_n, _round_up(n, 16))
    np_ = _round_up(n, bn)

    qp = _pack_heads(_pad_axis(_pad_axis(q.astype(k.dtype), 0, hp), 1, mp),
                     gh, pack, wl)
    kp = _pack_heads(_pad_axis(_pad_axis(k, 1, hp), 2, np_), gh, pack, wl)
    vp = _pack_heads(_pad_axis(_pad_axis(v, 1, hp), 2, np_), gh, pack, wl)
    kp = kp.reshape(b * gh, np_, wl)
    vp = vp.reshape(b * gh, np_, wl)

    cfg = _PackedCfg(
        pack=pack, mp=mp, d=d, block_n=bn,
        n_valid=n if n < np_ else None,
        m_valid=m if m < mp else None,
        interpret=bool(interpret),
    )
    y = _shard_core(cfg, gh, axes, qp, kp, vp)       # [B*Gh, Np, Wl]
    y = _unpack_heads(y.reshape(b, gh, np_, wl), pack, d)
    return y[:, :h, :n, :]


def flare_mixer_packed_shard(
    q: jax.Array,  # [H, M, D] latent queries (replicated over seq shards)
    k: jax.Array,  # [B, H, N, D]
    v: jax.Array,  # [B, H, N, D]
    *,
    mesh,
    seq_axes: Sequence[str] | str = ("data",),
    lat_axes: Sequence[str] | str = ("model",),
    pack: Optional[int] = None,
    block_n: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Mesh-parallel packed-head FLARE mixer; differentiable (custom VJP
    under shard_map, psum'd latent grads).

    Tokens shard over ``seq_axes``; whole heads shard over ``lat_axes``
    (head independence makes the model axis collective-free). Requires
    ``H % size(lat_axes) == 0`` and ``N % size(seq_axes) == 0`` — the plan
    builder surfaces this as a resolve-time ValueError so "auto" can fall
    back to another sharded form.
    """
    seq = _axes_tuple(seq_axes)
    lat = _axes_tuple(lat_axes)
    names = set(mesh.axis_names)
    for a in seq + lat:
        if a not in names:
            raise ValueError(f"axis {a!r} not in mesh axes {tuple(mesh.axis_names)}")
    if set(seq) & set(lat):
        raise ValueError(f"seq_axes {seq} and lat_axes {lat} must be disjoint")
    b, h, n, d = k.shape
    m = q.shape[1]
    seq_size = _axes_size(mesh, seq)
    lat_size = _axes_size(mesh, lat)
    if h % lat_size:
        raise ValueError(
            f"packed_shard: H={h} not divisible by lat_axes size {lat_size}")
    if n % seq_size:
        raise ValueError(
            f"packed_shard: N={n} not divisible by seq_axes size {seq_size}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if pack is None:
        pack = heuristic_pack(h // lat_size, m, d)

    body = functools.partial(_local_mixer, axes=seq, pack=pack,
                             block_n=block_n, interpret=bool(interpret))
    lat_e, seq_e = _spec_entry(lat), _spec_entry(seq)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(lat_e, None, None),
                  P(None, lat_e, seq_e, None),
                  P(None, lat_e, seq_e, None)),
        out_specs=P(None, lat_e, seq_e, None),
        check_vma=False,  # no replication rule exists for pallas_call
    )
    return fn(q, k, v)

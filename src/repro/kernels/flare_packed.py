"""Single-launch packed-head Pallas FLARE mixer with a custom VJP.

Three TPU-shaped optimizations over the two-launch kernels in ``flare.py``
(DESIGN.md §12):

  * **Packed-head lane layout.** The paper's strong configs use many heads
    with tiny head dims (D in {4, 8}); padding each head's D to the 128-lane
    boundary leaves the MXU <= 6% utilized. Here ``pack`` heads share the
    lane dimension: K/V tiles are [block_n, pack*D] and the latent queries
    are expanded in-VMEM to a block-diagonal [pack*Mp, pack*D] matrix, so
    ONE full-width matmul produces every packed head's score block
    (rows p*Mp..(p+1)*Mp of ``Q_bd @ K_packed^T`` are head p's [Mp, block_n]
    scores — off-head lanes are zeroed by the block-diagonal mask, keeping
    per-head dot products disjoint).

  * **Single-launch encode->decode.** Grid (G, 2, N_blocks): phase 0 runs
    the flash-style encode sweep, phase 1 the decode sweep. The latent
    summary Z (only [pack*Mp, pack*D]) never round-trips through HBM — it
    stays in VMEM scratch between the phases — and there is one kernel
    launch instead of two.

  * **Custom VJP.** The backward pass is two more fused sweeps in one
    launch: sweep 1 recomputes the decode weights from K and accumulates
    dZ; sweep 2 recomputes the encode weights from the saved row statistics
    (flash recomputation: softmax max + denominator per latent row) and
    emits dq/dk/dv. Residuals are O(M*D + N*D) — no [M, N] matrix is ever
    stored — so ``jax.grad`` through ``flare_mixer_packed`` runs entirely
    on the Pallas path.

Orientation note: every score tile is kept latent-major, [S, block_n] with
S = pack*Mp, because (a) encode's online softmax reduces along lanes as in
``flare.py`` and (b) decode's softmax over latents becomes a *sublane*
segmented softmax (per row-block max/sum), which is far cheaper on TPU than
lane-dimension segmentation.

All padding (head count to a pack multiple, M to the sublane tile, N to the
block boundary, lanes to 128) happens in plain-JAX wrapper code, so JAX
autodiff composes the pack/unpack reshapes with the kernel's custom VJP.

Steady-state sweep steps repeat no per-group setup. The block-diagonal
latent queries (and, in the backward, Z and the f32 block-diagonal
indicator) are built in VMEM scratch on each group's first grid step and
only read after that; the group axis is the grid's
outermost, so every group, on whatever core runs it, starts there. The
token-padding mask is built only on the last N block, the one block that
can hold padding: both forms of a sweep step come from one body with a
static ``masked`` flag (:func:`_sweep`), and the gauge
``flare_packed.masked_block_share`` records the share of blocks that take
the masked form. Neither changes a value the kernels compute. The shared
helpers (``_bd_mask``, ``_expand_block_diag``, ``_scores``, ``_token_ok``,
``_decode_weights``) keep their defaults for ``flare_packed_shard``, which
still builds its invariants on every step.

Per-latent-row softmax statistics (the encode sweep's running max and
denominator; the backward's saved max, denominator and ``delta_enc``) live
in [S, 128] f32 VMEM scratch with each row's value replicated across the
lanes, as flash-attention kernels keep m and l. A reduction over the token
axis keeps that axis, and a tile reads a statistic as whole 128-lane copies
(:func:`_lanes`), so a sweep step never holds a rank-1 [S] value: Mosaic
lays those along lanes, and each step would permute them to and from the
tile's sublanes. The statistics take their compact [G, 1, S] residual form
only once per group, by a transpose, when the encode sweep ends; the
backward widens them back, the same way, in its group's first step. The
forward gives back the VMEM they take: its numerator accumulator turns into
Z's block-diagonal scratch in place when the encode sweep ends, and it
builds the block-diagonal mask there, once per group, instead of keeping
it, so every (pack, block_n) the autotuner offers that fit before still
fits ``VMEM_LIMIT``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import scope
from repro.obs.metrics import REGISTRY

NEG_INF = -1e30
LANE = 128
# Scoped-VMEM cap for these launches. The backward sweep keeps ~10 live
# [pack*Mp, block_n] f32 temporaries; at M=2048 and block_n=256 that exceeds
# Mosaic's 16 MiB default, while v5e has 128 MiB of VMEM per core.
VMEM_LIMIT = 64 * 1024 * 1024


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def heuristic_pack(heads: int, latents: int, head_dim: int,
                   *, max_rows: int = 2048) -> int:
    """Default head-pack factor: fill the 128-lane dim, but never pack more
    heads than exist and keep the packed latent-row count (pack * padded M)
    within a VMEM-friendly budget."""
    pack = max(1, min(LANE // max(1, head_dim), heads))
    mp = _round_up(max(1, latents), 16)
    while pack > 1 and pack * mp > max_rows:
        pack = (pack + 1) // 2
    return pack


class _PackedCfg(NamedTuple):
    """Static launch config (hashable — custom_vjp nondiff argument)."""

    pack: int
    mp: int          # padded latent count per head
    d: int           # true head dim (for the lane->head mask)
    block_n: int
    n_valid: Optional[int]   # real token count when N carries tile padding
    m_valid: Optional[int]   # real latent count when M carries pad rows
    interpret: bool


# ---------------------------------------------------------------------------
# In-kernel helpers (shared by forward and backward so recomputation is
# bitwise-identical to the forward pass)
# ---------------------------------------------------------------------------


def _bd_mask(cfg: _PackedCfg, wl: int) -> jax.Array:
    """[S, Wl] block-diagonal mask: row s (head s // Mp) owns lane c iff
    c // D == s // Mp. Lane padding (c >= pack*D) matches no head."""
    s = cfg.pack * cfg.mp
    rh = jax.lax.broadcasted_iota(jnp.int32, (s, wl), 0) // cfg.mp
    ch = jax.lax.broadcasted_iota(jnp.int32, (s, wl), 1) // cfg.d
    return (rh == ch) & (ch < cfg.pack)


def _expand_block_diag(cfg: _PackedCfg, x: jax.Array, bd: jax.Array) -> jax.Array:
    """[Mp, Wl] packed-compact -> [S, Wl] block-diagonal (head p's columns
    appear in row block p, zeros elsewhere)."""
    tiled = x if cfg.pack == 1 else jnp.concatenate([x] * cfg.pack, axis=0)
    return jnp.where(bd, tiled, 0.0)


def _compact_block_diag(cfg: _PackedCfg, x_bd: jax.Array) -> jax.Array:
    """Inverse of :func:`_expand_block_diag` for an already-masked [S, Wl]
    array: row blocks occupy disjoint lane sets, so summing them is exact."""
    out = x_bd[0:cfg.mp, :]
    for p in range(1, cfg.pack):
        out = out + x_bd[p * cfg.mp:(p + 1) * cfg.mp, :]
    return out


def _scores(cfg: _PackedCfg, qbd: jax.Array, k: jax.Array, n_idx,
            *, token_mask: bool = True) -> jax.Array:
    """[S, bn] latent-major scores with token- and latent-padding masked to
    NEG_INF (exactly the mask the forward statistics were built under).
    ``token_mask=False`` leaves the token mask out, for a block that holds
    no token padding."""
    s = jax.lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    ok = None
    if token_mask and cfg.n_valid is not None:
        cols = n_idx * cfg.block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = cols < cfg.n_valid
    if cfg.m_valid is not None:
        lat = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % cfg.mp
        lat_ok = lat < cfg.m_valid
        ok = lat_ok if ok is None else (ok & lat_ok)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    return s


def _token_ok(cfg: _PackedCfg, shape, n_idx) -> Optional[jax.Array]:
    if cfg.n_valid is None:
        return None
    cols = n_idx * cfg.block_n + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return cols < cfg.n_valid


def _decode_weights(cfg: _PackedCfg, s: jax.Array) -> jax.Array:
    """Segmented decode softmax: per token (lane) and per head (sublane row
    block of Mp rows), normalized over that head's latents. Latent-pad rows
    arrive as NEG_INF in ``s`` and get exactly zero weight. Fully-masked
    token columns (N padding) come out uniform-finite, never NaN."""
    parts = []
    for p in range(cfg.pack):
        seg = s[p * cfg.mp:(p + 1) * cfg.mp, :]          # [Mp, bn]
        mseg = jnp.max(seg, axis=0)                      # [bn]
        eseg = jnp.exp(seg - mseg[None, :])
        parts.append(eseg / jnp.sum(eseg, axis=0)[None, :])
    return parts[0] if cfg.pack == 1 else jnp.concatenate(parts, axis=0)


def _lanes(x: jax.Array, width: int) -> jax.Array:
    """[S, 128] lane-replicated row statistic -> [S, width], every lane
    holding its row's value: whole copies of the 128 lanes, sliced when
    ``width`` is not a multiple of 128 (a tile of 208 or of 16 tokens)."""
    reps = -(-width // LANE)
    x = x if reps == 1 else jnp.tile(x, (1, reps))
    return x if width == reps * LANE else x[:, :width]


def _sweep(cfg: _PackedCfg, n_idx, n_blocks: int, body) -> None:
    """Run ``body(masked)`` for this grid step of an N sweep. Only the last
    block can hold token padding, so ``masked`` is True there (and only when
    N carries padding); every other block runs the body without the mask.
    A masked body is always the last block's, whose index is static."""
    if cfg.n_valid is None:
        body(False)
        return
    last = n_idx == n_blocks - 1
    pl.when(last)(lambda: body(True))
    pl.when(jnp.logical_not(last))(lambda: body(False))


def _note_masked_share(cfg: _PackedCfg, n_blocks: int) -> None:
    """Gauge the share of each sweep's blocks that build the token mask."""
    REGISTRY.gauge(
        "flare_packed.masked_block_share",
        "share of a packed sweep's N blocks that build the token-padding mask",
    ).set(1.0 / n_blocks if cfg.n_valid is not None else 0.0)


# ---------------------------------------------------------------------------
# Forward kernel: encode sweep (phase 0) then decode sweep (phase 1)
# ---------------------------------------------------------------------------


def _fused_fwd_kernel(q_ref, k_ref, v_ref, y_ref, z_ref, mx_ref, den_ref,
                      mx_scr, den_scr, zbd_scr, qbd_scr, *,
                      cfg: _PackedCfg, n_blocks: int):
    phase = pl.program_id(1)
    n_idx = pl.program_id(2)
    last = n_blocks - 1

    @pl.when(jnp.logical_and(phase == 0, n_idx == 0))
    def _init():
        # per-group invariants, read by every later step of the group
        bd = _bd_mask(cfg, q_ref.shape[-1])
        qbd_scr[...] = _expand_block_diag(cfg, q_ref[0], bd)  # input dtype
        mx_scr[...] = jnp.full_like(mx_scr, NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        zbd_scr[...] = jnp.zeros_like(zbd_scr)

    @pl.when(phase == 0)
    def _encode():
        def step(masked):
            k = k_ref[0]
            v = v_ref[0]
            s = _scores(cfg, qbd_scr[...], k, last, token_mask=masked)  # [S, bn]
            m_prev = mx_scr[...]                                       # [S, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, s.shape[1]))
            if masked:
                p = jnp.where(_token_ok(cfg, s.shape, last), p, 0.0)
            den_scr[...] = den_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            zbd_scr[...] = zbd_scr[...] * _lanes(alpha, zbd_scr.shape[1]) + \
                jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            mx_scr[...] = m_new

        _sweep(cfg, n_idx, n_blocks, step)

        @pl.when(n_idx == last)
        def _finish_encode():
            # the numerator turns into Z_bd in place
            wl = zbd_scr.shape[1]
            zbd = jnp.where(_bd_mask(cfg, wl),
                            zbd_scr[...] / _lanes(den_scr[...], wl), 0.0)
            zbd_scr[...] = zbd
            z_ref[0] = _compact_block_diag(cfg, zbd)
            mx_ref[0] = mx_scr[...].T[0:1, :]
            den_ref[0] = den_scr[...].T[0:1, :]

    @pl.when(phase == 1)
    def _decode():
        def step(masked):
            s = _scores(cfg, qbd_scr[...], k_ref[0], last, token_mask=masked)
            w = _decode_weights(cfg, s)                      # [S, bn]
            # y[n, c] = sum_s w[s, n] * Z_bd[s, c] — contraction over sublanes
            y = jax.lax.dot_general(w, zbd_scr[...], (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            y_ref[0] = y.astype(y_ref.dtype)

        _sweep(cfg, n_idx, n_blocks, step)


def _fwd_launch(cfg: _PackedCfg, gh: int, q_p, k_p, v_p):
    g, np_, wl = k_p.shape
    s_rows = cfg.pack * cfg.mp
    n_blocks = np_ // cfg.block_n
    bn = cfg.block_n
    mp = cfg.mp
    grid = (g, 2, n_blocks)
    kernel = functools.partial(_fused_fwd_kernel, cfg=cfg, n_blocks=n_blocks)
    _note_masked_share(cfg, n_blocks)
    # ``name`` also opens jax.named_scope(name) around the launch, so the
    # instruction and its op_name carry it
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # latent queries: one [Mp, Wl] block per packed head group,
            # shared across the batch through the index_map (never
            # broadcast to [B, ...] in HBM)
            pl.BlockSpec((1, mp, wl), lambda g_, p_, n_: (g_ % gh, 0, 0)),
            # K streams in both phases; V only during encode (constant
            # index during decode — the pipeline re-fetches nothing)
            pl.BlockSpec((1, bn, wl), lambda g_, p_, n_: (g_, n_, 0)),
            pl.BlockSpec((1, bn, wl), lambda g_, p_, n_: (g_, (1 - p_) * n_, 0)),
        ],
        out_specs=[
            # y is only written during decode; during encode the out index
            # pins to block 0, which decode's first step overwrites before
            # any flush can happen
            pl.BlockSpec((1, bn, wl), lambda g_, p_, n_: (g_, p_ * n_, 0)),
            pl.BlockSpec((1, mp, wl), lambda g_, p_, n_: (g_, 0, 0)),
            pl.BlockSpec((1, 1, s_rows), lambda g_, p_, n_: (g_, 0, 0)),
            pl.BlockSpec((1, 1, s_rows), lambda g_, p_, n_: (g_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, np_, wl), v_p.dtype),       # y
            jax.ShapeDtypeStruct((g, mp, wl), jnp.float32),      # Z (compact)
            jax.ShapeDtypeStruct((g, 1, s_rows), jnp.float32),   # encode max
            jax.ShapeDtypeStruct((g, 1, s_rows), jnp.float32),   # encode den
        ],
        scratch_shapes=[
            _vmem((s_rows, LANE), jnp.float32),   # running max (lane-replicated)
            _vmem((s_rows, LANE), jnp.float32),   # running denominator (same)
            _vmem((s_rows, wl), jnp.float32),     # running numerator, then
                                                  # Z block-diagonal (lives
                                                  # across the phase switch)
            _vmem((s_rows, wl), q_p.dtype),       # block-diagonal Q
        ],
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
        name="flare_packed_fwd",
    )(q_p, k_p, v_p)


# ---------------------------------------------------------------------------
# Backward kernel: dZ sweep (phase 0) then dq/dk/dv sweep (phase 1)
# ---------------------------------------------------------------------------


def _fused_bwd_kernel(q_ref, k_ref, v_ref, z_ref, mx_ref, den_ref, y_ref, dy_ref,
                      dq_ref, dk_ref, dv_ref,
                      dz_scr, dqa_scr, de_scr, mx_scr, den_scr, qbd_scr, zbd_scr,
                      bd_scr, *,
                      cfg: _PackedCfg, n_blocks: int):
    phase = pl.program_id(1)
    n_idx = pl.program_id(2)
    last = n_blocks - 1

    @pl.when(jnp.logical_and(phase == 0, n_idx == 0))
    def _init():
        # per-group invariants, read by every later step of the group
        bd = _bd_mask(cfg, q_ref.shape[-1])
        bd_scr[...] = bd.astype(jnp.float32)
        qbd_scr[...] = _expand_block_diag(cfg, q_ref[0], bd)   # input dtype
        zbd_scr[...] = _expand_block_diag(cfg, z_ref[0], bd)   # saved Z, fp32
        # the saved row statistics, lane-replicated for the grads sweep
        mx_scr[...] = jnp.broadcast_to(mx_ref[0], (LANE, mx_scr.shape[0])).T
        den_scr[...] = jnp.broadcast_to(den_ref[0], (LANE, den_scr.shape[0])).T
        dz_scr[...] = jnp.zeros_like(dz_scr)
        dqa_scr[...] = jnp.zeros_like(dqa_scr)
        de_scr[...] = jnp.zeros_like(de_scr)

    @pl.when(phase == 0)
    def _sweep_dz():
        # dZ_p = sum_n W_p[n, :]^T dy_p[n, :]: recompute the decode weights
        # from K (no [N, M] residual). Cross-head lanes accumulate too and
        # are zeroed once, by the block-diagonal mask, when the sweep ends:
        # the same values as masking every step's term.
        def step(masked):
            dy = dy_ref[0].astype(jnp.float32)
            s = _scores(cfg, qbd_scr[...], k_ref[0], last, token_mask=masked)
            w = _decode_weights(cfg, s)
            dz_scr[...] = dz_scr[...] + jax.lax.dot_general(
                w, dy, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _sweep(cfg, n_idx, n_blocks, step)

        @pl.when(n_idx == last)
        def _finish_dz():
            dz = jnp.where(bd_scr[...] != 0.0, dz_scr[...], 0.0)
            dz_scr[...] = dz
            # flash trick: rowsum(dA ∘ A) == rowsum(dZ ∘ Z) per latent row
            de_scr[...] = jnp.broadcast_to(
                jnp.sum(dz * zbd_scr[...], axis=-1, keepdims=True), de_scr.shape)

    @pl.when(phase == 1)
    def _sweep_grads():
        def step(masked):
            k = k_ref[0]
            v = v_ref[0].astype(jnp.float32)
            y = y_ref[0].astype(jnp.float32)
            dy = dy_ref[0].astype(jnp.float32)
            qbd = qbd_scr[...]
            zbd = zbd_scr[...]
            s = _scores(cfg, qbd, k, last, token_mask=masked)
            # encode weights from saved stats (flash recomputation)
            bn = s.shape[1]
            a = jnp.exp(s - _lanes(mx_scr[...], bn)) / _lanes(den_scr[...], bn)
            if masked:
                a = jnp.where(_token_ok(cfg, s.shape, last), a, 0.0)
            w = _decode_weights(cfg, s)
            # decode softmax VJP (per token, per head segment):
            #   dW[s, n]    = sum_c Z_bd[s, c] dy[n, c]
            #   delta[s, n] = sum_{c in head(s)} dy[n, c] y[n, c]  (== dy·y per
            #                 head — the decode flash trick), broadcast over the
            #                 segment's rows by the block-diagonal indicator
            dw = jax.lax.dot_general(zbd, dy, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            delta = jax.lax.dot_general(bd_scr[...], dy * y,
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            ds_dec = w * (dw - delta)
            # encode softmax VJP: dA = dZ V^T, delta_enc = rowsum(dZ ∘ Z)
            da = jax.lax.dot_general(dz_scr[...], v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds_enc = a * (da - _lanes(de_scr[...], bn))
            ds = ds_enc + ds_dec                              # [S, bn]
            dk_ref[0] = jax.lax.dot_general(
                ds, qbd.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dk_ref.dtype)
            dv_ref[0] = jax.lax.dot_general(
                a, dz_scr[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dv_ref.dtype)
            dqa_scr[...] = dqa_scr[...] + jax.lax.dot_general(
                ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _sweep(cfg, n_idx, n_blocks, step)

        @pl.when(n_idx == last)
        def _finish_dq():
            dq_ref[0] = _compact_block_diag(
                cfg, jnp.where(bd_scr[...] != 0.0, dqa_scr[...], 0.0)
            ).astype(dq_ref.dtype)


def _bwd_launch(cfg: _PackedCfg, gh: int, q_p, k_p, v_p, z, mx, den, y_p, dy_p):
    g, np_, wl = k_p.shape
    s_rows = cfg.pack * cfg.mp
    n_blocks = np_ // cfg.block_n
    bn = cfg.block_n
    mp = cfg.mp
    grid = (g, 2, n_blocks)
    kernel = functools.partial(_fused_bwd_kernel, cfg=cfg, n_blocks=n_blocks)
    _note_masked_share(cfg, n_blocks)
    q_spec = pl.BlockSpec((1, mp, wl), lambda g_, p_, n_: (g_ % gh, 0, 0))
    # streamed [G, Np, Wl] tensors; the ``when`` factor pins the index to
    # block 0 in the phase that does not consume them
    both = pl.BlockSpec((1, bn, wl), lambda g_, p_, n_: (g_, n_, 0))
    ph1 = pl.BlockSpec((1, bn, wl), lambda g_, p_, n_: (g_, p_ * n_, 0))
    per_group = lambda shape: pl.BlockSpec(
        (1,) + shape, lambda g_, p_, n_: (g_,) + (0,) * len(shape))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            q_spec,
            both,                         # k: scores recomputed in both sweeps
            ph1,                          # v: only dA in sweep 2
            per_group((mp, wl)),          # z compact
            per_group((1, s_rows)),       # encode max
            per_group((1, s_rows)),       # encode den
            ph1,                          # y: only delta_dec in sweep 2
            both,                         # dy: dZ in sweep 1, dS_dec in sweep 2
        ],
        out_specs=[
            per_group((mp, wl)),          # dq (written once per group)
            ph1,                          # dk
            ph1,                          # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, mp, wl), q_p.dtype),
            jax.ShapeDtypeStruct((g, np_, wl), k_p.dtype),
            jax.ShapeDtypeStruct((g, np_, wl), v_p.dtype),
        ],
        scratch_shapes=[
            _vmem((s_rows, wl), jnp.float32),   # dZ accumulator
            _vmem((s_rows, wl), jnp.float32),   # dq accumulator
            _vmem((s_rows, LANE), jnp.float32), # delta_enc (lane-replicated)
            _vmem((s_rows, LANE), jnp.float32), # encode max (lane-replicated)
            _vmem((s_rows, LANE), jnp.float32), # encode den (lane-replicated)
            _vmem((s_rows, wl), q_p.dtype),     # block-diagonal Q
            _vmem((s_rows, wl), jnp.float32),   # block-diagonal Z
            _vmem((s_rows, wl), jnp.float32),   # block-diagonal indicator
        ],
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
        name="flare_packed_bwd",
    )(q_p, k_p, v_p, z, mx, den, y_p, dy_p)


# ---------------------------------------------------------------------------
# custom_vjp core: operates on packed [Gh, Mp, Wl] / [G, Np, Wl] arrays.
# Everything outside (pack/pad/unpack) is plain JAX and composes with this.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _packed_core(cfg: _PackedCfg, gh: int, q_p, k_p, v_p):
    y, _, _, _ = _fwd_launch(cfg, gh, q_p, k_p, v_p)
    return y


def _packed_core_fwd(cfg: _PackedCfg, gh: int, q_p, k_p, v_p):
    y, z, mx, den = _fwd_launch(cfg, gh, q_p, k_p, v_p)
    return y, (q_p, k_p, v_p, z, mx, den, y)


def _packed_core_bwd(cfg: _PackedCfg, gh: int, res, dy):
    q_p, k_p, v_p, z, mx, den, y = res
    with scope("flare_packed_bwd"):
        dq_g, dk, dv = _bwd_launch(cfg, gh, q_p, k_p, v_p, z, mx, den, y, dy)
        # latent queries are shared across the batch: reduce the per-group dq
        g, mp, wl = dq_g.shape
        dq = dq_g.reshape(g // gh, gh, mp, wl).sum(axis=0).astype(q_p.dtype)
    return dq, dk, dv


_packed_core.defvjp(_packed_core_fwd, _packed_core_bwd)


# ---------------------------------------------------------------------------
# Public wrapper: [H, M, D] x [B, H, N, D] -> [B, H, N, D]
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pack_heads(x: jax.Array, gh: int, pack: int, wl: int) -> jax.Array:
    """[..., Hp, N, D] -> [..., Gh, N, pack*D] (lane-padded to ``wl``):
    consecutive heads share the lane dimension of one group."""
    *lead, hp, n, d = x.shape
    x = x.reshape(*lead, gh, pack, n, d)
    x = jnp.moveaxis(x, -3, -2)                      # [..., Gh, N, pack, D]
    x = x.reshape(*lead, gh, n, pack * d)
    if wl > pack * d:
        padw = [(0, 0)] * (x.ndim - 1) + [(0, wl - pack * d)]
        x = jnp.pad(x, padw)
    return x


def _unpack_heads(x: jax.Array, pack: int, d: int) -> jax.Array:
    """[..., Gh, N, Wl] -> [..., Gh*pack, N, D]."""
    *lead, gh, n, _ = x.shape
    x = x[..., :pack * d].reshape(*lead, gh, n, pack, d)
    x = jnp.moveaxis(x, -2, -3)                      # [..., Gh, pack, N, D]
    return x.reshape(*lead, gh * pack, n, d)


def _pad_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flare_mixer_packed(
    q: jax.Array,  # [H, M, D] latent queries
    k: jax.Array,  # [B, H, N, D]
    v: jax.Array,  # [B, H, N, D]
    *,
    pack: Optional[int] = None,
    block_n: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Packed-head single-launch FLARE mixer; differentiable (custom VJP)."""
    if interpret is None:
        interpret = not _on_tpu()
    b, h, n, d = k.shape
    m = q.shape[1]
    if pack is None:
        pack = heuristic_pack(h, m, d)
    pack = max(1, min(pack, h))
    gh = -(-h // pack)
    hp = gh * pack
    mp = _round_up(m, 16)
    wl = _round_up(pack * d, LANE)
    bn = min(block_n, _round_up(n, 16))
    np_ = _round_up(n, bn)

    # the wrapper's layout work (head packing, lane and token padding and
    # their inverses) is named apart from the launches in XLA profiles
    with scope("flare_packed.layout"):
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
    y = _packed_core(cfg, gh, qp, kp, vp)            # [B*Gh, Np, Wl]
    with scope("flare_packed.layout"):
        y = _unpack_heads(y.reshape(b, gh, np_, wl), pack, d)
        return y[:, :h, :n, :]
